"""Where JAX's persistent compilation cache lives — decided in one place.

``compile_cache_dir()`` is the one helper every entry point uses
(``chip_smoke.py``, ``perfbench/run.py``, ``tests/conftest.py``):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache;
  this code does not create, stamp or wipe it, and sets no other;
- where it is not set, the cache is the fixed ``<checkout>/build/jax_cache``
  (the path is part of the cache key, so it never carries a temporary name,
  a pid or a time), and the variable is set so that JAX — imported after
  this call — and every child process use the same directory.

The repo's own default directory is version-stamped: it carries a
``CACHE_KEY.json`` of the framework + jax/jaxlib versions that filled it,
and ``ensure_compile_cache_dir`` wipes the entries when the stamp no longer
matches the running build (a cache filled by an older build once replayed
executables with wrong numerics into the serving tests). Only that default
directory is ever wiped.

Deliberately import-light: no ``jax`` import (versions come from package
metadata), no ``paddle_tpu`` import (the framework version is parsed out of
``paddle_tpu/version/__init__.py`` as text) — a parent process that must not
touch the chip, or a conftest that has not pinned its platform yet, loads
this file with ``importlib.util.spec_from_file_location``.
"""

from __future__ import annotations

import json
import os
import re

STAMP_NAME = "CACHE_KEY.json"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/build/jax_cache``."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, "build", "jax_cache")


def compile_cache_dir(env=None) -> str:
    """The cache directory for this process and its children (module
    docstring). ``env`` is the mapping to read and update — ``os.environ``
    by default, or the environment being built for a child."""
    env = os.environ if env is None else env
    if not env.get(ENV_VAR):
        env[ENV_VAR] = ensure_compile_cache_dir(default_cache_dir())
    return env[ENV_VAR]


def _framework_version() -> str:
    version_py = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "version", "__init__.py")
    try:
        with open(version_py) as f:
            m = re.search(r"full_version\s*=\s*['\"]([^'\"]+)['\"]", f.read())
        return m.group(1) if m else "unknown"
    except OSError:
        return "unknown"


def _dist_version(name: str) -> str:
    try:
        import importlib.metadata as md

        return md.version(name)
    except Exception:
        return "unknown"


def cache_key() -> dict:
    """The stamp contents: every component whose change can invalidate a
    serialized XLA executable for our purposes."""
    return {
        "paddle_tpu": _framework_version(),
        "jax": _dist_version("jax"),
        "jaxlib": _dist_version("jaxlib"),
    }


def ensure_compile_cache_dir(path: str) -> str:
    """Create/validate ``path`` as a stamped compilation cache dir — the
    repo's own default directory (``compile_cache_dir``), never one the
    caller's environment named.

    A missing or mismatching ``CACHE_KEY.json`` wipes every cache entry in
    the directory and writes a fresh stamp, so stale AOT replays from an
    older build can never poison a run. Returns ``path`` (always usable),
    or the path unchanged if the directory cannot be created (read-only
    checkouts degrade to jax's no-persistent-cache behavior).
    """
    key = cache_key()
    stamp_path = os.path.join(path, STAMP_NAME)
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return path
    stale = True
    try:
        with open(stamp_path) as f:
            stale = json.load(f) != key
    except (OSError, ValueError):
        stale = True
    if stale:
        for name in os.listdir(path):
            if name == STAMP_NAME:
                continue
            full = os.path.join(path, name)
            try:
                if os.path.isdir(full):
                    import shutil

                    shutil.rmtree(full, ignore_errors=True)
                else:
                    os.unlink(full)
            except OSError:
                pass  # a concurrently-held entry; jax will overwrite it
        tmp = stamp_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(key, f, indent=1, sort_keys=True)
            os.replace(tmp, stamp_path)
        except OSError:
            pass
    return path
