"""paddle.version parity (reference: the version module setup.py:443-530
generates into python/paddle/version/__init__.py).

The accelerator fields are TPU-native: ``cuda()``/``cudnn()`` report
'False' (the reference's own spelling for a build without that stack),
and ``xpu()`` is joined by ``tpu()`` reporting the attached TPU-class
platform via PJRT.
"""

from __future__ import annotations

import subprocess

full_version = "0.1.0"
major = "0"
minor = "1"
patch = "0"
rc = "0"
istaged = False
with_pip_cuda_libraries = "OFF"


def _git_commit():
    try:
        import os

        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        # only trust git when `root` IS the repo toplevel containing this
        # package: an installed copy sitting inside some unrelated repo
        # must not report that repo's HEAD as the build commit
        top = subprocess.run(["git", "-C", root, "rev-parse",
                              "--show-toplevel"],
                             capture_output=True, text=True, timeout=5)
        if top.returncode != 0 or os.path.realpath(
                top.stdout.strip()) != os.path.realpath(root):
            return "Unknown"
        out = subprocess.run(["git", "-C", root, "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=5)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass  # no git / not a checkout / timed out: report Unknown
    return "Unknown"


_commit_cache = None


def _commit():
    global _commit_cache
    if _commit_cache is None:
        _commit_cache = _git_commit()
    return _commit_cache


def __getattr__(name):
    # `commit` resolves lazily (PEP 562): a git subprocess on EVERY import
    # would tax interpreter start (and can stall on wedged repos). NOTE:
    # in-module code must call _commit() — module __getattr__ does not
    # intercept global lookups.
    if name == "commit":
        return _commit()
    raise AttributeError(name)


def show():
    """Print the tagged version (or commit id) plus accelerator info —
    reference setup.py:462 show()."""
    if istaged:
        print("full_version:", full_version)
        print("major:", major)
        print("minor:", minor)
        print("patch:", patch)
        print("rc:", rc)
    else:
        print("commit:", _commit())
    print("cuda:", cuda())
    print("cudnn:", cudnn())
    print("tpu:", tpu())


def mkl():
    return "OFF"


def cuda():
    """'False' — this is a TPU-native build (reference spelling for a
    CUDA-less build)."""
    return "False"


def cudnn():
    return "False"


def xpu():
    return "False"


def xpu_xccl():
    return "False"


def xpu_xhpc():
    return "False"


def nccl():
    return "0"


def tpu():
    """``"tpu"`` when a chip is attached, else ``"False"`` (non-reference
    extension — this build's accelerator)."""
    from paddle_tpu.device import is_tpu

    return "tpu" if is_tpu() else "False"
