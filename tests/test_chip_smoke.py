"""chip_smoke.py's contract, as far as a CPU can check it: the rehearsal
mode runs every phase and passes; without the flag, no TPU means a non-zero
exit and no result line — there is no automatic fallback."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SCRIPT, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_rehearsal_mode_passes_and_says_it_proves_nothing(tmp_path):
    record = tmp_path / "rehearsal.json"
    r = _run(["--rehearse-cpu", "--record", str(record)])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    for line in (lines[0], lines[-1]):
        assert "REHEARSAL" in line and "proves nothing about the chip" in line
    assert not _result_lines(r.stdout)        # a rehearsal is not a result
    for phase in ("kernels", "serve", "train"):
        assert f"[{phase}] PASS" in r.stdout
    rec = json.load(open(record))
    assert rec["device"]["platform"] == "cpu"
    assert rec["phases"]["serve"]["compiles"]["compile_s"] > 0
    # the rehearsal places the cache but does not replay XLA:CPU programs
    assert rec["phases"]["serve"]["compiles"]["cache_hits"] == 0
    losses = rec["phases"]["train"]["losses"]
    assert losses[-1] < losses[0]

    # --expect's comparison (the parent is JAX-free, so it loads here):
    # equal values pass whatever the times; a different digest is named
    import importlib.util

    spec = importlib.util.spec_from_file_location("_chip_smoke", SCRIPT)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    warm = json.loads(json.dumps(rec["phases"]))
    warm["serve"]["setup_s"] = 0.0
    assert smoke._differences(warm, rec["phases"]) == []
    warm["serve"]["tokens_sha"] = "0" * 16
    diffs = smoke._differences(warm, rec["phases"])
    assert len(diffs) == 1 and diffs[0].startswith("serve.tokens_sha")


def test_default_mode_without_a_tpu_fails_and_names_the_platform():
    r = _run([])
    assert r.returncode != 0
    assert "JAX found platform 'cpu'" in r.stderr
    assert not _result_lines(r.stdout)
    assert "[serve]" not in r.stdout          # stopped at the first phase


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run([], cwd=tmp_path, script=alone)
    assert r.returncode != 0
    assert not _result_lines(r.stdout)


def test_dryrun_multichip_uses_its_own_devices_when_it_has_enough(
        monkeypatch, capsys):
    """``dryrun_multichip(n)`` runs on the process's real devices when it
    has n of them (this tier has 8 emulated ones) and re-execs onto an
    emulated CPU mesh only when it has fewer — saying which — with the
    caller's compile-cache variable left in the child's environment."""
    import jax

    import __graft_entry__ as entry

    calls = []
    monkeypatch.setattr(entry, "_dryrun_impl", lambda n: calls.append(n))
    entry.dryrun_multichip(2)
    assert calls == [2]
    assert "running on 2 real cpu device(s)" in capsys.readouterr().out

    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen.update(env)

        class Done:
            returncode, stdout, stderr = 0, "", ""
        return Done()

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    n = jax.device_count() + 1
    entry.dryrun_multichip(n)
    assert calls == [2]                       # not in this process
    out = capsys.readouterr().out
    assert "EMULATED CPU mesh" in out and "proves nothing about chips" in out
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert f"--xla_force_host_platform_device_count={n}" in seen["XLA_FLAGS"]
    assert seen["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"
