"""Fault-tolerant checkpoint manager (paddle_tpu/checkpoint/).

The guarantees under test:

- atomic commit: a simulated kill between shard write and commit, or
  between rename and marker, leaves ``latest()`` at the PREVIOUS commit,
  which loads bit-identical full train state (params + optimizer + RNG +
  step);
- integrity: a bit-flipped shard is caught by the manifest crc32 and
  skipped, falling back to the previous commit;
- full-state round trips, including save -> reshard (dp<->mp layouts) ->
  load bit-identity for params, optimizer slots, and the RNG stream;
- async snapshot-then-write: backpressure (one writer in flight), and the
  atexit flush that makes ``save_state_dict(async_save=True)`` + process
  exit durable (regression: in-flight writes used to be droppable);
- retention GC (keep-last-N + keep-every-K), persistables wrappers,
  elastic resume-step reporting, dataloader position resume, hapi fit
  auto-resume, serving weight hot-reload, checkpoint.* metrics.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.checkpoint import (
    CheckpointManager,
    SimulatedCrash,
    is_committed,
    read_manifest,
    verify_dir,
)
from paddle_tpu.framework import random as frand

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_train(seed=5, lr=0.01):
    paddle.seed(seed)
    m = nn.Linear(4, 3)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=lr)
    return m, opt


def _step(m, opt, x):
    loss = (m(x) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss)


def _assert_full_state_equal(m1, opt1, m2, opt2):
    for (k1, t1), (k2, t2) in zip(sorted(m1.state_dict().items()),
                                  sorted(m2.state_dict().items())):
        assert k1 == k2
        np.testing.assert_array_equal(t1.numpy(), t2.numpy())
    for p1, p2 in zip(opt1._parameter_list, opt2._parameter_list):
        s1, s2 = opt1._state[id(p1)], opt2._state[id(p2)]
        assert set(s1) == set(s2)
        for k in s1:
            np.testing.assert_array_equal(np.asarray(s1[k]),
                                          np.asarray(s2[k]))
    assert opt1._step_count == opt2._step_count


# ------------------------------------------------------------ commit protocol

def test_atomic_commit_layout_and_roundtrip(tmp_path, rng):
    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path), keep_last_n=4)
    path = mgr.save(1, model=m, optimizer=opt)
    assert os.path.basename(path) == "step_1"
    assert is_committed(path)
    man = read_manifest(path)
    assert man["step"] == 1 and man["files"]
    for meta in man["files"].values():
        assert meta["size"] > 0 and "crc32" in meta
    ok, problems = verify_dir(path)
    assert ok, problems

    m2, opt2 = _make_train(seed=99)
    res = mgr.restore(model=m2, optimizer=opt2)
    assert res.step == 1
    _assert_full_state_equal(m, opt, m2, opt2)


def test_kill_between_write_and_commit_falls_back(tmp_path, rng):
    """ISSUE acceptance: simulated kill between shard write and commit ->
    latest() returns the previous checkpoint, loading bit-identical."""
    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, optimizer=opt)
    want_params = {k: t.numpy().copy() for k, t in m.state_dict().items()}
    want_rng = frand.rng_state_to_host()

    _step(m, opt, x)  # state moves on; the next save will die
    mgr._fail_point = "before_commit"
    with pytest.raises(SimulatedCrash):
        mgr.save(2, model=m, optimizer=opt)
    # step_2 must be invisible: only a torn tmp dir may exist
    assert not os.path.isdir(mgr.step_dir(2))
    info = mgr.latest()
    assert info is not None and info.step == 1

    # a NEW manager (fresh process after the crash) sees the same commit
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.latest().step == 1
    m2, opt2 = _make_train(seed=123)
    res = mgr2.restore(model=m2, optimizer=opt2)
    assert res.step == 1 and res.extra["step"] == 1
    for k, t in m2.state_dict().items():
        np.testing.assert_array_equal(t.numpy(), want_params[k])
    assert frand.rng_state_to_host() == want_rng  # RNG restored to commit 1

    # the manager recovers: the next save commits normally
    mgr2.save(2, model=m2, optimizer=opt2)
    assert mgr2.latest().step == 2


def test_kill_between_rename_and_marker_falls_back(tmp_path, rng):
    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, optimizer=opt)
    mgr._fail_point = "before_marker"
    with pytest.raises(SimulatedCrash):
        mgr.save(2, model=m, optimizer=opt)
    # renamed dir exists but carries no COMMITTED marker -> skipped
    assert os.path.isdir(mgr.step_dir(2)) and not is_committed(
        mgr.step_dir(2))
    assert mgr.latest().step == 1


@pytest.mark.parametrize("site", ["ckpt.shard_write", "ckpt.manifest_write",
                                  "ckpt.rename"])
def test_injected_fault_during_save_falls_back(tmp_path, rng, site):
    """Chaos drill over every write-path injection site: a fault at shard
    fsync, MANIFEST write, or the commit rename must leave step 1 as the
    newest committed checkpoint, and a fresh manager must recover and
    commit normally afterwards — the same contract the SimulatedCrash
    fail-point tests pin, now reachable from a seeded FaultPlan."""
    from paddle_tpu.resilience import FaultPlan, InjectedFault, fault_plan

    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, optimizer=opt)

    _step(m, opt, x)
    with fault_plan(FaultPlan(seed=0).on(site, at=1, kind="fatal")):
        with pytest.raises(InjectedFault):
            mgr.save(2, model=m, optimizer=opt)
    # step_2 must be invisible: absent entirely, or present uncommitted
    assert not (os.path.isdir(mgr.step_dir(2))
                and is_committed(mgr.step_dir(2)))
    assert mgr.latest().step == 1

    # a NEW manager (fresh process after the fault) recovers and commits
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.latest().step == 1
    mgr2.save(2, model=m, optimizer=opt)
    assert mgr2.latest().step == 2 and is_committed(mgr2.step_dir(2))


def test_bit_flipped_shard_detected_and_skipped(tmp_path, rng):
    """ISSUE acceptance: a bit-flipped shard file leaves latest() at the
    previous commit (crc32 mismatch), which loads bit-identical."""
    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, optimizer=opt)
    want = {k: t.numpy().copy() for k, t in m.state_dict().items()}
    _step(m, opt, x)
    mgr.save(2, model=m, optimizer=opt)

    shard = next(f for f in os.listdir(mgr.step_dir(2))
                 if f.startswith("model.weight"))
    p = os.path.join(mgr.step_dir(2), shard)
    blob = bytearray(open(p, "rb").read())
    blob[-1] ^= 0x01  # single bit flip in the payload tail
    open(p, "wb").write(bytes(blob))

    with pytest.warns(UserWarning, match="failed verification"):
        info = mgr.latest()
    assert info.step == 1
    # quick (size-only) verification can NOT see it; full crc does
    assert mgr.latest(verify="quick").step == 2
    m2, opt2 = _make_train(seed=42)
    mgr.restore(step=1, model=m2, optimizer=opt2)
    for k, t in m2.state_dict().items():
        np.testing.assert_array_equal(t.numpy(), want[k])


def test_corrupt_metric_counts(tmp_path, rng):
    from paddle_tpu.observability import get_registry

    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m)
    reg = get_registry()
    saves0 = reg.get("checkpoint_saves_total").value
    corrupt0 = reg.get("checkpoint_corrupt_skipped_total").value
    mgr.save(2, model=m)
    os.remove(os.path.join(
        mgr.step_dir(2),
        next(f for f in os.listdir(mgr.step_dir(2))
             if f.endswith(".distcp"))))
    with pytest.warns(UserWarning):
        assert mgr.latest(verify="quick").step == 1
    assert reg.get("checkpoint_saves_total").value == saves0 + 1
    assert reg.get("checkpoint_corrupt_skipped_total").value == corrupt0 + 1


# ----------------------------------------------------------- async + atexit

def test_async_backpressure_single_writer(tmp_path, rng):
    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path), keep_last_n=8)
    for s in range(1, 4):
        mgr.save(s, model=m, optimizer=opt, async_save=True)
    mgr.wait()
    assert mgr.all_steps() == [1, 2, 3]
    ok, problems = verify_dir(mgr.step_dir(3))
    assert ok, problems


def test_async_writer_error_surfaces_on_wait(tmp_path, rng):
    m, opt = _make_train()
    mgr = CheckpointManager(str(tmp_path))
    mgr._fail_point = "before_commit"
    mgr.save(1, model=m, async_save=True)
    with pytest.raises(SimulatedCrash):
        mgr.wait()
    assert mgr.latest() is None


def test_async_save_state_dict_atexit_flush(tmp_path):
    """Regression (satellite): async_save=True followed by plain process
    exit must not drop in-flight shard writes — the atexit hook flushes."""
    code = f"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

sd = {{"w": paddle.to_tensor(np.arange(32.0, dtype=np.float32))}}
dist.save_state_dict(sd, {str(tmp_path)!r}, async_save=True)
# exit WITHOUT wait_async_save(): atexit must flush the daemon writer
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    import paddle_tpu.distributed as dist

    sd2 = {"w": paddle.to_tensor(np.zeros(32, np.float32))}
    dist.load_state_dict(sd2, str(tmp_path))
    np.testing.assert_array_equal(sd2["w"].numpy(),
                                  np.arange(32.0, dtype=np.float32))


# ----------------------------------------------------- reshard round trips

@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 virtual devices")
def test_full_state_reshard_dp_mp_roundtrip(tmp_path):
    """Satellite: save -> reshard (dp<->mp layouts) -> load bit-identical
    for params, optimizer slots, and RNG state."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh_dp = Mesh(np.asarray(jax.devices()).reshape(8), ("dp",))
    mesh_mp = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("dp", "mp"))
    vals = np.arange(64.0, dtype=np.float32).reshape(8, 8)

    paddle.seed(31)
    p = paddle.Tensor._from_value(
        jax.device_put(vals, NamedSharding(mesh_dp, P("dp"))))
    p.trainable = True
    opt = paddle.optimizer.AdamW(parameters=[p], learning_rate=0.01)
    # materialize sharded moments, then step so they are nonzero
    p._grad = jax.device_put(vals * 0.5, NamedSharding(mesh_dp, P("dp")))
    opt.step()
    want_p = np.asarray(p._value)
    want_m1 = np.asarray(opt._state[id(p)]["moment1"])
    frand.seed(7)
    _ = frand.next_key()
    want_rng = frand.rng_state_to_host()

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(10, state={"p": p}, optimizer=opt)

    # fresh target in the OTHER layout (mp-split on both axes)
    p2 = paddle.Tensor._from_value(
        jax.device_put(np.zeros((8, 8), np.float32),
                       NamedSharding(mesh_mp, P("dp", "mp"))))
    p2.trainable = True
    opt2 = paddle.optimizer.AdamW(parameters=[p2], learning_rate=0.01)
    frand.seed(0)  # clobber, restore must bring back want_rng
    res = mgr.restore(state={"p": p2}, optimizer=opt2)
    assert res.step == 10
    np.testing.assert_array_equal(np.asarray(p2._value), want_p)
    assert p2._value.sharding.spec == P("dp", "mp")  # target layout kept
    np.testing.assert_array_equal(
        np.asarray(opt2._state[id(p2)]["moment1"]), want_m1)
    assert frand.rng_state_to_host() == want_rng
    # optimizer slots inherit the checkpointed (replicated-save) layout,
    # values bit-identical regardless of source dp sharding
    np.testing.assert_array_equal(
        np.asarray(opt2._state[id(p2)]["moment2"]),
        np.asarray(opt._state[id(p)]["moment2"]))


# ------------------------------------------------------------------ retention

def test_retention_keep_last_and_every_k(tmp_path, rng):
    m, _ = _make_train()
    mgr = CheckpointManager(str(tmp_path), keep_last_n=2, keep_every_k=5)
    for s in range(1, 13):
        mgr.save(s, model=m)
    assert mgr.all_steps() == [5, 10, 11, 12]
    # orphan tmp dirs are swept by gc
    os.makedirs(os.path.join(str(tmp_path), "step_99.tmp"))
    mgr.gc()
    assert not os.path.isdir(os.path.join(str(tmp_path), "step_99.tmp"))


# ------------------------------------------------------------- integrations

def test_trainstep_full_resume_bit_identical(tmp_path, rng):
    from paddle_tpu.jit import TrainStep

    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((8, 3)).astype(np.float32))

    def make():
        m, opt = _make_train(seed=5)
        return m, opt, TrainStep(
            m, lambda mod, a, b: ((mod(a) - b) ** 2).mean(), opt)

    m, opt, ts = make()
    for _ in range(2):
        ts(x, y)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, train_step=ts, async_save=True)
    mgr.wait()
    after = [float(ts(x, y)) for _ in range(2)]

    m2, opt2, ts2 = make()
    res = CheckpointManager(str(tmp_path)).restore(train_step=ts2)
    assert res.step == 2
    resumed = [float(ts2(x, y)) for _ in range(2)]
    assert after == resumed  # bit-identical continuation


def test_lr_scheduler_roundtrip(tmp_path, rng):
    m, _ = _make_train()
    sched = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2,
                                          gamma=0.5)
    opt = paddle.optimizer.SGD(parameters=m.parameters(),
                               learning_rate=sched)
    x = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))
    for _ in range(3):
        _step(m, opt, x)
        sched.step()
    want_lr = opt.get_lr()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, model=m, optimizer=opt)

    m2, _ = _make_train(seed=8)
    sched2 = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2,
                                           gamma=0.5)
    opt2 = paddle.optimizer.SGD(parameters=m2.parameters(),
                                learning_rate=sched2)
    mgr.restore(model=m2, optimizer=opt2)
    assert opt2.get_lr() == want_lr
    assert sched2.last_epoch == sched.last_epoch


def test_dataloader_position_roundtrip(tmp_path):
    import paddle_tpu.io as pio

    class DS(pio.Dataset):
        def __len__(self):
            return 10

        def __getitem__(self, i):
            return np.float32(i)

    dl = pio.DataLoader(DS(), batch_size=2, shuffle=False)
    it = iter(dl)
    for _ in range(3):
        next(it)
    mgr = CheckpointManager(str(tmp_path))
    m, _ = _make_train()
    mgr.save(1, model=m, dataloader=dl)

    dl2 = pio.DataLoader(DS(), batch_size=2, shuffle=False)
    mgr.restore(model=m, dataloader=dl2)
    rest = [b.numpy().tolist() for b in dl2]
    assert rest == [[6.0, 7.0], [8.0, 9.0]]  # continues at batch 3
    assert dl2.state_dict() == {"epoch": 1, "offset": 0}  # epoch rolled


def test_persistables_wrappers_roundtrip(tmp_path):
    import paddle_tpu.distributed.io as dio
    from paddle_tpu import static

    prog = static.Program()
    prog.scope["w"] = np.arange(6.0, dtype=np.float32).reshape(2, 3)
    prog.scope["b"] = np.full(3, 5.0, np.float32)
    dio.save_persistables(None, str(tmp_path), prog)
    prog.scope["w"] = np.zeros((2, 3), np.float32)
    prog.scope["b"] = np.zeros(3, np.float32)
    dio.load_persistables(None, str(tmp_path), prog)
    np.testing.assert_allclose(np.asarray(prog.scope["w"]),
                               np.arange(6.0).reshape(2, 3))
    np.testing.assert_allclose(np.asarray(prog.scope["b"]), 5.0)
    # repeated saves bump the step; retention keeps the latest
    dio.save_persistables(None, str(tmp_path), prog)
    assert CheckpointManager(str(tmp_path)).latest(verify=False).step == 1


def test_elastic_reports_last_committed_step(tmp_path, monkeypatch):
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.store import create_or_get_global_tcp_store

    monkeypatch.setenv("PADDLE_ELASTIC_NP", "1:2")
    store = create_or_get_global_tcp_store()
    m, _ = _make_train()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(41, model=m)
    mgr.save(42, model=m)
    em = ElasticManager(store=store, heartbeat_interval=10.0)
    em.attach_checkpoint(mgr)
    assert em.last_committed_step() == 42
    # the restarted generation reads the published step without a manager
    em2 = ElasticManager(store=store, heartbeat_interval=10.0)
    assert em2.resume_step() == 42
    # a torn newest checkpoint rolls the report back
    os.remove(os.path.join(mgr.step_dir(42), "COMMITTED"))
    assert em.last_committed_step() == 41
    em.stop()
    em2.stop()


def test_hapi_fit_auto_resume(tmp_path):
    X = np.random.default_rng(3).standard_normal((16, 3)).astype(np.float32)
    Y = (X @ np.ones((3, 1))).astype(np.float32)

    import paddle_tpu.io as pio

    class DS(pio.Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return X[i], Y[i]

    def make():
        net = nn.Linear(3, 1)
        mdl = paddle.Model(net)
        mdl.prepare(paddle.optimizer.SGD(parameters=net.parameters(),
                                         learning_rate=0.01), nn.MSELoss())
        return net, mdl

    ck = str(tmp_path)
    net, mdl = make()
    mdl.fit(DS(), epochs=2, batch_size=4, verbose=0, checkpoint_dir=ck)
    assert CheckpointManager(ck).latest().step == 1
    w = net.weight.numpy().copy()
    # second fit resumes past both epochs: weights come from the checkpoint
    net2, mdl2 = make()
    mdl2.fit(DS(), epochs=2, batch_size=4, verbose=0, checkpoint_dir=ck)
    np.testing.assert_array_equal(net2.weight.numpy(), w)


def test_load_preserves_uncommitted_arrays(tmp_path, rng):
    """Serving hot-reload guarantee: loading into an UNcommitted param must
    not return a committed array — jit cache keys differ on committedness,
    so a device_put here would silently recompile every program using the
    weight (pinned end-to-end by the round-8 verify driver)."""
    import paddle_tpu.distributed as dist

    t = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))
    assert not t._value.committed
    dist.save_state_dict({"w": t}, str(tmp_path))
    t2 = paddle.to_tensor(np.zeros((4, 4), np.float32))
    dist.load_state_dict({"w": t2}, str(tmp_path))
    np.testing.assert_array_equal(t2.numpy(), t.numpy())
    assert not t2._value.committed


def test_metrics_and_spans_exposed(tmp_path, rng):
    from paddle_tpu.observability import get_registry

    m, opt = _make_train()
    x = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))
    _step(m, opt, x)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, optimizer=opt)
    m2, opt2 = _make_train(seed=77)
    mgr.restore(model=m2, optimizer=opt2)
    snap = get_registry().snapshot()
    for key in ("checkpoint_saves_total", "checkpoint_commits_total",
                "checkpoint_restores_total", "checkpoint_bytes_written_total",
                "checkpoint_save_seconds", "checkpoint_snapshot_seconds",
                "checkpoint_restore_seconds"):
        assert key in snap, key
    assert snap["checkpoint_bytes_written_total"] > 0
    assert "checkpoint_saves_total" in get_registry().prometheus_text()
