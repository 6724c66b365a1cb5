"""Zero-stall training hot path: DevicePrefetcher, TrainStep donation
(+ alias-safety audit + NonBlockingStepResult), overlapped ZeRO-3 fetch,
and the stamped compile cache."""

import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.io.dataloader import DataLoader, DevicePrefetcher
from paddle_tpu.io.dataset import Dataset
from paddle_tpu.jit.api import NonBlockingStepResult, TrainStep

warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


class _Seq(Dataset):
    def __len__(self):
        return 10

    def __getitem__(self, i):
        return np.float32(i)


def _batches(it):
    return [np.asarray(b.numpy()).ravel().tolist() for b in it]


# ------------------------------------------------------- DevicePrefetcher


def test_prefetcher_yields_identical_sequence():
    plain = _batches(DataLoader(_Seq(), batch_size=2, shuffle=False))
    for depth in (0, 1, 3):
        pf = DevicePrefetcher(DataLoader(_Seq(), batch_size=2,
                                         shuffle=False), depth=depth)
        assert _batches(pf) == plain, f"depth {depth}"
        assert pf.state_dict() == {"epoch": 1, "offset": 0}


def test_prefetcher_counts_consumed_not_buffered():
    """The state cursor moves with the CONSUMER: with depth 3 the producer
    runs ahead, but abandoning after 2 batches must report offset 2."""
    pf = DevicePrefetcher(DataLoader(_Seq(), batch_size=2, shuffle=False),
                          depth=3)
    it = iter(pf)
    next(it), next(it)
    it.close()  # abandon mid-epoch
    assert pf.state_dict() == {"epoch": 0, "offset": 2}
    # a fresh (non-resumed) iteration starts the epoch over
    assert _batches(pf) == _batches(
        DataLoader(_Seq(), batch_size=2, shuffle=False))


def test_prefetcher_resume_mid_epoch_no_off_by_depth():
    """Satellite regression: checkpoint/resume mid-epoch with prefetch
    depth > 0 replays the identical remaining sequence — the buffered
    (fetched-but-unconsumed) batches must not be skipped."""
    pf = DevicePrefetcher(DataLoader(_Seq(), batch_size=2, shuffle=False),
                          depth=2)
    it = iter(pf)
    seen = [next(it) for _ in range(3)]
    del seen
    state = pf.state_dict()
    assert state == {"epoch": 0, "offset": 3}
    it.close()

    pf2 = DevicePrefetcher(DataLoader(_Seq(), batch_size=2, shuffle=False),
                           depth=2)
    pf2.set_state_dict(state)
    rest = _batches(pf2)
    assert rest == [[6.0, 7.0], [8.0, 9.0]]  # continues at batch 3
    assert pf2.state_dict() == {"epoch": 1, "offset": 0}


def test_prefetcher_checkpoint_manager_roundtrip(tmp_path):
    from paddle_tpu.checkpoint import CheckpointManager

    paddle.seed(0)
    m = nn.Linear(2, 2)
    pf = DevicePrefetcher(DataLoader(_Seq(), batch_size=2, shuffle=False),
                          depth=2)
    it = iter(pf)
    for _ in range(3):
        next(it)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, model=m, dataloader=pf)
    it.close()

    pf2 = DevicePrefetcher(DataLoader(_Seq(), batch_size=2, shuffle=False),
                           depth=2)
    mgr.restore(model=m, dataloader=pf2)
    assert _batches(pf2) == [[6.0, 7.0], [8.0, 9.0]]


def test_prefetcher_propagates_worker_error():
    class Bad(Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            if i >= 2:
                raise ValueError("boom at 2")
            return np.float32(i)

    pf = DevicePrefetcher(DataLoader(Bad(), batch_size=1, shuffle=False),
                          depth=2)
    with pytest.raises(ValueError, match="boom at 2"):
        list(pf)


def test_prefetcher_meters_input_stall():
    from paddle_tpu.observability.train_stall import input_stall_counter

    before = input_stall_counter().value
    list(DevicePrefetcher(DataLoader(_Seq(), batch_size=5), depth=2))
    assert input_stall_counter().value > before  # pops were metered


# ------------------------------------------------- donation + nonblocking


def _build_train(seed=0, **step_kw):
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    optimizer = opt.AdamW(learning_rate=1e-2,
                          parameters=model.parameters())
    mse = nn.MSELoss()
    step = TrainStep(model, lambda m, a, b: mse(m(a), b), optimizer,
                     **step_kw)
    return model, step


def _batch_pair(rng):
    return (paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32)),
            paddle.to_tensor(rng.standard_normal((4, 1)).astype(np.float32)))


def test_donated_losses_bit_identical_and_buffers_reported():
    """Acceptance pin: donation changes residency, never math — and the
    step reports its donated state/input buffers via cache-probe evidence
    (deleted shells + the caller-side input guard)."""
    rng = np.random.default_rng(3)
    batches = [_batch_pair(rng) for _ in range(4)]
    vals = [(x.numpy().copy(), y.numpy().copy()) for x, y in batches]

    _, step_ref = _build_train(seed=7, donate=False)
    ref = [float(step_ref(x, y).numpy()) for x, y in batches]

    _, step_don = _build_train(seed=7, donate=True, donate_inputs=True,
                               nonblocking=True)
    got = [step_don(paddle.to_tensor(x), paddle.to_tensor(y)).loss_value()
           for x, y in vals]
    assert got == ref  # bit-identical, not allclose

    rep = step_don.donation_report()
    assert rep["donate_inputs"] and rep["inputs_guarded"]
    assert 0 in rep["donate_argnums"] and 4 in rep["donate_argnums"]
    # state buffers really were consumed in place (jax deletes donated
    # buffers whether or not the backend aliased them)
    assert rep["state_buffers_deleted_frac"] == 1.0


def test_donated_input_reread_raises():
    rng = np.random.default_rng(4)
    _, step = _build_train(donate_inputs=True, nonblocking=True)
    x, y = _batch_pair(rng)
    step(x, y).loss_value()
    for reuse in (lambda: x.numpy(), lambda: x.shape, lambda: x + 1.0,
                  lambda: y.numpy()):
        with pytest.raises(RuntimeError, match="donated"):
            reuse()


def test_donation_alias_audit_copies_duplicates():
    """step(x, x) would donate the same buffer twice — XLA rejects that at
    execute time; the audit must copy the duplicate leaf (metered)."""
    from paddle_tpu.observability.train_stall import donation_copy_counter

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8))
    optimizer = opt.SGD(learning_rate=1e-2, parameters=model.parameters())
    mse = nn.MSELoss()
    step = TrainStep(model, lambda m, a, b: mse(m(a), b), optimizer,
                     donate_inputs=True, nonblocking=True)
    rng = np.random.default_rng(5)
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    before = donation_copy_counter().value
    loss = step(x, x).loss_value()
    assert np.isfinite(loss)
    assert donation_copy_counter().value == before + 1


def test_gradscaler_skip_on_inf_bit_identical_with_donation(rng):
    """Satellite: scaler counters live in the donated pytree (argnum 7);
    the skip-on-inf round trip must stay bit-identical to the non-donated
    path — scale halves, weights untouched, counters equal."""

    def run(donate):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        optimizer = opt.SGD(learning_rate=1e-2,
                            parameters=model.parameters())
        scaler = paddle.amp.GradScaler(
            init_loss_scaling=2.0 ** 10, decr_every_n_nan_or_inf=1,
            incr_every_n_steps=3)
        mse = nn.MSELoss()
        step = TrainStep(model, lambda m, a, b: mse(m(a), b), optimizer,
                         scaler=scaler, donate=donate)
        r = np.random.default_rng(0)
        x = r.standard_normal((8, 8)).astype(np.float32)
        y = r.standard_normal((8, 1)).astype(np.float32)
        for _ in range(3):
            step(paddle.to_tensor(x), paddle.to_tensor(y))
        step(paddle.to_tensor(np.full((8, 8), 1e38, np.float32)),
             paddle.to_tensor(y))  # inf grads: skip + halve
        extra = step.checkpoint_extra()
        w = model[0].weight.numpy().copy()
        return extra, w, scaler.get_loss_scaling()

    extra_ref, w_ref, scale_ref = run(donate=False)
    extra_don, w_don, scale_don = run(donate=True)
    assert extra_ref == extra_don
    assert scale_ref == scale_don == 2.0 ** 10  # 2**11 halved by the skip
    np.testing.assert_array_equal(w_ref, w_don)


def test_nonblocking_result_defers_and_meters_sync():
    from paddle_tpu.observability.train_stall import sync_stall_counter

    rng = np.random.default_rng(6)
    _, step = _build_train(nonblocking=True)
    res = step(*_batch_pair(rng))
    assert isinstance(res, NonBlockingStepResult)
    assert res.loss.shape == []  # device handle, no sync needed
    before = sync_stall_counter().value
    v = res.loss_value()
    assert np.isfinite(v)
    assert sync_stall_counter().value > before
    assert float(res) == v  # repeat reads are stable


# ------------------------------------------------ ZeRO-3 overlapped fetch


def test_stage3_overlapped_fetch_frontier(monkeypatch):
    """The hook-driven frontier dispatches group k+1 before layer k runs:
    fetches happen in execution order, every group is fetched exactly once,
    and the overlap ratio reports (n-1)/n (group 0 cannot overlap)."""
    from paddle_tpu.distributed import sharding
    from paddle_tpu.observability.train_stall import offload_overlap_gauge

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 4), nn.ReLU(), nn.Linear(4, 4),
                          nn.Linear(4, 4))
    parked_ids = {id(p) for p in model.parameters()}
    fetch_log = []

    monkeypatch.setattr(sharding, "_parked",
                        lambda p: id(p) in parked_ids)

    def fake_fetch(params):
        group = [p for p in params if id(p) in parked_ids]
        if group:
            fetch_log.append([p.name for p in group])
            parked_ids.difference_update(id(p) for p in group)

    monkeypatch.setattr(sharding, "_fetch_group", fake_fetch)
    sharding._wrap_forward_param_fetch(model)

    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    _ = model(x)
    # 3 param groups (the ReLU owns none), fetched in execution order
    names = [p.name for p in model.parameters()]
    assert [n for g in fetch_log for n in g] == names
    assert len(fetch_log) == 3
    assert not parked_ids  # nothing left behind
    assert offload_overlap_gauge().value == pytest.approx(2.0 / 3.0)

    # second forward with nothing parked: no new fetches, same output path
    fetch_log.clear()
    _ = model(x)
    assert fetch_log == []


def test_stage3_overlap_kill_switch(monkeypatch):
    """PADDLE_TPU_OFFLOAD_OVERLAP=0 restores the one-shot entry fetch."""
    from paddle_tpu.distributed import sharding

    monkeypatch.setenv("PADDLE_TPU_OFFLOAD_OVERLAP", "0")
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 4))
    parked_ids = {id(p) for p in model.parameters()}
    calls = []

    monkeypatch.setattr(sharding, "_parked",
                        lambda p: id(p) in parked_ids)

    def fake_fetch(params):
        group = [p for p in params if id(p) in parked_ids]
        calls.append(len(group))
        parked_ids.difference_update(id(p) for p in group)

    monkeypatch.setattr(sharding, "_fetch_group", fake_fetch)
    sharding._wrap_forward_param_fetch(model)
    _ = model(paddle.to_tensor(np.ones((2, 4), np.float32)))
    assert calls == [4]  # ONE batched fetch of all 4 params at entry


# ---------------------------------------------------- stamped compile cache


def _load_compile_cache_module():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "utils",
        "compile_cache.py")
    spec = importlib.util.spec_from_file_location("_cc_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compile_cache_stamp_and_invalidate(tmp_path):
    cc = _load_compile_cache_module()
    d = str(tmp_path / "jax_cache")
    out = cc.ensure_compile_cache_dir(d)
    assert out == d
    stamp = os.path.join(d, cc.STAMP_NAME)
    assert json.load(open(stamp)) == cc.cache_key()

    # matching stamp: entries survive
    entry = os.path.join(d, "xla_program_abc")
    open(entry, "w").write("aot")
    cc.ensure_compile_cache_dir(d)
    assert os.path.exists(entry)

    # stale stamp (older framework/jax build): entries are wiped, restamped
    json.dump({"paddle_tpu": "0.0.0", "jax": "0.0.0", "jaxlib": "0.0.0"},
              open(stamp, "w"))
    open(entry, "w").write("aot")
    cc.ensure_compile_cache_dir(d)
    assert not os.path.exists(entry)
    assert json.load(open(stamp)) == cc.cache_key()

    # corrupt stamp counts as stale, not a crash
    open(stamp, "w").write("{not json")
    cc.ensure_compile_cache_dir(d)
    assert json.load(open(stamp)) == cc.cache_key()


def test_compile_cache_dir_respects_the_callers_directory(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache lives there and
    this code neither stamps nor wipes it; unset, it is the fixed
    <checkout>/build/jax_cache, exported for jax and every child."""
    cc = _load_compile_cache_module()
    theirs = tmp_path / "their_cache"
    theirs.mkdir()
    (theirs / "entry").write_text("aot")
    env = {cc.ENV_VAR: str(theirs)}
    assert cc.compile_cache_dir(env) == str(theirs)
    assert env == {cc.ENV_VAR: str(theirs)}
    assert sorted(os.listdir(theirs)) == ["entry"]   # no stamp, no wipe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    default = os.path.join(repo, "build", "jax_cache")
    assert cc.default_cache_dir() == default
    env = {}
    assert cc.compile_cache_dir(env) == default
    assert env == {cc.ENV_VAR: default}
    assert json.load(open(os.path.join(default, cc.STAMP_NAME))) \
        == cc.cache_key()


# ------------------------------------------------------- loop integrations


def test_hapi_fit_with_device_prefetch():
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.io.dataset import TensorDataset

    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 2)).astype(np.float32))
    net = nn.Linear(4, 2)
    m = Model(net)
    m.prepare(optimizer=opt.SGD(learning_rate=1e-2,
                                parameters=net.parameters()),
              loss=nn.MSELoss())
    m.fit(TensorDataset([x, y]), batch_size=4, epochs=1, verbose=0,
          device_prefetch=2)
    w = net.weight.numpy()
    assert np.all(np.isfinite(w))


def test_engine_fit_dispatch_ahead_history():
    """Engine.fit defers the loss sync to the epoch boundary; the history
    must still be the per-step float losses, identical to the eager-sync
    run of the same seeded setup."""
    from paddle_tpu.distributed.auto_parallel.static_engine import Engine

    def make():
        paddle.seed(0)
        net = nn.Linear(4, 2)
        mse = nn.MSELoss()
        e = Engine(net, loss=lambda out, y: mse(out, y),
                   optimizer=opt.SGD(learning_rate=1e-2,
                                     parameters=net.parameters()))
        rng = np.random.default_rng(0)
        data = [(paddle.to_tensor(rng.standard_normal((4, 4))
                                  .astype(np.float32)),
                 paddle.to_tensor(rng.standard_normal((4, 2))
                                  .astype(np.float32)))
                for _ in range(5)]
        return e, data

    e1, d1 = make()
    h1 = e1.fit(d1, epochs=1)
    e2, d2 = make()
    h2 = e2.fit(d2, epochs=1, device_prefetch=2)
    assert len(h1) == len(h2) == 5
    assert all(isinstance(v, float) for v in h2)
    assert h1 == h2  # prefetch + deferred sync change timing, not math
