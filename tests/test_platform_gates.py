"""Platform gates flip with the platform and with nothing else: the
kernels' route decisions are exercised on BOTH branches by mocking the
device, the XLA formulation the gate selects off-chip agrees with the
(interpreted) Pallas kernel, and a selected kernel that throws propagates
— the routed entries have no fallback."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.device as device_mod


class _FakeDev:
    def __init__(self, platform):
        self.platform = platform


@pytest.fixture
def fake_platform(monkeypatch):
    def set_platform(name):
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: [_FakeDev(name)])
    return set_platform


def test_is_tpu_flips_with_platform(fake_platform):
    fake_platform("tpu")
    assert device_mod.is_tpu()
    for other in ("cpu", "gpu", "oneapi"):
        fake_platform(other)
        assert not device_mod.is_tpu()


def test_flash_gate_selects_xla_on_foreign_platform(fake_platform,
                                                    monkeypatch):
    from paddle_tpu.ops.pallas import flash_attention as fa

    # the gate function consults is_tpu -> devices()
    fake_platform("oneapi")
    monkeypatch.setattr(fa, "_last_path", None)
    q = jnp.ones((1, 128, 2, 64), jnp.float32) * 0.1

    from paddle_tpu.tensor import Tensor

    out = fa.flash_attention(
        Tensor._from_value(q), Tensor._from_value(q),
        Tensor._from_value(q))
    val = out[0] if isinstance(out, tuple) else out
    assert np.isfinite(np.asarray(val.numpy())).all()
    assert fa._last_path == "xla"  # foreign platform must not take pallas


def test_fused_rms_gate_flips(fake_platform):
    from paddle_tpu.ops.pallas import fused_rms_norm as frn

    fake_platform("tpu")
    assert frn.use_fused_rms_norm(1024)       # eligible shape on tpu
    assert frn.use_fused_rms_norm(8192)       # the gate's limit
    assert not frn.use_fused_rms_norm(8320)   # past the limit
    assert not frn.use_fused_rms_norm(100)    # ineligible shape anywhere
    fake_platform("oneapi")
    assert not frn.use_fused_rms_norm(1024)   # foreign platform: XLA


def test_fused_rms_block_rows_fit_the_tile_budget():
    """Every width the gate admits gets a block of at most 1 MiB of fp32
    (VMEM at D = 8192 overflowed with a fixed 128 rows), in whole bf16
    sublane tiles."""
    from paddle_tpu.ops.pallas import fused_rms_norm as frn

    for d in range(128, 8192 + 1, 128):
        rows = frn._block_rows(d)
        assert rows % 16 == 0 and rows >= 16
        assert rows * d * 4 <= 1 << 20
    assert frn._block_rows(2048) == 128 and frn._block_rows(8192) == 32


def test_fused_adamw_gate_flips(fake_platform):
    from paddle_tpu.ops.pallas import fused_adamw as fad

    fake_platform("tpu")
    assert fad.use_fused_adamw()
    fake_platform("rocm")
    assert not fad.use_fused_adamw()


def test_rms_norm_xla_path_matches_interpreted_kernel():
    """Numerical contract across the gate: the XLA composition and the
    Pallas kernel (interpret mode — runs on any backend) agree, forward
    and backward, at a row count that pads and with the block size the
    width selects."""
    from paddle_tpu.ops.pallas import fused_rms_norm as frn

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(300, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    ref = frn.rms_ref(x, w, 1e-6)
    pal = frn.rms_norm_pallas(x, w, 1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    def grads(fn):
        return jax.jit(jax.grad(lambda a, b: jnp.sum(fn(a, b) ** 2),
                                argnums=(0, 1)))(x, w)

    for got, want in zip(
            grads(lambda a, b: frn.rms_norm_pallas(a, b, 1e-6, None, True)),
            grads(lambda a, b: frn.rms_ref(a, b, 1e-6))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("where", ["forward", "backward"])
def test_selected_rms_kernel_that_throws_propagates(monkeypatch, where):
    """Raise, not fall back: with the gate selecting the kernel, a failure
    in the forward or in the backward kernel reaches the caller."""
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas import fused_rms_norm as frn

    monkeypatch.setattr(frn, "use_fused_rms_norm", lambda d: True)
    monkeypatch.setattr(frn, "_interpret", True)
    real = pl.pallas_call

    def flaky(kernel, *a, **k):
        if where == "forward" or kernel is frn._bwd_kernel:
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return real(kernel, *a, **k)

    monkeypatch.setattr(frn.pl, "pallas_call", flaky)
    x = jnp.ones((32, 256), jnp.float32)
    w = jnp.ones((256,), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        if where == "forward":
            frn.rms_norm_routed(x, w, 1e-6)
        else:
            jax.grad(lambda a: jnp.sum(frn.rms_norm_routed(a, w, 1e-6)))(x)
    assert frn._last_path == "pallas"   # the gate's choice, not a fallback


def test_fused_adamw_interpret_is_asked_for_not_derived(monkeypatch):
    """Off a TPU the optimizer does NOT quietly switch the kernel to the
    interpreter ("no TPU found" used to become ``interpret=True``): the
    compile error reaches the caller, and interpret mode comes only from
    the module switch a test sets."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.incubate.optimizer import FusedAdamW
    from paddle_tpu.ops.pallas import fused_adamw as fad

    assert fad._interpret is False

    def one_step():
        m = nn.Linear(4, 4)
        o = FusedAdamW(learning_rate=1e-2, parameters=m.parameters())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        (m(x) ** 2).sum().backward()
        o.step()
        return m

    with pytest.raises(ValueError, match="interpret mode"):
        one_step()
    monkeypatch.setattr(fad, "_interpret", True)
    assert np.isfinite(np.asarray(one_step().weight.numpy())).all()
