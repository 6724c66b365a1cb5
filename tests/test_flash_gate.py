"""Pin the Pallas platform gates and that a selected kernel never falls back.

The predicate is ``device.is_tpu``: platform ``"tpu"`` and nothing else, and
it does not swallow a backend error. The gates decide once, from platform
and shape; a kernel that was selected and then throws propagates — there is
no XLA fallback behind a warning.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import device as pdev
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_adamw, fused_rms_norm


class _FakeDev:
    def __init__(self, platform):
        self.platform = platform


def _fake(monkeypatch, platform):
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeDev(platform)])


def test_is_tpu_is_platform_tpu_only():
    assert pdev.is_tpu(_FakeDev("tpu"))
    for platform in ("cpu", "gpu", "cuda", "rocm", "TPU", "tpu-like"):
        assert not pdev.is_tpu(_FakeDev(platform))
    assert not hasattr(pdev, "is_tpu_like")
    assert not hasattr(pdev, "is_tpu_like_platform")


def test_is_tpu_does_not_swallow_backend_errors(monkeypatch):
    """A chip that is busy or fails to initialise must not read as "no
    TPU" and silently select the XLA path everywhere."""
    def boom(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pdev.is_tpu()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        fa._use_pallas((2, 1024, 12, 64), 64)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        fused_adamw.use_fused_adamw()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        fused_rms_norm.use_fused_rms_norm(1024)


def test_use_pallas_selected_on_tpu(monkeypatch):
    _fake(monkeypatch, "tpu")
    # block-divisible GPT-ish shape: batch 2, seq 1024, heads 12, dim 64
    assert fa._use_pallas((2, 1024, 12, 64), 64)
    assert not fa._use_pallas((2, 1000, 12, 64), 64)   # seq % 128 != 0
    assert not fa._use_pallas((2, 1024, 12, 48), 48)   # odd head_dim


def test_use_pallas_rejected_off_tpu(monkeypatch):
    for platform in ("cpu", "gpu"):
        _fake(monkeypatch, platform)
        assert not fa._use_pallas((2, 1024, 12, 64), 64)


def test_fused_adamw_gate_uses_shared_predicate(monkeypatch):
    _fake(monkeypatch, "tpu")
    assert fused_adamw.use_fused_adamw()
    _fake(monkeypatch, "cpu")
    assert not fused_adamw.use_fused_adamw()


def test_flash_fwd_records_selected_path():
    """On the CPU test platform the gate selects the XLA path and records
    it; ``chip_smoke.py`` asserts ``_last_path == "pallas"`` on the real
    chip via the same hook."""
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    fa.flash_attention_fwd(q, q, q)
    assert fa._last_path == "xla"


def test_selected_flash_kernel_that_throws_propagates(monkeypatch):
    """Raise, not fall back: with the gate selecting the kernel, a kernel
    failure reaches the caller instead of being replaced by the XLA path."""
    from paddle_tpu.ops.pallas import flash_attention_tpu as ker

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    _fake(monkeypatch, "tpu")
    monkeypatch.setattr(ker, "flash_attention", boom)
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fa.flash_attention_fwd(q, q, q)
    assert not hasattr(fa, "_warn_kernel_fallback")


def test_selected_splash_kernel_that_throws_propagates(monkeypatch, rng):
    import paddle_tpu as paddle

    def boom(*a, **k):
        raise RuntimeError("splash kernel exceeded VMEM")

    _fake(monkeypatch, "tpu")
    monkeypatch.setattr(fa, "_splash_varlen", boom)
    T, H, D = 128, 2, 64
    q = paddle.to_tensor(rng.normal(size=(T, H, D)).astype(np.float32))
    cu = paddle.to_tensor(np.asarray([0, 50, 128], np.int32))
    with pytest.raises(RuntimeError, match="exceeded VMEM"):
        fa.flash_attn_unpadded(q, q, q, cu, cu, causal=True)


def test_splash_varlen_gate(monkeypatch):
    """The varlen splash path engages only on a TPU with self-attention
    packing and block-divisible totals; CPU tests always get the
    dense-mask formulation."""
    _fake(monkeypatch, "tpu")
    assert fa._use_splash_varlen(512, 512, 64)
    assert not fa._use_splash_varlen(512, 500, 64)   # cross-packing decode
    assert not fa._use_splash_varlen(500, 500, 64)   # not block-divisible
    assert not fa._use_splash_varlen(512, 512, 48)   # odd head dim
    _fake(monkeypatch, "cpu")
    assert not fa._use_splash_varlen(512, 512, 64)


def test_splash_kernel_interpreted_matches_dense_mask(monkeypatch, rng):
    """The splash wrapper itself (scale folding, segment ids, layout), run
    through the Pallas interpreter a test asks for, against the dense-mask
    formulation the gate selects off-chip."""
    import paddle_tpu as paddle

    monkeypatch.setattr(fa, "_interpret", True)
    T, H, D = 128, 2, 64
    cu = np.asarray([0, 50, 128], np.int32)
    q, k, v = (rng.normal(size=(T, H, D)).astype(np.float32)
               for _ in range(3))
    seg = jnp.searchsorted(jnp.asarray(cu), jnp.arange(T), side="right") - 1
    got = fa._splash_varlen(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            seg, seg, True, 1.0 / np.sqrt(D))
    t = paddle.to_tensor
    want, _ = fa.flash_attn_unpadded(t(q), t(k), t(v), t(cu), t(cu),
                                     causal=True)
    assert fa._last_path == "xla"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.numpy()),
                               rtol=1e-4, atol=1e-5)


def test_varlen_dense_path_exact_on_cpu(rng):
    import paddle_tpu as paddle

    T, H, D = 8, 2, 4
    cu = np.asarray([0, 3, 8], np.int32)
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    out, _ = fa.flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
        paddle.to_tensor(cu), paddle.to_tensor(cu), causal=True)
    got = np.asarray(out.numpy())
    # block-diagonal causal reference
    ref = np.zeros_like(q)
    for s in range(2):
        a, b = cu[s], cu[s + 1]
        blk = q[a:b]
        L = b - a
        sc = np.einsum("qhd,khd->hqk", blk, blk) / np.sqrt(D)
        mask = np.tril(np.ones((L, L), bool))
        sc = np.where(mask, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref[a:b] = np.einsum("hqk,khd->qhd", p, blk)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
