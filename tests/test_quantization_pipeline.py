"""Quantization pipeline tests (VERDICT #9): QAT insert/convert and the PTQ
calibration loop (reference flow: python/paddle/quantization/{qat,ptq}.py).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.quantization import (
    QAT,
    PTQ,
    AbsmaxObserver,
    FakeQuanterWithAbsMaxObserver,
    QuantConfig,
    QuantedLayer,
    QuantizedInferenceLayer,
    collect_scales,
)
from paddle_tpu.vision.models.lenet import LeNet


def _mnistish_data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 1, 28, 28)).astype(np.float32)
    y = rng.integers(0, 10, (n,)).astype(np.int64)
    return X, y


def test_qat_insert_swaps_layers():
    model = LeNet()
    cfg = QuantConfig(activation=FakeQuanterWithAbsMaxObserver,
                      weight=FakeQuanterWithAbsMaxObserver)
    q = QAT(cfg)
    qmodel = q.quantize(model)
    wrapped = [l for l in qmodel.sublayers() if isinstance(l, QuantedLayer)]
    assert len(wrapped) >= 3  # convs + linears got wrapped


def test_qat_lenet_trains_close_to_fp32():
    X, y = _mnistish_data()
    lossfn = nn.CrossEntropyLoss()

    def train(quantize):
        paddle.framework.random.seed(123)
        model = LeNet()
        if quantize:
            cfg = QuantConfig(activation=FakeQuanterWithAbsMaxObserver,
                              weight=FakeQuanterWithAbsMaxObserver)
            model = QAT(cfg).quantize(model)
        o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
        losses = []
        for _ in range(6):
            loss = lossfn(model(paddle.to_tensor(X)), paddle.to_tensor(y))
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss.numpy()))
        return model, losses

    fp_model, fp_losses = train(False)
    q_model, q_losses = train(True)
    # QAT tracks the fp32 trajectory within tolerance (STE + int8 sim)
    assert q_losses[-1] < q_losses[0]
    assert abs(q_losses[-1] - fp_losses[-1]) < 0.35 * max(fp_losses[-1], 0.5)


def test_qat_convert_produces_int8_weights():
    import jax.numpy as jnp

    X, y = _mnistish_data(16)
    paddle.framework.random.seed(1)
    model = LeNet()
    cfg = QuantConfig(activation=FakeQuanterWithAbsMaxObserver,
                      weight=FakeQuanterWithAbsMaxObserver)
    q = QAT(cfg)
    qmodel = q.quantize(model)
    # a few forwards so EMA scales exist
    for _ in range(3):
        qmodel(paddle.to_tensor(X))
    ref_out = qmodel(paddle.to_tensor(X)).numpy()

    converted = q.convert(qmodel)
    infl = [l for l in converted.sublayers()
            if isinstance(l, QuantizedInferenceLayer)]
    assert infl
    for l in infl:
        assert l.qweight is not None
        assert l.qweight.dtype == jnp.int8
        assert l.w_scale and l.w_scale > 0
    out = converted(paddle.to_tensor(X)).numpy()
    # converted int8 sim stays close to the observed-QAT forward
    assert np.mean(np.abs(out - ref_out)) < 0.25 * (np.abs(ref_out).mean() + 1e-3)


def test_ptq_calibration_produces_scales_and_converts():
    X, _ = _mnistish_data(32, seed=3)
    paddle.framework.random.seed(7)
    model = LeNet()
    fp_out = model(paddle.to_tensor(X)).numpy()

    cfg = QuantConfig(activation=AbsmaxObserver, weight=AbsmaxObserver)
    ptq = PTQ(cfg)
    observed = ptq.quantize(model)

    batches = [[paddle.to_tensor(X[i:i + 8])] for i in range(0, 32, 8)]
    n = ptq.calibrate(observed, batches)
    assert n == 4

    scales = collect_scales(observed)
    assert scales  # every wrapped layer calibrated
    for entry in scales.values():
        for v in entry.values():
            assert v is not None and v > 0

    converted = ptq.convert(observed)
    out = converted(paddle.to_tensor(X)).numpy()
    # int8 PTQ stays near the fp32 outputs on calibration data
    denom = np.abs(fp_out).mean() + 1e-6
    assert np.mean(np.abs(out - fp_out)) / denom < 0.2
    assert np.mean(np.argmax(out, -1) == np.argmax(fp_out, -1)) > 0.8


def test_hist_observer_robust_to_outliers():
    """Histogram calibration — one extreme outlier must not
    blow up the scale the way absmax does."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.quantization import AbsmaxObserver, HistObserver

    rng = np.random.default_rng(0)
    data = rng.normal(size=(4096,)).astype(np.float32)
    data[0] = 1000.0  # outlier
    t = paddle.to_tensor(data)
    absmax = AbsmaxObserver()
    hist = HistObserver(percent=0.999)
    absmax(t)
    hist(t)
    assert absmax.scales() > 5.0          # ruined by the outlier
    assert hist.scales() < 0.1            # percentile clips it


def test_kl_observer_reasonable_threshold():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.quantization import KLObserver

    rng = np.random.default_rng(1)
    data = rng.normal(size=(8192,)).astype(np.float32)
    t = paddle.to_tensor(data)
    obs = KLObserver()
    obs(t)
    # int8 scale for a unit gaussian should land near |x|max/127 ~ 0.03,
    # and the KL threshold must be within the observed range
    s = obs.scales()
    assert 0.005 < s < 0.05, s


def test_hist_observer_rebins_on_range_expansion():
    """Review r3: when a later batch widens the range, the accumulated
    histogram must re-bin to the new range (not pile old mass into the top
    bin, which would blow up the percentile threshold)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.quantization import HistObserver

    rng = np.random.default_rng(2)
    obs = HistObserver(percent=0.99)
    small = rng.uniform(0, 0.1, 8192).astype(np.float32)
    obs(paddle.to_tensor(small))
    s1 = obs.scales()
    # second batch doubles the range; the bulk of mass is still <= 0.1
    obs(paddle.to_tensor(np.concatenate(
        [small, np.asarray([0.2], np.float32)])))
    s2 = obs.scales()
    # correct re-binning keeps the 99% threshold near 0.1, NOT near 0.2
    assert s2 < 1.5 * s1, (s1, s2)


def test_kl_observer_rebins_on_range_expansion():
    """Advisor r3 (medium): KLObserver must re-bin accumulated counts when
    a later batch widens _hist_max — otherwise old counts binned under the
    narrow range are reinterpreted on the wider one, skewing the KL scale.

    Oracle: feeding batches incrementally must give (nearly) the same
    scale as feeding the concatenated data to a fresh observer."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.quantization import KLObserver

    rng = np.random.default_rng(3)
    a = rng.normal(0, 0.05, 8192).astype(np.float32)
    b = rng.normal(0, 1.0, 8192).astype(np.float32)  # 20x wider range

    inc = KLObserver()
    inc(paddle.to_tensor(a))
    inc(paddle.to_tensor(b))

    oracle = KLObserver()
    oracle(paddle.to_tensor(np.concatenate([a, b])))

    # rebinning preserves where the mass sits; without it the narrow
    # batch's counts land on wrong bins and shift the KL threshold
    assert abs(inc.scales() - oracle.scales()) < 0.25 * oracle.scales(), \
        (inc.scales(), oracle.scales())
