"""``StaticFunction`` keeps its parameter and buffer lists until the model's
structure changes.

``_state_tensors()`` walks the Layer tree once and again only after some
Layer registry was written (``nn/layer_base.py::_TreeEpoch``);
``state_walks`` counts the walks. Every way a tree can change shape has a
case here: after the change the compiled output equals the eager one and
the call walked exactly once more. And the complement: a tensor's value
changing (``_replace_value``, an optimizer step, ``.to(dtype)``,
``swap_values``), a mode flip and repeated calls walk nothing."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit.functional import swap_values
from paddle_tpu.nn import layer_base
from paddle_tpu.tensor import Parameter

W = 4


def _param(seed):
    rng = np.random.default_rng(seed)
    return Parameter(rng.normal(size=(W,)).astype(np.float32))


def _linear():
    return nn.Linear(W, W)


class Net(nn.Layer):
    """Reads every registry kind by what is there at the call, so any
    registration changes its output."""

    def __init__(self, lazy=False):
        super().__init__()
        self.fc = _linear()
        self.blocks = nn.LayerList([_linear()])
        self.named = nn.LayerDict({"a": _linear()})
        self.scales = nn.ParameterList([_param(1)])
        self.seq = nn.Sequential(_linear(), nn.ReLU())
        self.extra = _param(2)
        self.lazy = lazy

    def forward(self, x):
        if self.lazy and "late" not in self._buffers:
            # a buffer that exists only once a forward has run; made as
            # a constant, so that the trace it is born in leaves it whole
            with jax.ensure_compile_time_eval():
                late = paddle.to_tensor(np.full((W,), 0.5, np.float32))
            self.register_buffer("late", late)
        h = self.fc(x)
        for block in self.blocks:
            h = paddle.tanh(block(h))
        for key in self.named.keys():
            h = h + self.named[key](h)
        for scale in self.scales:
            h = h * scale
        h = self.seq(h)
        for name in ("extra", "second"):
            p = getattr(self, name, None)
            if p is not None:
                h = h + p
        post = self._sub_layers.get("post")
        if post is not None:
            h = post(h)
        for b in self._buffers.values():
            h = h * b
        return h


def _quantize(m):
    from paddle_tpu.quantization import PTQ, AbsmaxObserver, QuantConfig

    ptq = PTQ(QuantConfig(activation=AbsmaxObserver, weight=AbsmaxObserver))
    ptq.quantize(m)                      # _sub_layers[name] = QuantedLayer
    ptq.calibrate(m, [_x()], steps=1)
    ptq.convert(m, inplace=True)         # ... = QuantizedInferenceLayer


def _buffer():
    return paddle.to_tensor(np.full((W,), 1.5, np.float32))


MUTATIONS = {
    "setattr_new_parameter": lambda m: setattr(m, "second", _param(3)),
    "setattr_replacement_parameter": lambda m: setattr(m, "extra", _param(4)),
    "setattr_parameter_none": lambda m: setattr(m, "extra", None),
    "add_parameter": lambda m: m.add_parameter("second", _param(5)),
    "add_sublayer": lambda m: m.add_sublayer("post", _linear()),
    "setattr_sublayer": lambda m: setattr(m, "post", _linear()),
    "register_buffer": lambda m: m.register_buffer("shift", _buffer()),
    "delattr_parameter": lambda m: delattr(m, "extra"),
    "delattr_sublayer": lambda m: delattr(m.named, "a"),
    "layerlist_append": lambda m: m.blocks.append(_linear()),
    "layerlist_insert": lambda m: m.blocks.insert(0, _linear()),
    "layerlist_setitem": lambda m: m.blocks.__setitem__(0, _linear()),
    "layerlist_delitem": lambda m: m.blocks.__delitem__(0),
    "layerlist_extend": lambda m: m.blocks.extend([_linear(), _linear()]),
    "layerdict_set": lambda m: m.named.__setitem__("b", _linear()),
    "layerdict_pop": lambda m: m.named.pop("a"),
    "layerdict_clear": lambda m: m.named.clear(),
    "parameterlist_append": lambda m: m.scales.append(_param(6)),
    "parameterlist_setitem": lambda m: m.scales.__setitem__(0, _param(7)),
    "sequential_replacement": lambda m: setattr(m.seq, "0", _linear()),
    "direct_registry_write":
        lambda m: m.seq._sub_layers.__setitem__("0", _linear()),
    "nested_sublayer_parameter":
        lambda m: setattr(m.blocks[0], "bias", _param(8)),
    "quantization_swap": _quantize,
}


def _x():
    return paddle.to_tensor(
        np.random.default_rng(0).normal(size=(3, W)).astype(np.float32))


def _static(model):
    """The compiled forward beside the eager one (``model(x)``)."""
    return paddle.jit.to_static(model.forward)


def _same(a, b):
    np.testing.assert_allclose(np.asarray(a.numpy()), np.asarray(b.numpy()),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("case", list(MUTATIONS) + ["buffer_in_first_forward"])
def test_a_structural_change_walks_the_tree_once(case):
    paddle.seed(11)
    x = _x()
    if case == "buffer_in_first_forward":
        model = Net(lazy=True)
        sf = _static(model)
        with paddle.no_grad():
            first = sf(x)   # registers ``late`` inside the trace
        assert "late" in model._buffers
    else:
        model = Net()
        sf = _static(model)
        with paddle.no_grad():
            first = sf(x)
            _same(first, model(x))
        assert sf.state_walks == 1
        MUTATIONS[case](model)
    walks = sf.state_walks
    with paddle.no_grad():
        out = sf(x)
        assert sf.state_walks == walks + 1
        again = sf(x)
        assert sf.state_walks == walks + 1
        eager = model(x)
    _same(out, eager)
    _same(again, eager)
    if case == "buffer_in_first_forward":
        _same(first, eager)
    else:
        assert not np.allclose(first.numpy(), eager.numpy())


def _replace_value(model, sf, x):
    w = model.fc.weight
    w._replace_value(w._value * 2.0)


def _optimizer_step(model, sf, x):
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    loss = sf(x).sum()      # through the compiled program's tape node
    loss.backward()
    opt.step()
    opt.clear_grad()


def _to_dtype(model, sf, x):
    model.to(dtype="bfloat16")
    with paddle.no_grad():      # a new program for the new dtype: no walk
        assert sf(x.astype("bfloat16")).dtype == paddle.bfloat16
    model.to(dtype="float32")


def _swap_values(model, sf, x):
    params = model.parameters()
    with swap_values(params, [p._value + 1.0 for p in params]):
        with paddle.no_grad():
            _same(sf(x), model(x))


def _mode_flips(model, sf, x):
    model.eval()
    model.train()
    model.blocks[0].training = False


def _fifty_calls(model, sf, x):
    with paddle.no_grad():
        for _ in range(50):
            sf(x)


@pytest.mark.parametrize("change", [
    _replace_value, _optimizer_step, _to_dtype, _swap_values, _mode_flips,
    _fifty_calls], ids=lambda f: f.__name__.lstrip("_"))
def test_a_value_change_walks_nothing(change):
    paddle.seed(12)
    model, x = Net(), _x()
    sf = _static(model)
    with paddle.no_grad():
        sf(x)
    epoch = layer_base.structure_epoch()
    change(model, sf, x)
    with paddle.no_grad():
        _same(sf(x), model(x))
    assert sf.state_walks == 1
    assert layer_base.structure_epoch() == epoch


def test_the_grad_path_still_differentiates_what_trains():
    """``diff_idx`` is read at the call: a parameter frozen between two
    calls (a plain attribute, no epoch notices) drops out of the tape
    node without a walk."""
    paddle.seed(13)
    model, x = Net(), _x()
    sf = _static(model)
    sf(x).sum().backward()
    assert model.fc.weight.grad is not None
    assert model.extra.grad is not None
    for p in model.parameters():
        p.clear_grad()
    model.extra.stop_gradient = True
    sf(x).sum().backward()
    assert model.fc.weight.grad is not None
    assert model.extra.grad is None
    assert sf.state_walks == 1


def test_walks_are_counted_where_compiles_are():
    from paddle_tpu.observability import get_compile_tracker

    counter = get_compile_tracker().state_walks_total
    before = counter.value
    model, x = Net(), _x()
    sf = _static(model)
    with paddle.no_grad():
        sf(x)
        sf(x)
        model.blocks.append(_linear())
        sf(x)
    assert sf.state_walks == 2
    assert counter.value - before == 2
