"""What a launch uploads, and what its compiled step donates.

The compiled serving steps donate the KV pools and nothing else
(``kv_cache.donate_pools``), so ``ContinuousBatchingScheduler._caches``
uploads one block table and one position vector a launch (and one window
table and one window base for a model with window layers), whatever the
depth of the model, and a shared tensor is an ordinary input. Donation is
forced on here as the chip has it (``_backend_donates`` patched): the
uploads inside ``serving.stage`` are counted for a decode launch, a
whole-prompt prefill, a chunk and a verify step; after a launch the old
pools are deleted and nothing else is, and the step gives back the pools
alone; and a stand-in of the benchmark's
``Probe`` (an undonated program of the model over a launch's arguments,
then the scheduler's own step on the same arguments) leaves the run's
tokens as they were."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import tensor as tensor_mod
from paddle_tpu.jit.api import StaticFunction
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny
from paddle_tpu.nn import layer_base as nn_layer
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig
from paddle_tpu.serving import scheduler as sched_mod

# XLA:CPU replays of cached executables have given wrong decode numerics
# (tests/conftest.py): every serving test module compiles fresh
jax.config.update("jax_enable_compilation_cache", False)


def _gpt(layers):
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=layers))


def _mimo():
    paddle.seed(0)
    model = MiMoV2ForCausalLM(mimo_v2_tiny(experts_held=(4, 8)))
    model.eval()
    return model


def _case(kind):
    """``(model, vocabulary, scheduler sizes)``: GPT, or a toy MiMo-V2
    with window and full layers."""
    if kind == "gpt":
        return _gpt(2), 1000, {}
    return _mimo(), 256, dict(block_size=4, max_seq_len=64,
                              cache_dtype="float32")


def _prompts(vocab, lens=(5, 21, 9, 40, 12)):
    rng = np.random.default_rng(3)
    return [rng.integers(0, vocab, n) for n in lens]


def _sched(model, monkeypatch, donate=True, **over):
    monkeypatch.setattr(sched_mod, "_backend_donates", lambda: donate)
    cfg = dict(max_num_seqs=3, max_seq_len=128, block_size=8)
    cfg.update(over)
    return ContinuousBatchingScheduler(model, SchedulerConfig(**cfg))


def _tokens(sched, prompts, new=6):
    outs = sched.generate(prompts, max_new_tokens=new)
    assert sched.metrics.requests_failed == 0
    assert not sched.metrics.faults_snapshot()
    sched.shutdown()
    return [[int(t) for t in o] for o in outs]


# ---------------------------------------------------------- upload counts

class _UploadCount:
    """Host arrays that become device arrays (``Tensor(ndarray)``'s
    ``jnp.asarray`` and ``jax.device_put``) while a ``serving.stage`` span
    is open, one entry to a span: ``(the span around it, uploads)``."""

    def __init__(self, monkeypatch):
        self.stack, self.launches = [], []
        spy = self

        class Span(RecordEvent):
            def begin(self):
                if self.name == "serving.stage":
                    spy.launches.append([spy.stack[-1], 0])
                spy.stack.append(self.name)
                super().begin()

            def end(self):
                super().end()
                spy.stack.pop()

        class Jnp:
            """``jax.numpy`` as ``paddle_tpu.tensor`` sees it."""

            def __getattr__(self, name):
                return getattr(jax.numpy, name)

            @staticmethod
            def asarray(a, *args, **kw):
                spy.note(a)
                return jax.numpy.asarray(a, *args, **kw)

        real_put = jax.device_put

        def device_put(x, *args, **kw):
            spy.note(x)
            return real_put(x, *args, **kw)

        monkeypatch.setattr(sched_mod, "RecordEvent", Span)
        monkeypatch.setattr(tensor_mod, "jnp", Jnp())
        monkeypatch.setattr(jax, "device_put", device_put)

    def note(self, a):
        if (self.stack and self.stack[-1] == "serving.stage"
                and not isinstance(a, jax.Array)):
            self.launches[-1][1] += 1

    def of(self, parent):
        return sorted({n for p, n in self.launches if p == parent})


def _upload_counts(model, monkeypatch, vocab, **over):
    """``{span: the distinct upload counts of its launches}`` of one run
    with donation forced on."""
    count = _UploadCount(monkeypatch)
    sched = _sched(model, monkeypatch, **over)
    _tokens(sched, _prompts(vocab))
    assert count.stack == []
    return {p: count.of(p) for p in ("serving.decode_step",
                                     "serving.prefill")}


@pytest.mark.parametrize("depth", [0, 2])
def test_decode_uploads_do_not_grow_with_the_model(monkeypatch, depth):
    """ids, table, positions at ``dispatch_depth`` 0 (the carry feeds the
    ids at a depth >= 1): the same for 2 and for 4 layers, and the zero
    gather index and the [S, 1] position ids are not uploads."""
    got = [_upload_counts(_gpt(n), monkeypatch, 1000, dispatch_depth=depth)
           for n in (2, 4)]
    assert got[0] == got[1]
    decode, prefill = got[0]["serving.decode_step"], got[0]["serving.prefill"]
    assert max(decode) == (3 if depth == 0 else 2)    # the issue's bound: 4
    # ids, position ids, table row, position, gather index
    assert prefill == [5]


def test_window_model_uploads_one_table_a_class(monkeypatch):
    """+ the window class's table and base, once for all window layers."""
    model, vocab, over = _case("mimo")
    got = _upload_counts(model, monkeypatch, vocab, **over)
    assert got["serving.decode_step"] == [5]      # the issue's bound: 7
    assert got["serving.prefill"] == [7]


@pytest.mark.parametrize("over, span, want", [
    (dict(prefill_chunk_size=16), "serving.prefill", 5),
    (dict(spec_k=3), "serving.decode_step", 4),
], ids=["chunk", "verify"])
def test_chunk_and_verify_launches_stage_once_too(monkeypatch, over, span,
                                                  want):
    got = _upload_counts(_gpt(2), monkeypatch, 1000, **over)
    assert max(got[span]) == want, got


# ----------------------------------------------- what a launch donates

class _Recorder:
    """Stands in for a step: keeps each launch's arguments, and what the
    last one gave back."""

    def __init__(self, step):
        self.step, self.calls, self.out = step, [], None

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, *args):
        self.out = self.step(*args)
        self.calls.append(args)
        return self.out


def _deleted(t):
    return t._value.is_deleted()


@pytest.mark.parametrize("kind", ["gpt", "mimo"])
def test_a_launch_donates_the_pools_and_nothing_else(monkeypatch, kind):
    model, vocab, over = _case(kind)
    sched = _sched(model, monkeypatch, **over)
    rec = sched._step_fn = _Recorder(sched._step_fn)
    _tokens(sched, _prompts(vocab))
    widths = {args[0].shape[1] for args in rec.calls}
    assert 1 in widths and len(widths) > 1      # decode and prefill launches
    for ids, position_ids, caches, gather_idx in rec.calls:
        # one table / position / base tensor for all layers of a kind
        for field in ("block_table", "pos", "base"):
            shared = {id(getattr(c, field)) for c in caches
                      if getattr(c, field) is not None}
            assert len(shared) <= (2 if kind == "mimo" else 1), field
        assert len({id(c.pos) for c in caches}) == 1
        for c in caches:
            assert _deleted(c.k_pool) and _deleted(c.v_pool)
            assert not _deleted(c.block_table) and not _deleted(c.pos)
            assert c.base is None or not _deleted(c.base)
        assert not any(map(_deleted, (ids, position_ids, gather_idx)))
    # the live pools are the last launch's outputs, and a launch gives
    # back nothing else of its caches (a returned table would be a fresh
    # device buffer a layer a launch)
    assert not any(_deleted(p) for pair in sched._pools for p in pair)
    for c, pair in zip(rec.out[2], sched._pools):
        assert (c.k_pool, c.v_pool) == pair
        assert c.block_table is None and c.pos is None and c.base is None


def test_decode_engine_shares_its_table_and_donates_its_buffers():
    from paddle_tpu.models.serving import DecodeEngine

    model = _gpt(2)
    for paged in (False, True):
        engine = DecodeEngine(model, max_seq_len=64, use_paged=paged,
                              block_size=8)
        rec = engine._sf = _Recorder(engine._sf)
        out = engine.generate(_prompts(1000, (6,))[0][None], max_new_tokens=4)
        assert len(out[0]) == 10
        for _ids, _pos, caches, gather_idx in rec.calls:
            for c in caches:
                assert all(_deleted(t) for t in c[:2])
                assert not any(_deleted(t) for t in c[2:] if t is not None)
            assert not _deleted(gather_idx)
        # the prompt lengths go up once for every layer, as the table does
        first_decode = rec.calls[1][2]
        assert len({id(c.pos) for c in first_decode}) == 1
        if paged:
            assert len({id(c.block_table) for c in rec.calls[0][2]}) == 1
        # one zero gather index for all decode launches
        assert len({id(args[3]) for args in rec.calls[1:]}) == 1


# --------------------------------------- the seam the benchmark stands on

class _ProbeLike:
    """``perfbench/runners/serve_hybrid_moe.py::Probe``'s shape: every
    launch goes first through an undonated program of the model over the
    same arguments, then through the scheduler's own step."""

    def __init__(self, step, model):
        self.step, self.calls = step, []
        self.logits = StaticFunction(lambda i, p, c: model(i, p, c)[0],
                                     layer=model, name="test.probe")

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, ids, position_ids, caches, gather_idx):
        logits = self.logits(ids, position_ids, caches)
        out = self.step(ids, position_ids, caches, gather_idx)
        self.calls.append((np.asarray(logits.numpy()),
                           np.asarray(gather_idx.numpy()),
                           np.asarray(out[0].numpy())))
        return out


@pytest.mark.parametrize("kind", ["gpt", "mimo"])
def test_a_probe_round_the_step_leaves_the_tokens_unchanged(monkeypatch,
                                                            kind):
    model, vocab, over = _case(kind)
    prompts = _prompts(vocab)
    plain = _tokens(_sched(model, monkeypatch, **over), prompts)
    sched = _sched(model, monkeypatch, **over)
    probe = sched._step_fn = _ProbeLike(sched._step_fn, model)
    assert _tokens(sched, prompts) == plain
    assert len(probe.calls) > len(prompts)
    # the step samples the arg-max of the probe's logits at its own rows
    for logits, gather_idx, sampled in probe.calls:
        rows = np.arange(len(gather_idx))
        assert (logits[rows, gather_idx].argmax(-1) == sampled).all()


# ------------------------------- what a steady-state launch does not redo

def _steady(model, monkeypatch, vocab, **over):
    """A scheduler past its admissions, over a model in eval mode as a
    server's is: every prompt prefilled, decode launches only from here
    on."""
    model.eval()
    sched = _sched(model, monkeypatch, **over)
    for p in _prompts(vocab, lens=(5, 21, 9)):
        sched.add_request(p, max_new_tokens=40)
    for _ in range(4):
        sched.step()
    assert len(sched.queue) == 0
    return sched


def _count_eval(monkeypatch):
    calls = []
    real = nn_layer.Layer.eval

    def counted(self):
        calls.append(self)
        return real(self)

    monkeypatch.setattr(nn_layer.Layer, "eval", counted)
    return calls


@pytest.mark.parametrize("kind, depth", [("gpt", 0), ("gpt", 2), ("mimo", 0)])
def test_steady_state_steps_walk_no_layer_tree(monkeypatch, kind, depth):
    """Neither for the step program's parameter list (``state_walks``)
    nor for the mode flags (``Layer.eval``)."""
    model, vocab, over = _case(kind)
    sched = _steady(model, monkeypatch, vocab, dispatch_depth=depth, **over)
    walks = sched.compile_stats()["state_walks"]
    assert walks == sched._step_fn.state_walks == 1
    generated = sched.metrics.generated_tokens
    evals = _count_eval(monkeypatch)
    for _ in range(20):
        sched.step()
    assert sched.metrics.generated_tokens >= generated + 3 * 15
    assert sched.compile_stats()["state_walks"] == walks
    assert evals == []
    sched.shutdown()


def _modes_at_launch(sched):
    """Wraps the step: each launch's set of ``training`` flags over the
    whole tree."""
    seen = []
    step = sched._step_fn

    class Spy(_Recorder):
        def __call__(self, *args):
            seen.append({l.training for l in
                         sched.model.sublayers(include_self=True)})
            return self.step(*args)

    sched._step_fn = Spy(step)
    return seen


def test_a_sublayer_set_to_training_is_in_eval_at_the_launch(monkeypatch):
    model, vocab, over = _case("gpt")
    sched = _steady(model, monkeypatch, vocab, **over)
    seen = _modes_at_launch(sched)
    evals = _count_eval(monkeypatch)
    sched.step()
    assert evals == []
    inner = model.sublayers()[-1]
    inner.training = True
    sched.step()
    assert evals == [model] and not inner.training
    model.sublayers()[3].train()
    sched.step()
    assert len(evals) == 2
    sched.step()
    assert len(evals) == 2
    # a layer built in training mode and hung on the tree afterwards
    model.add_sublayer("late", paddle.nn.Dropout(0.5))
    assert model.late.training
    sched.step()
    assert len(evals) == 3 and not model.late.training
    assert seen == [{False}] * 5
    sched.shutdown()


def test_a_training_model_is_in_training_mode_after_every_step(monkeypatch):
    """``was_training`` is restored as before, and the launch between is
    in eval mode: the tokens are the eval model's, dropout or not."""
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=2, hidden_dropout=0.5))
    prompts = _prompts(1000)
    model.eval()
    plain = _tokens(_sched(model, monkeypatch), prompts)

    model.train()
    sched = _sched(model, monkeypatch)
    seen = _modes_at_launch(sched)
    rids = [sched.add_request(p, max_new_tokens=6) for p in prompts]
    outs = {}
    while sched.has_unfinished():
        for o in sched.step():
            outs[o.request_id] = o
        assert {l.training for l in model.sublayers(include_self=True)} \
            == {True}
    assert seen and all(modes == {False} for modes in seen)
    assert [[int(t) for t in outs[r].token_ids] for r in rids] == plain
    sched.shutdown()
