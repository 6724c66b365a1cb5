"""paddle_tpu.jit: to_static + TrainStep (parity: python/paddle/jit/api.py:173
to_static, dy2static/, sot/ — collapsed onto jax.jit tracing, see
jit/functional.py for why no AST/bytecode pass is needed).

``to_static(layer_or_fn)`` returns a callable that runs the full computation as
one XLA program. ``TrainStep`` captures forward+backward+optimizer into a
single jitted step — the TPU equivalent of the reference's Dy2Static whole
-program training path, and the perf-critical entry for every benchmark.
"""

from __future__ import annotations

import functools
import time
import os
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from paddle_tpu.autograd import tape
from paddle_tpu.framework import random as rng
from paddle_tpu.jit.functional import (
    collect_state,
    swap_values,
    tree_unwrap,
    tree_wrap,
)
from paddle_tpu.nn.layer_base import Layer, structure_epoch
from paddle_tpu.observability.annotations import hot_path
from paddle_tpu.observability.compile_tracker import (
    abstract_signature,
    get_compile_tracker,
    next_tracked_name,
)
from paddle_tpu.observability.program_inventory import get_program_inventory
from paddle_tpu.observability.step_profile import region
from paddle_tpu.tensor import Tensor


def _jit_cache_size(jitted) -> int:
    cs = getattr(jitted, "_cache_size", None)
    if cs is None:
        return 0
    try:
        return int(cs())
    except Exception:
        return 0


_GLOBAL_TO_STATIC_ENABLED = True


def _combine(picked, rest):
    """One tree from the two ``StaticFunction._split_donated`` made: each
    is the whole tree with the other's leaves set to None."""
    return jax.tree_util.tree_map(lambda a, b: b if a is None else a,
                                  picked, rest, is_leaf=lambda x: x is None)


class StaticFunction:
    """Callable wrapping (layer?, fn) with a cached jax.jit program."""

    def __init__(self, fn: Callable, layer: Optional[Layer] = None,
                 full_graph: bool = True, donate_buffers: bool = False,
                 donate_args: Union[bool, Callable] = False,
                 name: Optional[str] = None):
        """``donate_buffers`` donates the layer's buffer values (safe when no
        caller holds the previous values — they are replaced by the call's
        write-back). ``donate_args=True`` donates every positional-argument
        buffer: only for callers that never reuse an argument array after
        the call, and never pass one array twice (a donated pytree may not
        repeat a buffer). A callable donates by part: it is given the
        call's positional arguments (as arrays) and returns a tree prefix
        of bools over them, True above the leaves to donate (the serving
        steps donate the KV pools of ``caches`` and nothing else, so a
        block table may be shared by every layer); the other leaves are
        ordinary inputs that stay valid after the call and may repeat.
        Its answer may depend on the arguments' structure only: it is
        asked once for each structure.
        ``name`` labels this program cache in the CompileTracker."""
        self._fn = fn
        self._layer = layer
        self._full_graph = full_graph
        self._tracker_name = next_tracked_name(
            name or getattr(fn, "__qualname__",
                            getattr(fn, "__name__", "fn")))
        functools.update_wrapper(self, fn, updated=[])
        donate = ()
        if donate_buffers:
            donate += (1,)
        if donate_args:
            donate += (2,)
        self._donate_argnums = donate
        # donation by part: the donated leaves travel as ``arg_vals`` (jit
        # argument 2, donated), the others as ``kept_vals`` (argument 3)
        self._donate_select = donate_args if callable(donate_args) else None
        self._donate_flags = {}   # arguments' treedef -> one bool a leaf
        self._seen_programs = 0   # ProgramInventory capture high-water mark
        self._traces = 0          # times jax traced self._traced (cache misses)
        # the call plan: the layer's (params, buffers) lists, kept until
        # the Layer registries' structure epoch moves (_state_tensors)
        self._state = None
        self._state_epoch = -1
        self.state_walks = 0      # times the Layer tree was walked for them
        self._jitted = jax.jit(self._traced, static_argnames=("training",),
                               donate_argnums=donate)
        self._jitted_checked = None  # built lazily when nan/inf debug is on
        # grad path: same pure program, no donation (fwd runs under jax.vjp)
        self._jitted_nodonate = (
            self._jitted if not donate
            else jax.jit(self._traced, static_argnames=("training",)))
        self.forward = self.__call__

    # The traced program: pure function of (param_vals, buffer_vals, args, key)
    def _traced(self, param_vals, buffer_vals, arg_vals, kept_vals,
                kwarg_vals, key, training):
        self._traces += 1
        if kept_vals is not None:
            arg_vals = _combine(arg_vals, kept_vals)
        params, buffers = self._state_tensors()
        tensors = params + buffers
        values = list(param_vals) + list(buffer_vals)
        args = tree_wrap(arg_vals)
        kwargs = tree_wrap(kwarg_vals)
        if self._layer is not None:
            prev_training = self._layer.training
            (self._layer.train() if training else self._layer.eval())
        try:
            with swap_values(tensors, values), rng.traced_key(key):
                out = self._fn(*args, **kwargs)
                out_vals = tree_unwrap(out)
                new_buffer_vals = [b._value for b in buffers]
        finally:
            if self._layer is not None:
                (self._layer.train() if prev_training else self._layer.eval())
        return out_vals, new_buffer_vals

    def _split_donated(self, arg_vals):
        """``(donated, kept)`` of a call's arguments under donation by
        part: each the whole tree with the other's leaves set to None, so
        both keep the structure a jit cache keys on. One flatten and two
        unflattens a call (this runs on every launch); the selector is
        asked once for each structure."""
        leaves, treedef = jax.tree_util.tree_flatten(arg_vals)
        flags = self._donate_flags.get(treedef)
        if flags is None:
            flags = self._donate_flags[treedef] = tuple(jax.tree.leaves(
                jax.tree.broadcast(self._donate_select(*arg_vals),
                                   arg_vals)))
        return (treedef.unflatten([l if f else None
                                   for l, f in zip(leaves, flags)]),
                treedef.unflatten([None if f else l
                                   for l, f in zip(leaves, flags)]))

    def _state_tensors(self):
        """The layer's ``(params, buffers)`` in the order the traced
        program takes their values. Walks the tree (``collect_state``) once
        and again only after a registry of some Layer was written; the
        tensors' values are read fresh from the handles at every call."""
        if self._layer is None:
            return [], []
        epoch = structure_epoch()
        if epoch != self._state_epoch:
            # the epoch read before the walk: a write racing the walk
            # leaves it behind and the next call walks again
            p, b = collect_state(self._layer)
            self._state = (list(p.values()), list(b.values()))
            self._state_epoch = epoch
            self.state_walks += 1
            get_compile_tracker().state_walks_total.inc()
        return self._state

    def __call__(self, *args, **kwargs):
        if not _GLOBAL_TO_STATIC_ENABLED:
            # paddle.jit.enable_to_static(False): captured functions run
            # eagerly, exactly as the reference's global toggle does
            # (self._fn is already bound when wrapping a layer method)
            return self._fn(*args, **kwargs)
        if not self._full_graph:
            # SOT-style contract: constructs tracing can't swallow fall back
            # to eager instead of erroring (paddle's full_graph=False)
            from paddle_tpu.jit.sot import _graph_break_types

            try:
                return self._call_impl(*args, **kwargs)
            except _graph_break_types():
                return self._fn(*args, **kwargs)
        return self._call_impl(*args, **kwargs)

    def _program_count(self) -> int:
        """Total cached programs across this wrapper's jit objects."""
        n, seen = 0, set()
        for j in (self._jitted, self._jitted_nodonate,
                  self._jitted_checked):
            if j is None or id(j) in seen:
                continue
            seen.add(id(j))
            n += _jit_cache_size(j)
        return n

    def _call_impl(self, *args, **kwargs):
        # CompileTracker probe: program-cache growth across the call means
        # jax traced+compiled a fresh XLA program for these abstract shapes
        n0 = self._program_count()
        traces0 = self._traces
        import time as _time

        t0 = _time.perf_counter()
        try:
            return self._run_impl(*args, **kwargs)
        except Exception as exc:
            if self._traces != traces0:
                # this call traced, so the program was compiling or running
                # for the first time: a retry fails the same way
                # (resilience.classify_error reads the mark)
                exc.program_start = True
            raise
        finally:
            grown = self._program_count() - n0
            if grown > 0:
                get_compile_tracker().record(
                    self._tracker_name, _time.perf_counter() - t0,
                    abstract_signature(args, kwargs), n_programs=grown)

    def _run_impl(self, *args, **kwargs):
        from paddle_tpu.autograd import tape as _tape

        params, buffers = self._state_tensors()
        param_vals = [p._value for p in params]
        buffer_vals = [b._value for b in buffers]
        arg_vals = tree_unwrap(args)
        kwarg_vals = tree_unwrap(kwargs)
        key = rng.next_key()
        training = self._layer.training if self._layer is not None else False

        # the differentiable path's bookkeeping, only where a tape could
        # record it: a launch under no_grad (serving) pays for none of it.
        # ``stop_gradient`` is a plain attribute that flips without any
        # epoch noticing, so ``diff_idx`` is per call
        needs_grad = False
        if _tape.is_grad_enabled():
            orig_leaves = jax.tree_util.tree_leaves(
                (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
            arg_tensors = [l for l in orig_leaves if isinstance(l, Tensor)]
            diff_idx = [i for i, p in enumerate(params)
                        if not p.stop_gradient]
            needs_grad = bool(diff_idx) or any(
                not t.stop_gradient for t in arg_tensors)

        if not needs_grad:
            from paddle_tpu.amp import debugging as _dbg

            if _dbg.check_numerics_enabled():
                # the COMPILED-path numerics sanitizer (reference checks per
                # instruction in the interpreter, program_interpreter.cc:1131)
                # — checkify instruments every float op inside the program;
                # err.throw() is the one host sync, debug mode only
                if self._jitted_checked is None:
                    from jax.experimental import checkify as _checkify

                    # checkify erases the signature, so `training` must be
                    # marked static POSITIONALLY (arg 6 of the bound method)
                    self._jitted_checked = jax.jit(
                        _checkify.checkify(self._traced,
                                           errors=_checkify.float_checks),
                        static_argnums=(6,))
                err, (out_vals, new_buffer_vals) = self._jitted_checked(
                    param_vals, buffer_vals, arg_vals, None, kwarg_vals,
                    key, training)
                err.throw()
            else:
                kept_vals = None
                if self._donate_select is not None:
                    arg_vals, kept_vals = self._split_donated(arg_vals)
                out_vals, new_buffer_vals = self._jitted(
                    param_vals, buffer_vals, arg_vals, kept_vals,
                    kwarg_vals, key, training)
                # ProgramInventory capture: cache growth means this call
                # compiled a fresh program — record its specs (shape-only;
                # donated leaves are aval-readable shells by now) so cost
                # analysis can re-lower it later without touching the
                # runtime cache. One int compare per steady-state call.
                n_now = _jit_cache_size(self._jitted)
                if n_now != self._seen_programs:
                    self._seen_programs = n_now
                    get_program_inventory().capture(
                        self._tracker_name, "static_function", self._jitted,
                        (param_vals, buffer_vals, arg_vals, kept_vals,  # graft-lint: disable=donation-alias
                         kwarg_vals, key),
                        {"training": training},
                        donate_argnums=self._donate_argnums)
            for b, v in zip(buffers, new_buffer_vals):
                b._replace_value(v)
            return tree_wrap(out_vals)

        # differentiable path: ONE tape node spanning the whole compiled
        # program (paddle's to_static-training parity: loss.backward()
        # through a @to_static forward). The vjp runs the same XLA program,
        # differentiating only the trainable params (frozen ones are closed
        # over like buffers — no wasted backward compute/residuals).
        diff_set = set(diff_idx)
        diff_vals = [param_vals[i] for i in diff_idx]

        def call(dpv, av, kv):
            it = iter(dpv)
            pv = [next(it) if i in diff_set else param_vals[i]
                  for i in range(len(params))]
            return self._jitted_nodonate(pv, buffer_vals, av, None, kv, key,
                                         training)

        (out_vals, new_buffer_vals), vjp_fn = jax.vjp(
            call, diff_vals, arg_vals, kwarg_vals)
        out_leaves, out_treedef = jax.tree_util.tree_flatten(out_vals)
        buf_zero = jax.tree_util.tree_map(jnp.zeros_like, new_buffer_vals)
        in_tensors = [params[i] for i in diff_idx] + arg_tensors
        n_out = len(out_leaves)

        def node_vjp(out_cot):
            import jax.dtypes

            cots = out_cot if isinstance(out_cot, tuple) else (out_cot,)
            cot_tree = jax.tree_util.tree_unflatten(out_treedef, list(cots))
            pv_cot, av_cot, kv_cot = vjp_fn((cot_tree, buf_zero))
            # align arg cotangents with the Tensor leaves of (args, kwargs):
            # non-Tensor numeric leaves produce float0 cots that are dropped
            cot_leaves = jax.tree_util.tree_leaves((av_cot, kv_cot))
            arg_cots = [c for o, c in zip(orig_leaves, cot_leaves)
                        if isinstance(o, Tensor)]

            def clean(c):
                return None if c.dtype == jax.dtypes.float0 else c

            return tuple(clean(c) for c in list(pv_cot) + arg_cots)

        node = tape.TapeNode(getattr(self._fn, "__name__", "to_static"),
                             node_vjp, in_tensors, n_out)
        wrapped = []
        for i, v in enumerate(out_leaves):
            t = Tensor._from_value(v)
            t.stop_gradient = False
            t._node = node
            node.register_output(i, t)
            wrapped.append(t)
        for b, v in zip(buffers, new_buffer_vals):
            b._replace_value(v)
        return jax.tree_util.tree_unflatten(out_treedef, wrapped)

    @property
    def program_cache(self):
        return self._jitted._cache_size() if hasattr(self._jitted, "_cache_size") else None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True):
    """paddle.jit.to_static parity: decorator or direct call on fn/Layer."""

    def _ast(fn):
        """Rewrite data-dependent if/while into cond/while_loop ops (the
        dy2static AST pass); identity when nothing needs rewriting or the
        source is unavailable."""
        from paddle_tpu.jit import dy2static

        try:
            out = dy2static.ast_transform(fn)
        except Exception:
            return fn
        return out if out is not None else fn

    def decorate(obj):
        if isinstance(obj, Layer):
            if isinstance(obj.forward, StaticFunction):
                return obj  # already static — idempotent re-decoration
            func = getattr(obj.forward, "__func__", None)
            fwd = _ast(func).__get__(obj) if func is not None else obj.forward
            sf = StaticFunction(fwd, layer=obj, full_graph=full_graph)
            obj.forward = sf
            return obj
        layer = getattr(obj, "__self__", None)
        if isinstance(layer, Layer):
            fn = _ast(obj.__func__).__get__(layer)
            return StaticFunction(fn, layer=layer, full_graph=full_graph)
        return StaticFunction(_ast(obj), layer=None, full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class _DonatedValue:
    """Payload installed into a batch Tensor after its buffer was donated
    into a compiled TrainStep: ANY further use raises. This makes the
    donate_inputs contract enforced rather than advisory — on backends
    where XLA aliases the buffer jax already marks it deleted, but where
    the donation is unusable (no same-shaped output; CPU) the array would
    silently stay readable and a caller could come to depend on it."""

    __slots__ = ()

    def __getattr__(self, name):
        raise RuntimeError(
            "this Tensor's buffer was donated to a compiled TrainStep "
            "(donate_inputs=True) and must not be reused; copy the batch "
            "before the step if you need it afterwards")


class NonBlockingStepResult:
    """A TrainStep's outputs left ON DEVICE: jax dispatch is asynchronous,
    so holding this object costs nothing — the loop can dispatch the next
    step immediately. Reading the loss as a host number is the only sync,
    and the wall it blocks is metered as ``train_sync_stall_seconds`` (a
    dispatch-ahead loop pays it once per log window, not once per step)."""

    __slots__ = ("_loss_val", "_aux_vals", "_has_aux")

    def __init__(self, loss_val, aux_vals=None, has_aux=False):
        self._loss_val = loss_val
        self._aux_vals = aux_vals
        self._has_aux = has_aux

    @property
    def loss(self) -> "Tensor":
        """The device-resident loss (no host sync)."""
        return Tensor._from_value(self._loss_val)

    @property
    def aux(self):
        """The device-resident aux pytree (no host sync); None w/o has_aux."""
        return tree_wrap(self._aux_vals) if self._has_aux else None

    def loss_value(self) -> float:
        """Host float of the loss — blocks until the step (and everything
        dispatched before it) completes; the wait is metered."""
        import numpy as _np

        from paddle_tpu.observability.train_stall import record_sync_stall

        t0 = time.perf_counter()
        v = float(_np.asarray(self._loss_val))
        record_sync_stall(time.perf_counter() - t0)
        return v

    def __float__(self):
        return self.loss_value()

    def block(self):
        """Wait for the step to retire without pulling values to host."""
        import jax as _jax

        from paddle_tpu.observability.train_stall import record_sync_stall

        t0 = time.perf_counter()
        _jax.block_until_ready(self._loss_val)
        record_sync_stall(time.perf_counter() - t0)
        return self


class TrainStep:
    """One fully-jitted training step: forward + backward + optimizer update.

    The functional analogue of the 3.1-3.2 hot loop in the reference's call
    stacks (SURVEY §3), compiled into a single XLA program so matmuls, the
    backward pass, and the parameter update all fuse and overlap.

    Usage:
        step = TrainStep(model, loss_fn, opt)
        loss = step(x, y)            # params/opt state updated in place
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True, scaler=None, has_aux: bool = False,
                 donate_inputs: bool = False, nonblocking: bool = False):
        """``has_aux``: loss_fn returns (loss, aux) — aux (any Tensor pytree,
        e.g. model outputs for metrics) is threaded out of the compiled step
        and returned alongside the loss.

        ``donate``: donate the param/optimizer/master/scaler state buffers
        into the compiled step so the update happens in place — without it a
        step holds state twice (old + new) at its peak.

        ``donate_inputs``: ALSO donate the batch buffers. Only for callers
        that feed each step a fresh batch and never touch it again (a
        ``DevicePrefetcher`` loop); the caller's batch Tensors are dead
        after the call — re-reading one raises jax's deleted-array error.
        An alias-safety audit copies any batch leaf that would donate the
        same buffer twice (``step(x, x)``) or that aliases donated state.

        ``nonblocking``: return a :class:`NonBlockingStepResult` instead of
        a loss Tensor, keeping the loop fully dispatch-ahead."""
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._has_aux = has_aux
        # amp.GradScaler: loss scaling + skip-on-inf + dynamic scale update,
        # all inside the compiled step (the reference's scaler.step path).
        # Scale/good/bad counters live as DEVICE arrays updated in-graph so
        # the hot loop never syncs to host; the scaler object reads them
        # lazily through get_loss_scaling().
        self._scaler = scaler if (scaler is not None and
                                  scaler.is_enable()) else None
        if self._scaler is not None:
            s = self._scaler
            self._scaler_state = (
                jnp.asarray(s.get_loss_scaling(), jnp.float32),
                jnp.asarray(s._good_steps, jnp.int32),
                jnp.asarray(s._bad_steps, jnp.int32),
            )
            step_self = self

            def _lazy_scale():
                sc, good, bad = step_self._scaler_state
                s._scale = float(sc)
                s._good_steps = int(good)
                s._bad_steps = int(bad)
                return s._scale

            s.get_loss_scaling = _lazy_scale
        self._params = [p for p in optimizer._parameter_list if p.trainable]
        # FusedAdamW inside the compiled step: measured on-chip (r3,
        # GPT-2s), the flat-master layout LOSES under jit — 0.645x with the
        # Pallas kernel, 0.70x even with a plain XLA update on the flat
        # buffer — because the AD slice-transpose that assembles the flat
        # gradient costs more than it saves; XLA's own per-param update
        # fusion is the fastest formulation inside one program. So
        # FusedAdamW routes through the SAME per-param path as stock AdamW
        # here (speedup 1.0, the kernel's domain is the eager loop where it
        # wins ~10x on dispatch amortization). The flat in-graph mode is
        # kept behind PADDLE_TPU_FUSED_FLAT=1 for measurement.
        self._fused_mode = False
        self._fused_jitted = None
        if (self._scaler is None and not getattr(optimizer, "_offload", False)
                and getattr(optimizer, "_sharding_level", None) is None
                and os.environ.get("PADDLE_TPU_FUSED_FLAT") == "1"):
            try:
                from paddle_tpu.incubate.optimizer import FusedAdamW

                self._fused_mode = isinstance(optimizer, FusedAdamW)
            except ImportError:
                pass  # incubate tree absent: fused mode simply stays off
        # eager state init so shapes are known before trace; master weights
        # (multi_precision) materialize here so the jitted step carries them
        if not self._fused_mode:
            for p in self._params:
                if id(p) not in optimizer._state:
                    optimizer._state[id(p)] = optimizer._init_state(p)
                optimizer._master(p)
        if getattr(optimizer, "_offload", False):
            # states initialized above live on device; move them to their
            # pinned-host residence before the layout is baked into the jit
            from paddle_tpu.distributed.sharding import _offload_state

            _offload_state(optimizer)
        # donation layout over _step's positional args:
        #   0 param_vals, 1 opt_states, 2 master_vals, 3 buffer_vals,
        #   4 batch_vals, 5 lr, 6 key, 7 scale
        # state donation covers 0/1/2 (+7, the in-graph scaler counters:
        # a fresh tuple is returned every step so the old one has no
        # reader); buffers (3) stay undonated — they are re-read by the
        # eager model between steps (eval/forward outside the jit).
        self._donate_inputs = bool(donate_inputs)
        self._nonblocking = bool(nonblocking)
        self._donate_argnums = (0, 1, 2, 7) if donate else ()
        if donate and donate_inputs:
            self._donate_argnums += (4,)
        self._last_donated = None  # shells of last call's donated buffers
        self._seen_programs = 0    # ProgramInventory capture high-water mark
        self._ledger_handles = None  # weights/slots/masters, registered once
        self._jitted = None  # built at first call (out_shardings need state)
        self._tracker_name = next_tracked_name(
            f"TrainStep[{type(model).__name__}]")

    def _build_jit(self, opt_states, master_vals, n_buffers, has_scaler):
        """Compile-time layout: when the optimizer is ZeRO-offloaded, pin the
        state/master outputs to their (pinned_host) input shardings so the
        compiled hot loop keeps them in host memory across steps."""
        out_shardings = None
        self._offload_sh = None
        self._offload_post = False
        if getattr(self._opt, "_offload", False):
            def shard_of(v):
                return v.sharding if hasattr(v, "sharding") else None

            st_sh = [jax.tree_util.tree_map(shard_of, st) for st in opt_states]
            mv_sh = [shard_of(mv) if mv is not None else None
                     for mv in master_vals]
            # stage-3 offload: params ALSO rest in pinned host; pin their
            # outputs to the RECORDED park layout (not p._value's current
            # sharding — an eager warmup forward may have fetched params to
            # device, and baking that in would keep them device-resident
            # forever) so the hot loop never migrates them
            host_sh = getattr(self._opt, "_param_host_sh", {})
            pv_sh = [host_sh.get(id(p), shard_of(p._value))
                     if getattr(self._opt, "_offload_params", False)
                     else None
                     for p in self._params]
            self._offload_sh = (st_sh, mv_sh, pv_sh)
            if jax.default_backend() == "cpu":
                # CPU PJRT can't annotate host placement inside compiled
                # programs (annotate_device_placement unimplemented): fall
                # back to eager re-offload after each step. On TPU the
                # out_shardings pin states to pinned_host inside the step.
                self._offload_post = True
                self._offload_sh = None
            else:
                out_shardings = (None, pv_sh, st_sh,
                                 mv_sh, [None] * n_buffers,
                                 (None, None, None) if has_scaler else None,
                                 None)
        self._out_shardings = out_shardings
        self._jitted = jax.jit(self._step,
                               donate_argnums=self._donate_argnums,
                               out_shardings=out_shardings)

    def _step(self, param_vals, opt_states, master_vals, buffer_vals,
              batch_vals, lr, key, scale=None):
        if self._offload_sh is not None:
            # ZeRO offload: stream pinned-host states/masters (and stage-3
            # params) to device for the update (XLA overlaps the PCIe
            # copies with compute); the jit's out_shardings pin the results
            # back to host
            st_sh, mv_sh, pv_sh = self._offload_sh

            def to_dev(v, sh):
                if sh is None or sh.memory_kind in (None, "device"):
                    return v
                return jax.device_put(v, sh.with_memory_kind("device"))

            opt_states = [jax.tree_util.tree_map(to_dev, st, sh)
                          for st, sh in zip(opt_states, st_sh)]
            master_vals = [mv if mv is None else to_dev(mv, sh)
                           for mv, sh in zip(master_vals, mv_sh)]
            param_vals = [to_dev(pv, sh)
                          for pv, sh in zip(param_vals, pv_sh)]
        params = self._params
        _, buffers_dict = collect_state(self._model)
        buffers = [b for b in buffers_dict.values() if b is not None]
        args = tree_wrap(batch_vals)
        with swap_values(params + buffers, list(param_vals) + list(buffer_vals)), \
                rng.traced_key(key):
            for p in params:
                p._grad = None
                p.stop_gradient = False
            with region("forward"):
                res = self._loss_fn(self._model, *args)
                loss, aux = res if self._has_aux else (res, None)
                aux_vals = tree_unwrap(aux)
            with region("backward"):
                if scale is not None:
                    (loss * scale[0].astype(loss.dtype)).backward()
                else:
                    loss.backward()
                grads = [p._grad for p in params]
            # don't let grad tracers outlive the trace: a later eager
            # backward/step would consume leaked tracers
            for p in params:
                p._grad = None
            new_buffer_vals = [b._value for b in buffers]
            loss_val = loss._value
        with region("optimizer"):
            found_inf = None
            new_scaler_state = None
            if scale is not None:
                scale_v, good, bad = scale
                # unscale + joint finiteness check (scaler.unscale_ semantics)
                inv = (1.0 / scale_v).astype(jnp.float32)
                grads = [None if g is None else g.astype(jnp.float32) * inv
                         for g in grads]
                finite = jnp.asarray(True)
                for g in grads:
                    if g is not None:
                        finite = jnp.logical_and(finite,
                                                 jnp.all(jnp.isfinite(g)))
                found_inf = jnp.logical_not(finite)
                # dynamic scale update, in-graph (GradScaler.update semantics)
                s = self._scaler
                bad2 = jnp.where(found_inf, bad + 1, 0)
                good2 = jnp.where(found_inf, 0, good + 1)
                dec = bad2 >= s._decr_every_n
                inc = good2 >= s._incr_every_n_steps
                scale2 = jnp.where(
                    dec, jnp.maximum(scale_v * s._decr_ratio, 1.0),
                    jnp.where(inc, scale_v * s._incr_ratio, scale_v))
                new_scaler_state = (scale2,
                                    jnp.where(inc, 0, good2).astype(jnp.int32),
                                    jnp.where(dec, 0, bad2).astype(jnp.int32))
            # grad clip (pure, works on tracers)
            if self._opt._grad_clip is not None:
                grads = self._opt._grad_clip._clip_arrays(grads)
            new_params, new_states, new_masters = [], [], []
            for p, pv, g, st, mv in zip(params, param_vals, grads, opt_states,
                                        master_vals):
                if g is None:
                    new_params.append(pv)
                    new_states.append(st)
                    new_masters.append(mv)
                    continue
                target = mv if mv is not None else pv
                np_, ns = self._opt._apply_one(
                    target, g.astype(target.dtype), lr, st,
                    self._opt._decay_for(p)
                )
                if found_inf is not None:
                    # skip the whole update on non-finite grads (scaler.step)
                    np_ = jnp.where(found_inf, target, np_)
                    ns = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(found_inf, old, new),
                        ns, st)
                if mv is not None:  # update fp32 master, cast to param dtype
                    new_masters.append(np_)
                    new_params.append(np_.astype(pv.dtype))
                else:
                    new_masters.append(None)
                    new_params.append(np_)
                new_states.append(ns)
        return (loss_val, new_params, new_states, new_masters,
                new_buffer_vals, new_scaler_state, aux_vals)

    # ------------------------------------------------ FusedAdamW flat mode

    def _build_fused_jit(self):
        import numpy as _np

        from paddle_tpu.ops.pallas import fused_adamw as _kernel
        from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_flat

        opt = self._opt
        st = opt._flat
        sizes = list(st["sizes"])
        shapes = list(st["shapes"])
        dtypes = [str(d) for d in st["dtypes"]]
        offsets = [int(o) for o in _np.cumsum([0] + sizes[:-1])]
        beta1, beta2, eps = opt._beta1, opt._beta2, opt._epsilon
        block_rows = opt._block_rows
        interpret = _kernel._interpret
        params = self._params

        def pieces_of(flat):
            return [flat[off:off + n].reshape(shp).astype(dt)
                    for off, n, shp, dt in zip(offsets, sizes, shapes,
                                               dtypes)]

        def step(flat_p, flat_m, flat_v, b1p, b2p, wd, buffer_vals,
                 batch_vals, lr, key, training):
            _, buffers_dict = collect_state(self._model)
            buffers = [b for b in buffers_dict.values() if b is not None]
            args = tree_wrap(batch_vals)

            def forward(fp):
                pvals = pieces_of(fp)
                with swap_values(params + buffers,
                                 pvals + list(buffer_vals)), \
                        rng.traced_key(key):
                    from paddle_tpu.autograd import tape as _t

                    with _t.no_grad():  # jax.grad owns AD here, not the tape
                        res = self._loss_fn(self._model, *args)
                    loss, aux = res if self._has_aux else (res, None)
                    aux_vals = tree_unwrap(aux)
                    new_buf = [b._value for b in buffers]
                return loss._value.astype(jnp.float32), (aux_vals, new_buf)

            (loss_val, (aux_vals, new_buffer_vals)), dflat = \
                jax.value_and_grad(forward, has_aux=True)(flat_p)
            if opt._grad_clip is not None:
                # clip on the PER-PARAM views, then re-flatten: per-tensor
                # clips (ClipGradByNorm) are NOT flat-equivalent — a single
                # norm over the concatenation would change their semantics
                gpieces = [dflat[off:off + n].reshape(shp)
                           for off, n, shp in zip(offsets, sizes, shapes)]
                gpieces = opt._grad_clip._clip_arrays(gpieces)
                dflat = jnp.concatenate(
                    [jnp.ravel(g) for g in gpieces]
                    + [dflat[sum(sizes):]])
            new_p, new_m, new_v, nb1, nb2 = fused_adamw_flat(
                flat_p, dflat, flat_m, flat_v, wd, lr, b1p, b2p,
                beta1=beta1, beta2=beta2, eps=eps,
                block_rows=block_rows, interpret=interpret)
            return (loss_val, new_p, new_m, new_v, nb1, nb2,
                    pieces_of(new_p), new_buffer_vals, aux_vals)

        # donate the five flat state buffers (the param/master/moment
        # round-trip becomes in-place)
        self._fused_jitted = jax.jit(step, donate_argnums=(0, 1, 2, 3, 4),
                                     static_argnums=(10,))

    @hot_path(reason="FusedAdamW flat-mode dispatch path")
    def _fused_call(self, batch):
        opt = self._opt
        params = self._params
        if opt._flat is None or opt._flat["ids"] != [id(p) for p in params]:
            opt._build_flat([(p, None) for p in params])
            self._fused_jitted = None
        st = opt._flat
        wd_sig = tuple(float(opt._decay_for(p)) for p in params)
        if wd_sig != st["wd_sig"]:
            st["wd"], st["wd_sig"] = opt._wd_buffer(params, st["sizes"])
            self._fused_jitted = None
        if self._fused_jitted is None:
            self._build_fused_jit()
        _, buffers_dict = collect_state(self._model)
        buffers = [b for b in buffers_dict.values() if b is not None]
        buffer_vals = [b._value for b in buffers]
        batch_vals = tree_unwrap(batch)
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        key = rng.next_key()
        training = self._model.training
        (loss_val, st["p"], st["m"], st["v"], st["b1pow"], st["b2pow"],
         pieces, new_buffer_vals, aux_vals) = self._fused_jitted(
            st["p"], st["m"], st["v"], st["b1pow"], st["b2pow"], st["wd"],
            buffer_vals, batch_vals, lr, key, training)
        for p, v in zip(params, pieces):
            p._replace_value(v)
        for b, v in zip(buffers, new_buffer_vals):
            b._replace_value(v)
        opt._step_count += 1
        loss_t = Tensor._from_value(loss_val)
        if self._has_aux:
            return loss_t, tree_wrap(aux_vals)
        return loss_t

    # ------------------------------------------------------- checkpointing
    def checkpoint_extra(self):
        """Host-side state beyond model+optimizer that a bit-identical
        resume needs: the in-graph GradScaler counters (scale / good / bad
        live as device arrays between steps)."""
        if self._scaler is None:
            return None
        sc, good, bad = self._scaler_state
        return {"loss_scale": float(sc), "good_steps": int(good),
                "bad_steps": int(bad)}

    def apply_checkpoint_extra(self, extra):
        if self._scaler is None or not extra:
            return
        self._scaler_state = (
            jnp.asarray(extra["loss_scale"], jnp.float32),
            jnp.asarray(extra["good_steps"], jnp.int32),
            jnp.asarray(extra["bad_steps"], jnp.int32),
        )
        s = self._scaler
        s._scale = float(extra["loss_scale"])
        s._good_steps = int(extra["good_steps"])
        s._bad_steps = int(extra["bad_steps"])

    def _program_count(self) -> int:
        n, seen = 0, set()
        for j in (self._jitted, getattr(self, "_jitted_checked", None),
                  self._fused_jitted):
            if j is None or id(j) in seen:
                continue
            seen.add(id(j))
            n += _jit_cache_size(j)
        return n

    # ------------------------------------------------------- donation audit
    def _audit_donated_inputs(self, batch_vals, param_vals, opt_states,
                              master_vals, scale):
        """Alias-safety audit for ``donate_inputs``: a donated pytree must
        not contain the same buffer twice (XLA rejects double donation at
        execute time), and a batch leaf must not alias a donated state
        buffer. Offending leaves are defensively copied (metered)."""
        seen = set()
        for v in param_vals:
            seen.add(id(v))
        for tree in (opt_states, master_vals, scale):
            for v in jax.tree_util.tree_leaves(tree):
                seen.add(id(v))
        copies = 0

        def guard(v):
            nonlocal copies
            if not isinstance(v, jax.Array):
                return v
            if id(v) in seen:
                copies += 1
                return jnp.copy(v)
            seen.add(id(v))
            return v

        out = jax.tree_util.tree_map(guard, batch_vals)
        if copies:
            from paddle_tpu.observability.train_stall import (
                donation_copy_counter,
            )

            donation_copy_counter().inc(copies)
        return out

    def donation_report(self) -> dict:
        """Cache-probe evidence that donation actually engaged: after a
        donated call the input buffers are deleted (jax marks them dead
        whether or not the backend aliased them — the caller-visible
        contract is identical). Fractions are over the LAST call."""

        def frac_deleted(vals):
            leaves = [v for v in jax.tree_util.tree_leaves(vals)
                      if hasattr(v, "is_deleted")]
            if not leaves:
                return None
            return sum(1 for v in leaves if v.is_deleted()) / len(leaves)

        rep = {"donate_argnums": tuple(self._donate_argnums),
               "donate_inputs": self._donate_inputs,
               # the caller-side guard always engages with donate_inputs,
               # even where XLA found the donation unusable (frac 0.0)
               "inputs_guarded": self._donate_inputs}
        if self._last_donated is not None:
            rep["state_buffers_deleted_frac"] = frac_deleted(
                self._last_donated.get("params"))
            rep["input_buffers_deleted_frac"] = frac_deleted(
                self._last_donated.get("batch"))
        return rep

    def __call__(self, *batch):
        from paddle_tpu.profiler import RecordEvent, TracerEventType

        n0 = self._program_count()
        t0 = time.perf_counter()
        try:
            with RecordEvent("train.step", TracerEventType.ProfileStep):
                return self._call_inner(*batch)
        finally:
            grown = self._program_count() - n0
            if grown > 0:
                get_compile_tracker().record(
                    self._tracker_name, time.perf_counter() - t0,
                    abstract_signature(batch), n_programs=grown)

    @hot_path(reason="per-step dispatch: host work here serializes steps")
    def _call_inner(self, *batch):
        if self._fused_mode:
            return self._fused_call(batch)
        params = self._params
        param_vals = [p._value for p in params]
        opt_states = [self._opt._state[id(p)] for p in params]
        master_vals = [self._opt._master_weights.get(id(p)) for p in params]
        _, buffers_dict = collect_state(self._model)
        buffers = [b for b in buffers_dict.values() if b is not None]
        buffer_vals = [b._value for b in buffers]
        batch_vals = tree_unwrap(batch)
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        key = rng.next_key()
        scale = self._scaler_state if self._scaler is not None else None
        if self._donate_inputs and 4 in self._donate_argnums:
            batch_vals = self._audit_donated_inputs(
                batch_vals, param_vals, opt_states, master_vals, scale)
        if self._jitted is None:
            self._build_jit(opt_states, master_vals, len(buffer_vals),
                            scale is not None)
        if self._offload_post:
            # CPU fallback: states rest in pinned host between steps but the
            # compiled step wants uniform (device) memory spaces — stream in
            # eagerly, stream out in the write-back below
            from paddle_tpu.distributed.sharding import to_device_memory

            opt_states = [jax.tree_util.tree_map(to_device_memory, st)
                          for st in opt_states]
            master_vals = [mv if mv is None else to_device_memory(mv)
                           for mv in master_vals]
            if getattr(self._opt, "_offload_params", False):
                param_vals = [to_device_memory(pv) for pv in param_vals]
        from paddle_tpu.amp import debugging as _dbg

        if _dbg.check_numerics_enabled():
            # compiled-path sanitizer for the TRAINING hot loop: checkify
            # instruments every float op of fwd+bwd+update (the reference's
            # per-instruction FLAGS_check_nan_inf); debug mode only
            if getattr(self, "_jitted_checked", None) is None:
                from jax.experimental import checkify as _checkify

                # keep the offload out_shardings: the debug step must not
                # migrate pinned-host optimizer state into HBM
                osh = getattr(self, "_out_shardings", None)
                self._jitted_checked = jax.jit(
                    _checkify.checkify(self._step,
                                       errors=_checkify.float_checks),
                    out_shardings=(None, osh) if osh is not None else None)
            err, (loss_val, new_params, new_states, new_masters,
                  new_buffer_vals, new_scaler_state, aux_vals) = \
                self._jitted_checked(
                    param_vals, opt_states, master_vals, buffer_vals,
                    batch_vals, lr, key, scale)
            err.throw()
        else:
            # train.dispatch: HOST time to enqueue the compiled step — in a
            # dispatch-ahead loop this (plus the input pop) is the whole
            # per-step host cost; device completion is read later
            from paddle_tpu.profiler import RecordEvent as _RE
            from paddle_tpu.profiler import TracerEventType as _TET

            with _RE("train.dispatch", _TET.Operator):
                (loss_val, new_params, new_states, new_masters,
                 new_buffer_vals, new_scaler_state, aux_vals) = self._jitted(
                    param_vals, opt_states, master_vals, buffer_vals,
                    batch_vals, lr, key, scale
                )
            if self._donate_argnums:
                # deleted-buffer shells: donation_report()'s evidence
                self._last_donated = {
                    # graft-lint: disable-next=donation-alias (the deleted
                    # shells ARE donation_report()'s cache-probe evidence)
                    "params": list(param_vals),
                    # graft-lint: disable-next=donation-alias (same: shells
                    # are probed via is_deleted(), contents never read)
                    "batch": (batch_vals if self._donate_inputs else None),
                }
            if self._donate_inputs and 4 in self._donate_argnums:
                # enforce the contract on the caller's handles: donated
                # batch Tensors are dead, and a re-read must RAISE even on
                # backends where the donation was unusable and jax left
                # the buffer alive (dropping the ref frees it either way)
                for leaf in jax.tree_util.tree_leaves(
                        batch, is_leaf=lambda x: isinstance(x, Tensor)):
                    if isinstance(leaf, Tensor):
                        leaf._replace_value(_DonatedValue())
        # device observability: record this step's program specs on cache
        # growth (cost inventory) and account weights / optimizer slots /
        # fp32 masters with the device ledger exactly once — steady-state
        # cost is one int compare and one is-None check
        n_now = _jit_cache_size(self._jitted)
        if n_now != self._seen_programs:
            self._seen_programs = n_now
            get_program_inventory().capture(
                self._tracker_name, "train_step", self._jitted,
                (param_vals, opt_states, master_vals, buffer_vals,  # graft-lint: disable=donation-alias
                 batch_vals, lr, key, scale),  # graft-lint: disable=donation-alias
                donate_argnums=self._donate_argnums)
        if self._ledger_handles is None:
            from paddle_tpu.observability.device_memory import (
                get_device_ledger,
                tree_nbytes,
            )

            led = get_device_ledger()
            self._ledger_handles = (
                led.register("model_weights", self._tracker_name,
                             tree_nbytes(new_params)),
                led.register("optimizer_slots", self._tracker_name,
                             tree_nbytes(new_states)),
                led.register("fp32_masters", self._tracker_name,
                             tree_nbytes([m for m in new_masters
                                          if m is not None])),
            )
        offload_params = getattr(self._opt, "_offload_params", False)
        for p, v in zip(params, new_params):
            p._replace_value(v)
        if self._offload_post:
            from paddle_tpu.distributed.sharding import to_host_memory

            new_states = [
                jax.tree_util.tree_map(to_host_memory, st)
                for st in new_states
            ]
            new_masters = [mv if mv is None else to_host_memory(mv)
                           for mv in new_masters]
            if offload_params:
                for p in params:
                    p._replace_value(to_host_memory(p._value))
        for p, st in zip(params, new_states):
            self._opt._state[id(p)] = st
        for p, mv in zip(params, new_masters):
            if mv is not None:
                self._opt._master_weights[id(p)] = mv
        for b, v in zip(buffers, new_buffer_vals):
            b._replace_value(v)
        self._opt._step_count += 1
        if new_scaler_state is not None:
            self._scaler_state = new_scaler_state  # device-side, no sync
        if hasattr(self._opt._lr, "step"):
            pass  # caller drives scheduler.step() as in paddle
        hook = getattr(self._opt, "_post_step_hook", None)
        if hook is not None:
            hook()  # e.g. ASP re-masking (the wrapper's step() is bypassed)
        if self._nonblocking:
            return NonBlockingStepResult(loss_val, aux_vals, self._has_aux)
        loss_t = Tensor._from_value(loss_val)
        if self._has_aux:
            return loss_t, tree_wrap(aux_vals)
        return loss_t

    def __del__(self):
        # return this step's weights/slots/masters bytes to the ledger so
        # short-lived TrainSteps (bench phases, tests) don't accumulate;
        # release() is idempotent, but interpreter teardown may reach the
        # ledger after its module globals are already gone
        for h in (getattr(self, "_ledger_handles", None) or ()):
            try:
                h.release()
            except Exception:  # graft-lint: disable=swallowed-exception
                pass
