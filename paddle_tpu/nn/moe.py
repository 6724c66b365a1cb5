"""Dropless sparse-expert feed-forward layer that holds a share of the experts.

The served counterpart of ``incubate/distributed/models/moe/moe_layer.py``
(which stays as the reference-parity GShard layer: capacity buckets, a dense
``[T, k, E, C]`` dispatch, a Python list of expert Layers, tokens over
capacity dropped). This one is built for a compiled serving step:

- the router keeps its published width: sigmoid scores over all ``E``
  experts in float32, the chosen set is the top ``k`` of ``score + bias``
  (the ``noaux_tc`` correction bias chooses, it does not weigh), and the
  weights are the chosen scores normalised over all ``k`` chosen, times
  ``routed_scaling_factor`` where a model states one;
- the layer is told which experts it holds (``first, count``: expert
  parallelism's share of one chip) and computes
  ``sum_{e chosen and held} w_e E_e(x)``: what the absent experts would have
  added is left out, and that partial sum goes on to the next layer. Nothing
  here stands in for the other chips or their exchange;
- a shared expert (``shared_width``), where the model has one, is a dense
  SwiGLU over every token that every chip of the deployment computes alike:
  it is computed whole here and added to the held experts' partial sum;
- no capacity and no drops: the ``T * k`` (token, expert) pairs are sorted by
  expert, pairs of experts held elsewhere go to the end, and one grouped
  product over the stacked weights ``[count, H, 2F]`` / ``[count, F, H]``
  computes the held pairs only (rows past the groups are not multiplied).
  Every shape is static, so a serving step stays one program: the pair
  buffer the products run over is 4 x the share of the pairs a uniform
  router sends here, with the whole ``T * k`` buffer as the branch a step
  takes when its routing overflows that (``lax.cond``; never a drop).

The grouped product has one gate, decided at trace time from what the code
can observe: on a TPU with shapes the kernel tiles, the Pallas grouped
matmul that JAX ships (``megablox.gmm``: it streams each expert's weights
once for the row tiles that hold its pairs); everywhere else
``lax.ragged_dot``. ``_last_path`` records which.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.param_attr import ParamAttr
from paddle_tpu.observability.step_profile import region

# evidence trail: "gmm" | "ragged_dot", set on every trace of the layer
_last_path = None

# row tile of the Pallas grouped matmul; the pair buffer is padded to it
_GMM_ROWS = 128


def route(x, router_w, bias, top_k: int, scaling: float = 1.0):
    """``(experts [T, k] int32, weights [T, k] float32)`` of tokens
    ``x [T, H]``: sigmoid scores in float32, top ``k`` of score + bias,
    weights normalised over the chosen, times ``scaling`` (at 1.0 the
    program is the one without it)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x, router_w, preferred_element_type=jnp.float32).astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True)
    if scaling != 1.0:
        weights = weights * scaling
    return experts.astype(jnp.int32), weights


def _gmm_tiles(m: int, k: int, n: int):
    """Tile sizes of the Pallas grouped matmul for ``[m, k] x [g, k, n]``,
    or ``None`` where the shapes do not tile: rows in tiles of 128, and the
    widest 128-multiples of ``k`` up to 1024 and of ``n`` up to 2048 (a
    weight tile of 4 MiB in bf16, double-buffered). Measured on a v5e at
    16 x [4096, 4096] with 64 live pairs in 1024 rows (PERF.md, PR 28):
    0.757 ms at (128, 1024, 2048) = 87 % of the weights' HBM time, 0.80 at
    (128, 1024, 1024), 0.90 at (256, ..); ``lax.ragged_dot`` 1.71 ms."""
    def widest(d, top):
        return next((t for t in (2048, 1024, 512, 256, 128)
                     if t <= top and d % t == 0), None)

    tk, tn = widest(k, 1024), widest(n, 2048)
    if m % _GMM_ROWS or tk is None or tn is None:
        return None
    return _GMM_ROWS, tk, tn


def grouped_matmul(x, w, group_sizes):
    """Rows of ``x [M, K]`` in consecutive groups, group ``g`` of
    ``group_sizes[g]`` rows times ``w[g] [K, N]``; rows past the last group
    are not computed and read as zero or garbage (the caller masks them)."""
    global _last_path
    from paddle_tpu.device import is_tpu

    tiles = _gmm_tiles(x.shape[0], x.shape[1], w.shape[2])
    if is_tpu() and tiles is not None and x.dtype == jnp.bfloat16:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        _last_path = "gmm"
        return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                   tiling=tiles)
    _last_path = "ragged_dot"
    return jax.lax.ragged_dot(x, w, group_sizes)


def _dropless_raw(x, router_w, bias, w_in, w_out, *, top_k: int, first: int,
                  scaling: float = 1.0):
    """``(partial sum [T, H], stats f32[2], experts [T, k])`` of tokens
    ``x [T, H]`` over the experts ``first .. first + count`` whose stacked
    weights are ``w_in [count, H, 2F]`` (gate | up) and ``w_out [count, F,
    H]``. The stats are the pairs routed to held experts and the largest
    held expert's load; ``experts`` is the router's choice."""
    tokens, hidden = x.shape
    count, width = w_out.shape[0], w_out.shape[1]
    with region("moe_route"):
        experts, weights = route(x, router_w, bias, top_k, scaling)
        local = experts - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count).reshape(-1)        # [T * k]
        order = jnp.argsort(key, stable=True)
        pairs = tokens * top_k
        rows = -(-pairs // _GMM_ROWS) * _GMM_ROWS
        order = jnp.pad(order, (0, rows - pairs))
        group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        live = jnp.arange(rows) < group_sizes.sum()
        token_of = order // top_k
        weight_of = jnp.where(live, weights.reshape(-1)[order], 0.0)
    def experts_over(n):
        """The held pairs' weighted outputs summed into their tokens, over
        the first ``n`` rows of the sorted pair buffer (the held pairs
        come first, so ``n`` rows hold them all whenever they are that
        few)."""
        tok = token_of[:n]
        xs = x[tok]                                            # [n, H]
        gate_up = grouped_matmul(xs, w_in, group_sizes)
        act = (jax.nn.silu(gate_up[:, :width].astype(jnp.float32))
               * gate_up[:, width:].astype(jnp.float32)).astype(x.dtype)
        ys = grouped_matmul(act, w_out, group_sizes)
        ys = jnp.where(live[:n, None],
                       ys.astype(jnp.float32) * weight_of[:n, None], 0.0)
        return jnp.zeros((tokens, hidden), jnp.float32).at[tok].add(ys)

    with region("moe_experts"):
        # a share of count / E of the experts draws that share of the
        # pairs: the products run over a buffer of 4 x that expectation,
        # and over the whole pair buffer on the rare step that overflows
        # it, so nothing is dropped and no shape depends on the routing
        share = -(-pairs * count // router_w.shape[1])
        few = min(rows, -(-4 * share // _GMM_ROWS) * _GMM_ROWS)
        if few < rows:
            out = jax.lax.cond(group_sizes.sum() <= few,
                               lambda: experts_over(few),
                               lambda: experts_over(rows))
        else:
            out = experts_over(rows)
    stats = jnp.stack([group_sizes.sum(), group_sizes.max()]).astype(
        jnp.float32)
    return out.astype(x.dtype), stats, experts


def step_stats(layers):
    """``(names, traced f32 values)`` of the last forward of a model whose
    ``layers`` hold these expert layers, for the compiled serving step's
    telemetry block (``model.step_stats()``): pairs routed to held experts
    and the largest held expert's load, each a mean over the layers."""
    stats = [l.last_stats for l in layers]
    if not stats or any(s is None for s in stats):
        return (), None
    mean = apply("moe_step_stats",
                 lambda *s: jnp.mean(jnp.stack(s), axis=0), *stats,
                 differentiable=False)
    return ("moe_pairs_held", "moe_load_max"), mean


def _shared_raw(x, w_gate, w_up, w_down):
    """The shared expert: SwiGLU of ``x [T, H]``, the activation in float32
    as in the routed experts."""
    with region("moe_shared"):
        act = (jax.nn.silu(jnp.dot(x, w_gate).astype(jnp.float32))
               * jnp.dot(x, w_up).astype(jnp.float32)).astype(x.dtype)
        return jnp.dot(act, w_down)


class DroplessMoE(nn.Layer):
    """Router over ``num_experts`` and the stacked weights of the
    ``experts_held = (first, count)`` experts this layer holds (all of them
    by default). ``forward(x [..., H])`` returns the held experts' partial
    sum (the routed weights times ``routed_scaling_factor``) plus, where
    ``shared_width`` is given, a shared expert of that width over every
    token; ``last_stats`` is then the f32[2] ``[pairs to held experts,
    largest held expert's load]`` of that call and ``last_experts`` the
    router's choice ``[T, k]`` (traced values inside a compiled step, for
    whoever reads them in the same trace; unread, they cost nothing)."""

    def __init__(self, hidden_size: int, expert_width: int, num_experts: int,
                 top_k: int, experts_held=None, initializer_range: float = 0.02,
                 dtype=None, routed_scaling_factor: float = 1.0,
                 shared_width: int = 0):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and first + count <= num_experts and count > 0):
            raise ValueError(f"experts_held {experts_held} is not a share of "
                             f"{num_experts} experts")
        self.top_k, self.first, self.count = int(top_k), int(first), int(count)
        self.scaling = float(routed_scaling_factor)
        normal = ParamAttr(initializer=I.Normal(0.0, initializer_range))
        self.router = self.create_parameter(
            [hidden_size, num_experts], attr=normal, dtype=dtype)
        # seeded like a weight, so that choosing differs from weighing
        self.e_score_correction_bias = self.create_parameter(
            [num_experts], attr=normal, dtype="float32")
        self.w_in = self.create_parameter(
            [count, hidden_size, 2 * expert_width], attr=normal, dtype=dtype)
        self.w_out = self.create_parameter(
            [count, expert_width, hidden_size], attr=normal, dtype=dtype)
        self.shared_width = int(shared_width)
        if shared_width:
            self.shared_gate, self.shared_up, self.shared_down = (
                self.create_parameter(shape, attr=normal, dtype=dtype)
                for shape in ([hidden_size, shared_width],
                              [hidden_size, shared_width],
                              [shared_width, hidden_size]))
        self.last_stats = self.last_experts = None

    def forward(self, x):
        shape = x.shape
        flat = x.reshape([-1, shape[-1]])
        out, self.last_stats, self.last_experts = apply(
            "dropless_moe", _dropless_raw, flat, self.router,
            self.e_score_correction_bias, self.w_in, self.w_out,
            top_k=self.top_k, first=self.first, scaling=self.scaling)
        if self.shared_width:
            out = out + apply("moe_shared_expert", _shared_raw, flat,
                              self.shared_gate, self.shared_up,
                              self.shared_down)
        return out.reshape(shape)
