"""Special functions, norms, and tensor-misc ops from the reference manifest.

Parity targets: paddle/phi/ops/yaml/ops.yaml entries (gammaln, i0e, p_norm,
diag_embed, fill_diagonal, multiplex, ...). Implementations are jnp/lax
compositions; XLA fuses them — there is no hand-written kernel to match
because on TPU the fusion IS the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dispatch import apply
from paddle_tpu.ops.registry import register_op
from paddle_tpu.tensor import Tensor

# ------------------------------------------------------------- special funcs


@register_op("gammaln")
def gammaln(x, name=None):
    return apply("gammaln", lambda a: jax.scipy.special.gammaln(a), x)


@register_op("gammaincc")
def gammaincc(x, y, name=None):
    """Regularized upper incomplete gamma Q(x, y) (phi gammaincc_kernel)."""
    return apply("gammaincc", lambda a, b: jax.scipy.special.gammaincc(a, b), x, y)


@register_op("i0e")
def i0e(x, name=None):
    return apply("i0e", lambda a: jax.scipy.special.i0e(a), x)


@register_op("i1e")
def i1e(x, name=None):
    return apply("i1e", lambda a: jax.scipy.special.i1e(a), x)


@register_op("polygamma")
def polygamma(x, n, name=None):
    return apply("polygamma", lambda a: jax.scipy.special.polygamma(n, a), x)


# ------------------------------------------------------------------- complex


@register_op("complex")
def complex(real, imag, name=None):
    return apply("complex", jax.lax.complex, real, imag)


@register_op("as_complex")
def as_complex(x, name=None):
    """[..., 2] float -> [...] complex (phi as_complex_kernel)."""
    return apply("as_complex",
                 lambda a: jax.lax.complex(a[..., 0], a[..., 1]), x)


@register_op("as_real")
def as_real(x, name=None):
    return apply("as_real",
                 lambda a: jnp.stack([jnp.real(a), jnp.imag(a)], axis=-1), x)


# --------------------------------------------------------------------- norms


def guarded_root(s, porder, epsilon=1e-12):
    """s ** (1/p) whose FORWARD is exact (||0|| == 0, no eps bias) and
    whose backward applies the epsilon divide-guard the reference p_norm
    kernel uses, so the grad at s == 0 is finite (0) instead of nan."""

    @jax.custom_vjp
    def root(sv):
        return sv ** (1.0 / porder)

    def root_fwd(sv):
        return root(sv), sv

    def root_bwd(sv, ct):
        return (ct * (1.0 / porder)
                * (sv + epsilon) ** (1.0 / porder - 1.0),)

    root.defvjp(root_fwd, root_bwd)
    return root(s)


@register_op("p_norm")
def p_norm(x, porder=2.0, axis=-1, epsilon=1e-12, keepdim=False,
           asvector=False, name=None):
    def f(a):
        v = a.reshape(-1) if asvector else a
        ax = None if asvector else axis
        if porder == float("inf"):
            return jnp.max(jnp.abs(v), axis=ax, keepdims=keepdim)
        if porder == float("-inf"):
            return jnp.min(jnp.abs(v), axis=ax, keepdims=keepdim)
        if porder == 0:
            return jnp.sum((v != 0).astype(a.dtype), axis=ax, keepdims=keepdim)
        # epsilon guards ONLY the backward's s**(1/p - 1) divide (the
        # reference kernel's use); adding it to the forward value biases
        # the norm by eps^(1/p) — e.g. ||0||_2 == 1e-6 (ADVICE r4)
        s = jnp.sum(jnp.abs(v) ** porder, axis=ax, keepdims=keepdim)
        return guarded_root(s, porder, epsilon)

    return apply("p_norm", f, x)


@register_op("frobenius_norm")
def frobenius_norm(x, axis=None, keepdim=False, name=None):
    def f(a):
        ax = tuple(axis) if isinstance(axis, (list, tuple)) else axis
        return jnp.sqrt(jnp.sum(a * a, axis=ax, keepdims=keepdim))

    return apply("frobenius_norm", f, x)


@register_op("l1_norm")
def l1_norm(x, name=None):
    return apply("l1_norm", lambda a: jnp.sum(jnp.abs(a)), x)


@register_op("squared_l2_norm")
def squared_l2_norm(x, name=None):
    return apply("squared_l2_norm", lambda a: jnp.sum(a * a), x)


@register_op("clip_by_norm")
def clip_by_norm(x, max_norm, name=None):
    def f(a):
        norm = jnp.sqrt(jnp.sum(a * a))
        return jnp.where(norm > max_norm, a * (max_norm / norm), a)

    return apply("clip_by_norm", f, x)


@register_op("mean_all")
def mean_all(x, name=None):
    return apply("mean_all", jnp.mean, x)


@register_op("reduce_as")
def reduce_as(x, target, name=None):
    """Sum-reduce x to target's shape (phi reduce_as_kernel)."""
    tshape = target.shape if isinstance(target, Tensor) else tuple(target)

    def f(a):
        ndiff = a.ndim - len(tshape)
        axes = tuple(range(ndiff)) + tuple(
            i + ndiff for i, d in enumerate(tshape) if a.shape[i + ndiff] != d)
        out = jnp.sum(a, axis=axes, keepdims=False)
        return out.reshape(tshape)

    return apply("reduce_as", f, x)


@register_op("divide_scalar")
def divide_scalar(x, scalar, name=None):
    return apply("divide_scalar", lambda a: a / scalar, x)


# ----------------------------------------------------------- diagonal / fill


@register_op("diag_embed")
def diag_embed(x, offset=0, dim1=-2, dim2=-1, name=None):
    def f(a):
        n = a.shape[-1] + abs(offset)
        out = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
        rows = jnp.arange(a.shape[-1]) + max(-offset, 0)
        cols = jnp.arange(a.shape[-1]) + max(offset, 0)
        out = out.at[..., rows, cols].set(a)
        # move the two new axes to dim1/dim2
        d1 = dim1 % (out.ndim)
        d2 = dim2 % (out.ndim)
        perm = [i for i in range(out.ndim) if i not in (out.ndim - 2, out.ndim - 1)]
        order = sorted([(d1, out.ndim - 2), (d2, out.ndim - 1)])
        for pos, src in order:
            perm.insert(pos, src)
        return jnp.transpose(out, perm)

    return apply("diag_embed", f, x)


@register_op("fill")
def fill(x, value, name=None):
    """In-place full fill (phi fill_kernel); returns x."""
    x._value = jnp.full_like(x._value, value)
    return x


@register_op("fill_diagonal")
def fill_diagonal(x, value=0.0, offset=0, wrap=False, name=None):
    def f(a):
        if a.ndim == 2 and wrap:
            # numpy-style wrapped diagonal: every (cols+1)-th flat element
            rows, cols = a.shape
            flat = a.reshape(-1)
            return flat.at[::cols + 1].set(value).reshape(a.shape)
        n = min(a.shape[-2], a.shape[-1]) - abs(offset)
        i = jnp.arange(n) + max(-offset, 0)
        j = jnp.arange(n) + max(offset, 0)
        return a.at[..., i, j].set(value)

    x._value = f(x._value)
    return x


@register_op("fill_diagonal_tensor")
def fill_diagonal_tensor(x, y, offset=0, dim1=0, dim2=1, name=None):
    def f(a, b):
        d1, d2 = dim1 % a.ndim, dim2 % a.ndim
        perm = [i for i in range(a.ndim) if i not in (d1, d2)] + [d1, d2]
        ap = jnp.transpose(a, perm)
        n = min(ap.shape[-2], ap.shape[-1]) - abs(offset)
        i = jnp.arange(n) + max(-offset, 0)
        j = jnp.arange(n) + max(offset, 0)
        ap = ap.at[..., i, j].set(b)
        inv = np.argsort(perm)
        return jnp.transpose(ap, inv)

    return apply("fill_diagonal_tensor", f, x, y)


@register_op("tril_indices", differentiable=False)
def tril_indices(rows, cols=None, offset=0, dtype="int64", name=None):
    r, c = np.tril_indices(rows, offset, cols or rows)
    return Tensor._from_value(jnp.asarray(np.stack([r, c]), jnp.int64
                                          if str(dtype).endswith("64") else jnp.int32))


@register_op("triu_indices", differentiable=False)
def triu_indices(rows, cols=None, offset=0, dtype="int64", name=None):
    r, c = np.triu_indices(rows, offset, cols or rows)
    return Tensor._from_value(jnp.asarray(np.stack([r, c]), jnp.int64
                                          if str(dtype).endswith("64") else jnp.int32))


# ------------------------------------------------------------ rearrangement


@register_op("unstack")
def unstack(x, axis=0, num=None, name=None):
    n = num or x.shape[axis]
    outs = []
    for i in range(n):
        outs.append(apply("unstack", lambda a, i=i: jnp.take(a, i, axis=axis), x))
    return outs


@register_op("reverse")
def reverse(x, axis, name=None):
    ax = tuple(axis) if isinstance(axis, (list, tuple)) else (axis,)
    return apply("reverse", lambda a: jnp.flip(a, ax), x)


@register_op("multiplex")
def multiplex(inputs, index, name=None):
    """out[i] = inputs[index[i]][i] (phi multiplex_kernel)."""
    def f(idx, *ins):
        stacked = jnp.stack(ins)  # [n_ins, batch, ...]
        return jnp.take_along_axis(
            stacked, idx.reshape(1, -1, *([1] * (stacked.ndim - 2))), axis=0
        )[0]

    return apply("multiplex", f, index, *inputs)


@register_op("crop")
def crop(x, shape=None, offsets=None, name=None):
    shape = [int(s) for s in (shape or x.shape)]
    offsets = [int(o) for o in (offsets or [0] * len(shape))]
    sl = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return apply("crop", lambda a: a[sl], x)


@register_op("index_select_strided")
def index_select_strided(x, index, axis=0, name=None):
    return apply("index_select_strided",
                 lambda a, i: jnp.take(a, i, axis=axis), x, index)


@register_op("repeat_interleave_with_tensor_index")
def repeat_interleave_with_tensor_index(x, repeats, axis=None, name=None):
    rep = np.asarray(repeats.numpy() if isinstance(repeats, Tensor) else repeats)

    def f(a):
        if axis is None:
            return jnp.repeat(a.reshape(-1), jnp.asarray(rep))
        return jnp.repeat(a, jnp.asarray(rep), axis=axis)

    return apply("repeat_interleave_with_tensor_index", f, x)


@register_op("tensor_unfold")
def tensor_unfold(x, axis, size, step, name=None):
    def f(a):
        n = (a.shape[axis] - size) // step + 1
        idx = jnp.arange(n)[:, None] * step + jnp.arange(size)[None, :]
        win = jnp.take(a, idx.reshape(-1), axis=axis)
        shp = list(a.shape)
        shp[axis:axis + 1] = [n, size]
        win = win.reshape([*a.shape[:axis], n, size, *a.shape[axis + 1:]])
        # paddle unfold puts the window dim last
        return jnp.moveaxis(win, axis + 1, -1)

    return apply("tensor_unfold", f, x)


@register_op("view_dtype")
def view_dtype(x, dtype, name=None):
    from paddle_tpu.framework.dtype import convert_dtype
    return apply("view_dtype",
                 lambda a: jax.lax.bitcast_convert_type(a, convert_dtype(dtype)), x)


@register_op("view_shape")
def view_shape(x, shape, name=None):
    return apply("view_shape", lambda a: a.reshape(shape), x)


@register_op("set_value_with_tensor")
def set_value_with_tensor(x, value, starts, ends, steps=None, axes=None,
                          name=None):
    axes = list(axes or range(len(starts)))
    steps = list(steps or [1] * len(starts))

    def f(a, v):
        idx = [slice(None)] * a.ndim
        for ax, s, e, st in zip(axes, starts, ends, steps):
            idx[ax] = slice(int(s), int(e), int(st))
        return a.at[tuple(idx)].set(v)

    return apply("set_value_with_tensor", f, x, value)


@register_op("split_with_num")
def split_with_num(x, num, axis=0, name=None):
    outs = []
    sz = x.shape[axis] // num
    for i in range(num):
        outs.append(apply(
            "split_with_num",
            lambda a, i=i: jax.lax.slice_in_dim(a, i * sz, (i + 1) * sz, axis=axis),
            x))
    return outs


@register_op("shape", differentiable=False)
def shape(x, name=None):
    return Tensor._from_value(jnp.asarray(x._value.shape, jnp.int32))


@register_op("partial_concat")
def partial_concat(inputs, start_index=0, length=-1, name=None):
    def f(*ins):
        outs = []
        for a in ins:
            end = a.shape[1] if length < 0 else start_index + length
            outs.append(a[:, start_index:end])
        return jnp.concatenate(outs, axis=1)

    return apply("partial_concat", f, *inputs)


@register_op("partial_sum")
def partial_sum(inputs, start_index=0, length=-1, name=None):
    def f(*ins):
        outs = []
        for a in ins:
            end = a.shape[1] if length < 0 else start_index + length
            outs.append(a[:, start_index:end])
        return sum(outs[1:], outs[0])

    return apply("partial_sum", f, *inputs)


@register_op("bilinear")
def bilinear(x1, x2, weight, bias=None, name=None):
    """out[n,o] = x1[n,:] @ W[o] @ x2[n,:] + b (phi bilinear_kernel)."""
    def f(a, b, w, *bb):
        out = jnp.einsum("ni,oij,nj->no", a, w, b)
        return out + bb[0] if bb else out

    args = (x1, x2, weight) + ((bias,) if bias is not None else ())
    return apply("bilinear", f, *args)


@register_op("lu_unpack")
def lu_unpack(x, y, unpack_ludata=True, unpack_pivots=True, name=None):
    """Unpack compact LU + pivots into (P, L, U) (phi lu_unpack_kernel)."""
    def f(lu, piv):
        m, n = lu.shape[-2], lu.shape[-1]
        k = min(m, n)
        L = jnp.tril(lu[..., :, :k], -1) + jnp.eye(m, k, dtype=lu.dtype)
        U = jnp.triu(lu[..., :k, :])
        # pivots (1-based row swaps) -> permutation matrix
        def perm_of(pv):
            perm = jnp.arange(m)

            def body(i, p):
                j = pv[i] - 1
                pi, pj = p[i], p[j]
                return p.at[i].set(pj).at[j].set(pi)

            perm = jax.lax.fori_loop(0, pv.shape[0], body, perm)
            return jnp.eye(m, dtype=lu.dtype)[perm].T

        if piv.ndim == 1:
            P = perm_of(piv)
        else:
            P = jnp.stack([perm_of(p) for p in piv.reshape(-1, piv.shape[-1])])
            P = P.reshape(piv.shape[:-1] + (m, m))
        return P, L, U

    return apply("lu_unpack", f, x, y)


@register_op("matrix_rank_tol", differentiable=False)
def matrix_rank_tol(x, atol_tensor=None, rtol_tensor=None, use_default_tol=True,
                    hermitian=False, name=None):
    """Rank via singular values > tol (phi matrix_rank_tol_kernel)."""
    def f(a, *tols):
        s = jnp.abs(jnp.linalg.eigvalsh(a)) if hermitian \
            else jnp.linalg.svd(a, compute_uv=False)
        smax = jnp.max(s, axis=-1, keepdims=True)
        if tols:
            tol = tols[0].reshape(tols[0].shape + (1,) * (s.ndim - tols[0].ndim))
        else:
            eps = jnp.finfo(a.dtype).eps
            tol = max(a.shape[-2], a.shape[-1]) * eps * smax
        return jnp.sum(s > tol, axis=-1).astype(jnp.int64)

    args = (x,) + ((atol_tensor,) if atol_tensor is not None else ())
    return apply("matrix_rank_tol", f, *args)


# ------------------------------------------------------- placement / assign


@register_op("copy_to", differentiable=False)
def copy_to(x, place=None, blocking=True, name=None):
    return Tensor._from_value(jax.device_put(x._value))


@register_op("memcpy_h2d", differentiable=False)
def memcpy_h2d(x, dst_place_type=1, name=None):
    return Tensor._from_value(jax.device_put(x._value))


@register_op("memcpy_d2h", differentiable=False)
def memcpy_d2h(x, dst_place_type=0, name=None):
    return Tensor._from_value(jnp.asarray(np.asarray(x._value)))


@register_op("trans_layout")
def trans_layout(x, perm, name=None):
    return apply("trans_layout", lambda a: jnp.transpose(a, perm), x)


@register_op("assign_out_")
def assign_out_(x, output, name=None):
    output._value = x._value.astype(output._value.dtype) \
        if output._value.dtype != x._value.dtype else x._value
    return output


@register_op("assign_value_", differentiable=False)
def assign_value_(output, shape=None, dtype=None, values=None, name=None):
    from paddle_tpu.framework.dtype import convert_dtype
    arr = np.asarray(values, dtype=np.dtype(str(convert_dtype(dtype or "float32"))))
    if shape:
        arr = arr.reshape(shape)
    output._value = jnp.asarray(arr)
    return output


@register_op("full_with_tensor", differentiable=False)
def full_with_tensor(value, shape, dtype=None, name=None):
    v = value._value if isinstance(value, Tensor) else value
    out = jnp.full(tuple(int(s) for s in shape), v)
    from paddle_tpu.framework.dtype import convert_dtype
    if dtype is not None:
        out = out.astype(convert_dtype(dtype))
    return Tensor._from_value(out)


@register_op("full_int_array", differentiable=False)
def full_int_array(values, dtype="int64", name=None):
    return Tensor._from_value(jnp.asarray(np.asarray(values, np.int64)))


@register_op("full_batch_size_like", differentiable=False)
def full_batch_size_like(input, shape, value, input_dim_idx=0, output_dim_idx=0,
                         dtype=None, name=None):
    shp = list(shape)
    shp[output_dim_idx] = input.shape[input_dim_idx]
    from paddle_tpu.framework.dtype import convert_dtype
    dt = convert_dtype(dtype or "float32")
    return Tensor._from_value(jnp.full(shp, value, dt))


@register_op("uniform_random_batch_size_like", differentiable=False)
def uniform_random_batch_size_like(input, shape, min=-1.0, max=1.0, seed=0,
                                   input_dim_idx=0, output_dim_idx=0,
                                   dtype=None, name=None):
    from paddle_tpu.framework import random as rng
    shp = list(shape)
    shp[output_dim_idx] = input.shape[input_dim_idx]
    key = rng.next_key() if seed == 0 else jax.random.PRNGKey(seed)
    return Tensor._from_value(
        jax.random.uniform(key, tuple(int(s) for s in shp),
                           minval=min, maxval=max))


@register_op("coalesce_tensor", differentiable=False)
def coalesce_tensor(inputs, dtype=None, copy_data=True, set_constant=False,
                    constant=0.0, name=None):
    """Fuse tensors into one contiguous buffer; returns (views, fused).

    Reference: coalesce_tensor_kernel — used for fused grad allreduce.
    """
    flats = [t._value.reshape(-1) for t in inputs]
    fused = jnp.concatenate(flats) if flats else jnp.zeros((0,))
    if set_constant:
        fused = jnp.full_like(fused, constant)
    views = []
    off = 0
    for t in inputs:
        n = int(np.prod(t.shape)) if t.ndim else 1
        views.append(Tensor._from_value(fused[off:off + n].reshape(t.shape)))
        off += n
    return views, Tensor._from_value(fused)


@register_op("merge_selected_rows", differentiable=False)
def merge_selected_rows(rows, values, height=None, name=None):
    """Deduplicate a rows/values sparse-gradient pair (SelectedRows analogue):
    duplicate row ids have their value slices summed (segment_sum)."""
    def f(r, v):
        uniq, inv = jnp.unique(r, return_inverse=True, size=r.shape[0],
                               fill_value=-1)
        summed = jax.ops.segment_sum(v, inv.reshape(-1), num_segments=r.shape[0])
        return uniq, summed

    r = rows._value if isinstance(rows, Tensor) else jnp.asarray(rows)
    v = values._value if isinstance(values, Tensor) else jnp.asarray(values)
    uniq, summed = f(r, v)
    return Tensor._from_value(uniq), Tensor._from_value(summed)


# ----------------------------------------------------------------- metrics


@register_op("accuracy", differentiable=False)
def accuracy(x, indices, label, name=None):
    """Top-k accuracy given topk (values-ignored) indices (phi accuracy_kernel).
    Returns (accuracy, correct, total)."""
    idx = indices._value
    lab = label._value.reshape(-1, 1)
    correct_mat = (idx == lab).any(axis=1)
    correct = jnp.sum(correct_mat.astype(jnp.int32))
    total = lab.shape[0]
    acc = correct.astype(jnp.float32) / total
    return (Tensor._from_value(acc), Tensor._from_value(correct),
            Tensor._from_value(jnp.asarray(total, jnp.int32)))


@register_op("accuracy_check", differentiable=False)
def accuracy_check(x, y, rtol=1e-5, atol=1e-8, equal_nan=False, fn_name="",
                   name=None):
    ok = jnp.allclose(x._value, y._value, rtol=float(rtol), atol=float(atol),
                      equal_nan=equal_nan)
    return Tensor._from_value(ok)


@register_op("auc", differentiable=False)
def auc(predict, label, stat_pos=None, stat_neg=None, num_thresholds=4095,
        curve="ROC", slide_steps=1, name=None):
    """Binned ROC-AUC with accumulation buffers (phi auc_kernel)."""
    probs = predict._value[:, -1] if predict._value.ndim == 2 \
        else predict._value.reshape(-1)
    lab = label._value.reshape(-1)
    bins = jnp.clip((probs * num_thresholds).astype(jnp.int32), 0,
                    num_thresholds)
    pos_hist = jnp.zeros(num_thresholds + 1, jnp.int64).at[bins].add(
        (lab == 1).astype(jnp.int64))
    neg_hist = jnp.zeros(num_thresholds + 1, jnp.int64).at[bins].add(
        (lab == 0).astype(jnp.int64))
    if stat_pos is not None:
        pos_hist = pos_hist + stat_pos._value
        neg_hist = neg_hist + stat_neg._value
    # integrate: walk thresholds high->low
    pos_c = jnp.cumsum(pos_hist[::-1])
    neg_c = jnp.cumsum(neg_hist[::-1])
    tot_pos, tot_neg = pos_c[-1], neg_c[-1]
    # trapezoid over (fpr, tpr)
    tpr = pos_c / jnp.maximum(tot_pos, 1)
    fpr = neg_c / jnp.maximum(tot_neg, 1)
    a = jnp.trapezoid(tpr, fpr) if hasattr(jnp, "trapezoid") else jnp.trapz(tpr, fpr)
    return (Tensor._from_value(a.astype(jnp.float32)),
            Tensor._from_value(pos_hist), Tensor._from_value(neg_hist))


@register_op("edit_distance", differentiable=False)
def edit_distance(hyps, refs, hypslength=None, refslength=None,
                  normalized=True, name=None):
    """Batched Levenshtein distance (phi edit_distance kernel). Host-side
    numpy DP — metric op, matches the reference's CPU-only kernel."""
    h = np.asarray(hyps.numpy() if isinstance(hyps, Tensor) else hyps)
    r = np.asarray(refs.numpy() if isinstance(refs, Tensor) else refs)
    hl = (np.asarray(hypslength.numpy()) if hypslength is not None
          else np.full(h.shape[0], h.shape[1]))
    rl = (np.asarray(refslength.numpy()) if refslength is not None
          else np.full(r.shape[0], r.shape[1]))
    out = np.zeros((h.shape[0], 1), np.float32)
    for b in range(h.shape[0]):
        m, n = int(hl[b]), int(rl[b])
        dp = np.arange(n + 1, dtype=np.float32)
        for i in range(1, m + 1):
            prev = dp.copy()
            dp[0] = i
            for j in range(1, n + 1):
                cost = 0 if h[b, i - 1] == r[b, j - 1] else 1
                dp[j] = min(prev[j] + 1, dp[j - 1] + 1, prev[j - 1] + cost)
        d = dp[n]
        out[b, 0] = d / n if (normalized and n) else d
    seq_num = Tensor._from_value(jnp.asarray(h.shape[0], jnp.int64))
    return Tensor._from_value(jnp.asarray(out)), seq_num


@register_op("identity_loss")
def identity_loss(x, reduction="none", name=None):
    red = {0: "sum", 1: "mean", 2: "none"}.get(reduction, reduction)
    if red == "mean":
        return apply("identity_loss", jnp.mean, x)
    if red == "sum":
        return apply("identity_loss", jnp.sum, x)
    return apply("identity_loss", lambda a: a, x)


@register_op("log_loss")
def log_loss(input, label, epsilon=1e-4, name=None):
    def f(p, y):
        return (-y * jnp.log(p + epsilon)
                - (1 - y) * jnp.log(1 - p + epsilon))

    return apply("log_loss", f, input, label)


@register_op("gather_tree", differentiable=False)
def gather_tree(ids, parents, name=None):
    """Beam-search backtrace (phi gather_tree_kernel): walk parent pointers
    from the last step back, as a reverse lax.scan."""
    def f(i, p):
        T = i.shape[0]

        def step(parent, t):
            out = jnp.take_along_axis(i[t], parent, axis=1)
            parent = jnp.take_along_axis(p[t], parent, axis=1)
            return parent, out

        init = jnp.tile(jnp.arange(i.shape[2])[None, :], (i.shape[1], 1))
        _, outs = jax.lax.scan(step, init, jnp.arange(T - 1, -1, -1))
        return outs[::-1]

    return apply("gather_tree", f, ids, parents)


# --------------------------------------------------------------------------
# r4 API-breadth sweep: the remaining top-level paddle.* tensor functions
# (reference python/paddle/tensor/{manipulation,math,creation,random,attribute,
# einsum}.py — each cited per op)
# --------------------------------------------------------------------------


def block_diag(inputs, name=None):
    """paddle.block_diag (tensor/creation.py): 2-D block-diagonal stack."""
    def f(*mats):
        mats = [m.reshape(1, 1) if m.ndim == 0
                else (m.reshape(1, -1) if m.ndim == 1 else m) for m in mats]
        rows = sum(m.shape[0] for m in mats)
        cols = sum(m.shape[1] for m in mats)
        out = jnp.zeros((rows, cols), mats[0].dtype)
        r = c = 0
        for m in mats:
            out = jax.lax.dynamic_update_slice(out, m, (r, c))
            r += m.shape[0]
            c += m.shape[1]
        return out

    return apply("block_diag", f, *inputs)


def tensor_split(x, num_or_indices, axis=0, name=None):
    """paddle.tensor_split (tensor/manipulation.py): numpy array_split
    semantics — uneven splits allowed."""
    def split_points(n):
        if isinstance(num_or_indices, int):
            k = num_or_indices
            base, extra = divmod(n, k)
            sizes = [base + 1] * extra + [base] * (k - extra)
            pts, acc = [], 0
            for s in sizes[:-1]:
                acc += s
                pts.append(acc)
            return pts
        return list(num_or_indices)

    # shape metadata only — never materialize the array
    n = x.shape[axis]
    ndim = len(x.shape)
    pts = split_points(n)
    pieces = []
    prev = 0
    for p in pts + [n]:
        idx = [slice(None)] * ndim
        idx[axis] = slice(prev, p)
        pieces.append(apply("tensor_split", lambda a, sl=tuple(idx): a[sl], x))
        prev = p
    return pieces


def hstack(x, name=None):
    """paddle.hstack (tensor/manipulation.py)."""
    def f(*ts):
        return jnp.hstack(ts)

    return apply("hstack", f, *x)


def vstack(x, name=None):
    def f(*ts):
        return jnp.vstack(ts)

    return apply("vstack", f, *x)


def dstack(x, name=None):
    def f(*ts):
        return jnp.dstack(ts)

    return apply("dstack", f, *x)


def sgn(x, name=None):
    """paddle.sgn (tensor/math.py): sign for real, x/|x| for complex."""
    def f(a):
        if jnp.iscomplexobj(a):
            mag = jnp.abs(a)
            return jnp.where(mag == 0, 0, a / jnp.maximum(mag, 1e-38))
        return jnp.sign(a)

    return apply("sgn", f, x)


def signbit(x, name=None):
    """paddle.signbit (tensor/math.py)."""
    return apply("signbit", lambda a: jnp.signbit(a), x)


def polar(abs, angle, name=None):  # noqa: A002 — paddle arg name
    """paddle.polar (tensor/creation.py): abs * exp(1j*angle); complex128
    for float64 inputs, complex64 otherwise (reference promotion)."""
    def f(r, t):
        cdt = (jnp.complex128 if r.dtype == jnp.float64
               else jnp.complex64)
        return (r * jnp.cos(t) + 1j * r * jnp.sin(t)).astype(cdt)

    return apply("polar", f, abs, angle)


def view_as(x, other, name=None):
    """paddle.view_as (tensor/manipulation.py): reshape to other's shape."""
    shp = tuple(other.shape)
    return apply("view_as", lambda a: a.reshape(shp), x)


def isin(x, test_x, assume_unique=False, invert=False, name=None):
    """paddle.isin (tensor/search.py)."""
    def f(a, t):
        out = jnp.isin(a, t.reshape(-1))
        return ~out if invert else out

    return apply("isin", f, x, test_x, differentiable=False)


def floor_mod(x, y, name=None):
    """paddle.floor_mod == paddle.remainder (tensor/math.py alias)."""
    return apply("floor_mod", lambda a, b: jnp.mod(a, b), x, y)


def broadcast_shape(x_shape, y_shape):
    """paddle.broadcast_shape (tensor/manipulation.py) — pure shape math."""
    return list(np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def is_floating_point(x):
    """paddle.is_floating_point (tensor/attribute.py). `.dtype` exists on
    Tensor and jax.Array alike — never touch `._value` (on a jax.Array it
    copies the array to the host)."""
    return jnp.issubdtype(jnp.dtype(x.dtype), jnp.floating)


def is_complex(x):
    return jnp.issubdtype(jnp.dtype(x.dtype), jnp.complexfloating)


def is_integer(x):
    return jnp.issubdtype(jnp.dtype(x.dtype), jnp.integer)


def diagonal_scatter(x, y, offset=0, axis1=0, axis2=1, name=None):
    """paddle.diagonal_scatter (tensor/manipulation.py): write y onto the
    selected diagonal of x."""
    def f(a, b):
        n = min(a.shape[axis1], a.shape[axis2])
        if offset >= 0:
            i = jnp.arange(min(n, a.shape[axis2] - offset))
            rows, cols = i, i + offset
        else:
            i = jnp.arange(min(n, a.shape[axis1] + offset))
            rows, cols = i - offset, i
        # move axis1/axis2 to the front so the .at indexing is general
        am = jnp.moveaxis(a, (axis1, axis2), (0, 1))
        bm = jnp.moveaxis(b, -1, 0) if b.ndim > 1 else b
        am = am.at[rows, cols].set(bm)
        return jnp.moveaxis(am, (0, 1), (axis1, axis2))

    return apply("diagonal_scatter", f, x, y)


def cumulative_trapezoid(y, x=None, dx=None, axis=-1, name=None):
    """paddle.cumulative_trapezoid (tensor/math.py)."""
    def f(yv, *rest):
        d = dx if dx is not None else 1.0
        yv1 = jnp.take(yv, jnp.arange(1, yv.shape[axis]), axis=axis)
        yv0 = jnp.take(yv, jnp.arange(0, yv.shape[axis] - 1), axis=axis)
        if rest:
            xv = rest[0]
            x1 = jnp.take(xv, jnp.arange(1, xv.shape[axis]), axis=axis)
            x0 = jnp.take(xv, jnp.arange(0, xv.shape[axis] - 1), axis=axis)
            d = x1 - x0
        return jnp.cumsum((yv0 + yv1) / 2.0 * d, axis=axis)

    args = (y,) if x is None else (y, x)
    return apply("cumulative_trapezoid", f, *args)


def combinations(x, r=2, with_replacement=False, name=None):
    """paddle.combinations (tensor/math.py): r-combinations of a 1-D
    tensor's elements."""
    import itertools as _it

    n = x.shape[0]
    picker = (_it.combinations_with_replacement if with_replacement
              else _it.combinations)
    idx = np.asarray(list(picker(range(n), r)), np.int32).reshape(-1, r)

    def f(a):
        return a[jnp.asarray(idx)]

    return apply("combinations", f, x)


def histogramdd(x, bins=10, ranges=None, density=False, weights=None,
                name=None):
    """paddle.histogramdd (tensor/linalg.py): D-dimensional histogram.
    Host computation (np.histogramdd) — binning is data-dependent."""
    xv = np.asarray(x.numpy() if hasattr(x, "numpy") else x)
    wv = (np.asarray(weights.numpy() if hasattr(weights, "numpy")
                     else weights) if weights is not None else None)
    if isinstance(bins, (list, tuple)) and len(bins) and hasattr(
            bins[0], "numpy"):
        bins = [np.asarray(b.numpy()) for b in bins]
    hist, edges = np.histogramdd(xv, bins=bins, range=ranges,
                                 density=density, weights=wv)
    return (Tensor(hist.astype(np.float32)),
            [Tensor(e.astype(np.float32)) for e in edges])


def gammainc(x, y, name=None):
    """paddle.gammainc: regularized lower incomplete gamma."""
    return apply("gammainc", lambda a, b: jax.scipy.special.gammainc(a, b),
                 x, y)


def multigammaln(x, p, name=None):
    """paddle.multigammaln (tensor/math.py)."""
    def f(a):
        j = jnp.arange(1, p + 1, dtype=a.dtype)
        return (p * (p - 1) / 4.0 * jnp.log(jnp.pi)
                + jnp.sum(jax.scipy.special.gammaln(
                    a[..., None] + (1.0 - j) / 2.0), axis=-1))

    return apply("multigammaln", f, x)


def log_normal(mean=1.0, std=2.0, shape=None, dtype=None, name=None):
    """paddle.log_normal (tensor/random.py): exp(normal(mean, std))."""
    from paddle_tpu.framework import random as _rng_mod

    def f():
        key = _rng_mod.next_key()
        samp = mean + std * jax.random.normal(
            key, tuple(shape or (1,)), jnp.float32)
        return jnp.exp(samp)

    return apply("log_normal", f, differentiable=False)





def randint_like(x, low=0, high=None, dtype=None, name=None):
    """paddle.randint_like (tensor/random.py)."""
    from paddle_tpu.framework import random as _rng_mod

    if high is None:
        low, high = 0, low

    def f(a):
        key = _rng_mod.next_key()
        return jax.random.randint(key, a.shape, low, high,
                                  dtype=jnp.int32)

    out = apply("randint_like", f, x, differentiable=False)
    # reference semantics: default dtype is X's dtype, not int32
    return out.astype(x.dtype if dtype is None else dtype)


class _DTypeInfo:
    def __init__(self, np_info, kind):
        self.min = (int(np_info.min) if kind == "i" else float(np_info.min))
        self.max = (int(np_info.max) if kind == "i" else float(np_info.max))
        self.bits = np_info.bits
        self.dtype = str(np_info.dtype)
        if kind == "f":
            self.eps = float(np_info.eps)
            self.tiny = float(np_info.tiny)
            self.smallest_normal = float(np_info.tiny)
            self.resolution = float(np_info.resolution)

    def __repr__(self):
        return f"{type(self).__name__}({self.dtype})"


def iinfo(dtype):
    """paddle.iinfo (python/paddle/framework/dtype.py iinfo parity)."""
    from paddle_tpu.framework import dtype as _dt

    return _DTypeInfo(np.iinfo(np.dtype(_dt.convert_dtype(dtype))), "i")


def finfo(dtype):
    """paddle.finfo."""
    from paddle_tpu.framework import dtype as _dt

    name = _dt.convert_dtype(dtype)
    try:
        info = np.finfo(np.dtype(name))
    except (TypeError, ValueError):
        # numpy's finfo rejects the ml_dtypes-registered types (bfloat16,
        # fp8) even though np.dtype resolves them
        import ml_dtypes

        info = ml_dtypes.finfo(name)
    return _DTypeInfo(info, "f")
