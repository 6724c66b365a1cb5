"""FusedAdamW: AdamW whose step is ONE Pallas kernel over the flat
parameter space (kernel: ops/pallas/fused_adamw.py).

Reference capability: multi-tensor fused optimizer updates
(distributed_fused_lamb's flat-buffer pattern, phi fused adam). The flat
fp32 master buffer, moments, and per-element decay coefficients persist
across steps; each step flattens the incoming grads, runs the kernel
(in-place via buffer aliasing), and scatters the updated values back into
the (possibly bf16) parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.optimizer.optimizer import AdamW
from paddle_tpu.ops.pallas import fused_adamw as _kernel
from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_flat, pad_flat


class FusedAdamW(AdamW):
    """The ENTIRE step — grad flatten, Pallas kernel, scatter-back — is one
    jitted program, so the eager hot loop pays a single dispatch instead of
    one per parameter (the multi-tensor-apply win; stock eager AdamW issues
    ~4 ops per parameter per step)."""

    def __init__(self, *args, block_rows=512, **kwargs):
        super().__init__(*args, **kwargs)
        self._block_rows = block_rows
        self._flat = None
        self._jitted_step = None

    def _build_flat(self, pairs):
        old = self._flat
        params = [p for p, _ in pairs]
        flat_p, sizes, padded = pad_flat([p._value for p in params])
        flat_m = jnp.zeros_like(flat_p)
        flat_v = jnp.zeros_like(flat_p)
        flat_wd, wd_sig = self._wd_buffer(params, sizes)
        # PER-ELEMENT pow chains: new params start their own correction
        b1pow = jnp.full_like(flat_p, self._beta1)
        b2pow = jnp.full_like(flat_p, self._beta2)
        if old is None and self._state:
            # the optimizer previously ran through TrainStep's per-param
            # path (or a stock-format resume): seed the flat buffers from
            # the per-param moments instead of silently zeroing them
            off = 0
            for p, n in zip(params, sizes):
                st = self._state.get(id(p))
                if st is not None and "moment1" in st:
                    flat_m = flat_m.at[off:off + n].set(
                        jnp.ravel(st["moment1"]).astype(jnp.float32))
                    flat_v = flat_v.at[off:off + n].set(
                        jnp.ravel(st["moment2"]).astype(jnp.float32))
                    step = int(st.get("step", 0))
                    b1pow = b1pow.at[off:off + n].set(
                        float(self._beta1) ** (step + 1))
                    b2pow = b2pow.at[off:off + n].set(
                        float(self._beta2) ** (step + 1))
                mw = self._master_weights.get(id(p))
                if mw is not None:
                    flat_p = flat_p.at[off:off + n].set(
                        jnp.ravel(mw).astype(jnp.float32))
                off += n
        if old is not None:
            # the grad-bearing param set changed (layers frozen/unfrozen):
            # CARRY OVER moments + fp32 master segments for surviving params
            # instead of silently resetting optimizer state mid-training
            old_off = {}
            off = 0
            for pid, n in zip(old["ids"], old["sizes"]):
                old_off[pid] = (off, n)
                off += n
            off = 0
            for p, n in zip(params, sizes):
                hit = old_off.get(id(p))
                if hit is not None and hit[1] == n:
                    oo, _ = hit
                    flat_m = flat_m.at[off:off + n].set(old["m"][oo:oo + n])
                    flat_v = flat_v.at[off:off + n].set(old["v"][oo:oo + n])
                    flat_p = flat_p.at[off:off + n].set(old["p"][oo:oo + n])
                    b1pow = b1pow.at[off:off + n].set(
                        old["b1pow"][oo:oo + n])
                    b2pow = b2pow.at[off:off + n].set(
                        old["b2pow"][oo:oo + n])
                off += n
        self._flat = {
            "p": flat_p, "m": flat_m, "v": flat_v, "wd": flat_wd,
            "sizes": sizes, "padded": padded,
            "ids": [id(p) for p in params],
            "shapes": [tuple(p.shape) for p in params],
            "dtypes": [p.dtype for p in params],
            "b1pow": b1pow,
            "b2pow": b2pow,
            "wd_sig": wd_sig,
        }
        sizes_t = tuple(sizes)
        shapes_t = tuple(self._flat["shapes"])
        dtypes_t = tuple(str(d) for d in self._flat["dtypes"])
        beta1, beta2, eps = self._beta1, self._beta2, self._epsilon
        block_rows = self._block_rows
        interpret = _kernel._interpret

        # not donated, although the kernel compiles and matches under
        # donation on a v5e (PR 21): state_dict() hands these flat buffers
        # out, and a later step must not invalidate what a caller holds
        @jax.jit
        def step_impl(flat_p, gvals, flat_m, flat_v, flat_wd, lr, b1p, b2p):
            flat_g, _, _ = pad_flat(gvals)
            new_p, new_m, new_v, nb1, nb2 = fused_adamw_flat(
                flat_p, flat_g, flat_m, flat_v, flat_wd, lr, b1p, b2p,
                beta1=beta1, beta2=beta2, eps=eps,
                block_rows=block_rows, interpret=interpret)
            outs = []
            off = 0
            for n, shp, dt in zip(sizes_t, shapes_t, dtypes_t):
                outs.append(new_p[off:off + n].reshape(shp).astype(dt))
                off += n
            return new_p, new_m, new_v, nb1, nb2, outs

        self._jitted_step = step_impl

    def _wd_buffer(self, params, sizes):
        """Per-element decay buffer + its python signature (re-evaluated
        every step so runtime decay changes — p.no_weight_decay toggles,
        apply_decay_param_fun — take effect like stock AdamW)."""
        sig = tuple(float(self._decay_for(p)) for p in params)
        pieces = [jnp.full(s, c, jnp.float32) for c, s in zip(sig, sizes)]
        flat_wd, _, _ = pad_flat(pieces)
        return flat_wd, sig

    def step(self):
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        self._step_count += 1
        pairs = list(self._clipped_grads())
        if not pairs:
            return
        if self._flat is None or self._flat["ids"] != [id(p) for p, _ in pairs]:
            self._build_flat(pairs)
        st = self._flat
        params = [p for p, _ in pairs]
        wd_sig = tuple(float(self._decay_for(p)) for p in params)
        if wd_sig != st["wd_sig"]:
            st["wd"], st["wd_sig"] = self._wd_buffer(params, st["sizes"])
        # pass device arrays through untouched. NB: do not duck-type on
        # `_value` here — jax.Array has an INTERNAL ._value property that
        # materializes the array to host numpy (a full download on remote
        # backends)
        from paddle_tpu.tensor import Tensor
        gvals = [g._value if isinstance(g, Tensor) else g for _, g in pairs]
        (st["p"], st["m"], st["v"], st["b1pow"], st["b2pow"],
         new_vals) = self._jitted_step(
            st["p"], gvals, st["m"], st["v"], st["wd"], lr,
            st["b1pow"], st["b2pow"])
        for (p, _), v in zip(pairs, new_vals):
            p._replace_value(v)

    # ------------------------------------------------------ checkpointing
    def state_dict(self):
        """Flat-buffer state when the eager fused loop ran; the per-param
        base-class dict when the optimizer was driven through TrainStep's
        per-param path (where the flat buffers are never built)."""
        from paddle_tpu.tensor import Tensor

        if self._flat is None and self._state:
            return super().state_dict()
        sd = {"step_count": self._step_count}
        if self._flat is not None:
            st = self._flat
            sd["fused"] = {
                "p": Tensor._from_value(st["p"]),
                "m": Tensor._from_value(st["m"]),
                "v": Tensor._from_value(st["v"]),
                "b1pow": Tensor._from_value(st["b1pow"]),
                "b2pow": Tensor._from_value(st["b2pow"]),
                "sizes": list(st["sizes"]),
            }
        from paddle_tpu.optimizer import lr as lr_mod
        if isinstance(self._lr, lr_mod.LRScheduler):
            sd["LR_Scheduler"] = self._lr.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        from paddle_tpu.tensor import Tensor

        self._step_count = state_dict.get("step_count", 0)
        fused = state_dict.get("fused")
        if fused is None and state_dict.get("states"):
            # stock-AdamW-format checkpoint: reconstruct the flat buffers
            # from the per-param moment1/moment2/step entries (drop-in
            # resume path; silently zeroing moments would be a trap)
            pairs = [(p, None) for p in self._parameter_list if p.trainable]
            self._build_flat(pairs)
            st = self._flat
            unwrap = lambda t: t._value if isinstance(t, Tensor) \
                else jnp.asarray(t)
            states = state_dict["states"]
            off_map = {}
            off = 0
            for (p, _), n in zip(pairs, st["sizes"]):
                off_map[id(p)] = (off, n)
                off += n
            for p, entry in zip(self._parameter_list, states):
                loc = off_map.get(id(p))
                if entry is None or loc is None:
                    continue
                off, n = loc
                m1 = unwrap(entry["moment1"]).reshape(-1).astype(jnp.float32)
                m2 = unwrap(entry["moment2"]).reshape(-1).astype(jnp.float32)
                step = int(unwrap(entry["step"]))
                st["m"] = st["m"].at[off:off + n].set(m1)
                st["v"] = st["v"].at[off:off + n].set(m2)
                # after t recorded steps, the NEXT update's input pow is
                # beta^(t+1) (phi input convention)
                st["b1pow"] = st["b1pow"].at[off:off + n].set(
                    float(self._beta1) ** (step + 1))
                st["b2pow"] = st["b2pow"].at[off:off + n].set(
                    float(self._beta2) ** (step + 1))
            masters = state_dict.get("master_weights") or []
            for p, mw in zip(self._parameter_list, masters):
                loc = off_map.get(id(p))
                if mw is None or loc is None:
                    continue
                off, n = loc
                st["p"] = st["p"].at[off:off + n].set(
                    unwrap(mw).reshape(-1).astype(jnp.float32))
            return
        if fused is not None:
            # rebuild layout from the CURRENT params (same model/order),
            # then overwrite the buffers with the checkpointed state
            pairs = [(p, None) for p in self._parameter_list if p.trainable]
            self._build_flat(pairs)
            unwrap = lambda t: t._value if isinstance(t, Tensor) \
                else jnp.asarray(t)
            if list(fused["sizes"]) != list(self._flat["sizes"]):
                raise ValueError(
                    "FusedAdamW.set_state_dict: parameter layout mismatch "
                    f"(ckpt {fused['sizes'][:3]}..., "
                    f"model {self._flat['sizes'][:3]}...)")
            for k in ("p", "m", "v", "b1pow", "b2pow"):
                self._flat[k] = unwrap(fused[k])
            # push restored master params back into the live parameters
            off = 0
            for (p, _), n in zip(pairs, self._flat["sizes"]):
                piece = self._flat["p"][off:off + n].reshape(p.shape)
                p._replace_value(piece.astype(p.dtype))
                off += n
        from paddle_tpu.optimizer import lr as lr_mod
        if "LR_Scheduler" in state_dict and isinstance(self._lr,
                                                       lr_mod.LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
