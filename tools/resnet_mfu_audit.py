"""ResNet-50 MFU audit (VERDICT r4 "do this" #3): attack 13.1% MFU or
prove the ceiling with HLO-level evidence. One command on the chip:

    python tools/resnet_mfu_audit.py            # full audit
    RESNET_AUDIT_QUICK=1 python ...             # skip the batch sweep

Output, in order:
1. HLO transpose/layout scan (subprocess with --xla_dump_to) — per-op
   instruction counts in the optimized train-step HLO; layout churn is
   the classic silent MFU killer.
2. Batch sweep — img/s + MFU at batch 64..512 via bench.py subprocesses.
3. Per-stage conv ceilings — sustained TF/s at each ResNet stage's exact
   shape, FLOP-weighted into the honest model-level ceiling. Runs LAST
   and in-process: a TPU belongs to one process at a time, so this parent
   must not touch JAX while the bench subprocesses of steps 1-2 need the
   chip.
4. Verdict line — best achieved MFU vs the shape-weighted ceiling MFU:
   the gap to the ceiling is the framework's to close; the ceiling's gap
   to nominal peak is structural (channel mix / spatial shapes).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tools.chip_ceiling import _sync  # shared device-sync discipline

# ResNet-50 stage shapes at 224 (NHWC): (H, W, Cin, Cout, k, stride, count)
# counts aggregate the repeated bottleneck convs carrying ~all FLOPs.
STAGES = [
    ("stem", 224, 224, 3, 64, 7, 2, 1),
    ("c2_1x1a", 56, 56, 64, 64, 1, 1, 3),
    ("c2_3x3", 56, 56, 64, 64, 3, 1, 3),
    ("c2_1x1b", 56, 56, 64, 256, 1, 1, 6),
    ("c3_3x3", 28, 28, 128, 128, 3, 1, 4),
    ("c3_1x1", 28, 28, 128, 512, 1, 1, 8),
    ("c4_3x3", 14, 14, 256, 256, 3, 1, 6),
    ("c4_1x1", 14, 14, 256, 1024, 1, 1, 12),
    ("c5_3x3", 7, 7, 512, 512, 3, 1, 3),
    ("c5_1x1", 7, 7, 512, 2048, 1, 1, 6),
]


def conv_ceiling(batch, h, w, cin, cout, k, stride, iters=10):
    """Sustained TF/s of one conv shape, chained (data-dependent loop in
    ONE jitted program) so per-call dispatch latency never enters."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((batch, h, w, cin), jnp.bfloat16)
    kern = jnp.ones((k, k, cin, cout), jnp.bfloat16) * 0.01

    def op(hbuf, kern_):
        out = jax.lax.conv_general_dilated(
            hbuf, kern_, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        # fold back to the INPUT shape to keep the loop data-dependent:
        # reduce channels, upsample strided spatial dims, broadcast
        red = jnp.mean(out, axis=-1, keepdims=True).astype(jnp.bfloat16)
        if stride > 1:
            red = jnp.repeat(jnp.repeat(red, stride, axis=1), stride,
                             axis=2)[:, :h, :w, :]
        return jnp.broadcast_to(red, hbuf.shape) * 0.5 + hbuf * 0.5

    @jax.jit
    def chained(h0, kern_):
        return jax.lax.fori_loop(0, iters, lambda _, hh: op(hh, kern_), h0)

    _sync(chained(x, kern))
    t0 = time.perf_counter()
    _sync(chained(x, kern))
    dt = (time.perf_counter() - t0) / iters
    ho = -(-h // stride)
    wo = -(-w // stride)
    flops = 2.0 * batch * ho * wo * cin * cout * k * k
    return flops / dt / 1e12


def hlo_layout_scan(batch=128):
    """Compile the full train step with --xla_dump_to in a SUBPROCESS
    (keeps the dump flag and the device out of this process), scan the
    dumped optimized HLO for layout churn."""
    import shutil
    import tempfile

    dump = tempfile.mkdtemp(prefix="resnet_hlo_")
    code = f"""
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.jit.api import TrainStep
from paddle_tpu.vision.models.resnet import resnet50
model = resnet50(data_format="NHWC")
optimizer = opt.Momentum(learning_rate=0.1, parameters=model.parameters(),
                         momentum=0.9)
model, optimizer = paddle.amp.decorate(model, optimizer, level="O2")
ce = nn.CrossEntropyLoss()
step = TrainStep(model, lambda m, a, b: ce(m(a), b), optimizer)
rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.normal(size=({batch}, 224, 224, 3))
                     .astype(np.float32)).astype("bfloat16")
y = paddle.to_tensor(rng.integers(0, 10, ({batch},)).astype(np.int64))
print(float(np.asarray(step(x, y).numpy())))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_dump_to={dump}").strip()
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        print(json.dumps({"hlo_scan_error": r.stderr[-300:]}))
        shutil.rmtree(dump, ignore_errors=True)
        return
    cands = [os.path.join(dump, f) for f in os.listdir(dump)
             if "after_optimizations" in f and f.endswith(".txt")]
    if not cands:
        print(json.dumps({"hlo_scan": "no after_optimizations dump"}))
        shutil.rmtree(dump, ignore_errors=True)
        return
    big = max(cands, key=os.path.getsize)
    text = open(big).read()
    # each HLO instruction line applies exactly one "opcode(" — counting
    # that form counts instructions once (operand references carry no "(")
    counts = {op: len(re.findall(rf"\b{op}\(", text))
              for op in ("convolution", "transpose", "copy", "convert",
                         "reshape")}
    print(json.dumps({"hlo_scan": {"module": os.path.basename(big),
                                   "instruction_counts": counts,
                                   "bytes": len(text)}}))
    shutil.rmtree(dump, ignore_errors=True)


def main():
    batch = int(os.environ.get("RESNET_AUDIT_BATCH", "256"))

    # 1. layout scan (subprocess)
    try:
        hlo_layout_scan(batch=min(batch, 128))
    except Exception as e:
        print(json.dumps({"hlo_scan_error": str(e)[:200]}))

    # 2. batch sweep (subprocesses) — BEFORE this process touches the chip
    best_mfu = None
    if os.environ.get("RESNET_AUDIT_QUICK") != "1":
        for b in (64, 128, 256, 512):
            env = dict(os.environ)
            env["RESNET_BENCH_BATCH"] = str(b)
            r = subprocess.run(
                [sys.executable, "bench.py", "--one", "bench_resnet50"],
                capture_output=True, text=True, timeout=900, env=env,
                cwd=_REPO)
            emitted = False
            for line in r.stdout.splitlines():
                if line.startswith("{"):
                    emitted = True
                    print(f'{{"batch": {b}, "result": {line}}}')
                    try:
                        mfu = json.loads(line).get("mfu_pct")
                        if mfu is not None:
                            best_mfu = max(best_mfu or 0.0, float(mfu))
                    except (ValueError, TypeError, AttributeError):
                        pass  # non-JSON or shapeless line: not a result
            if not emitted:
                print(json.dumps({
                    "batch": b,
                    "error": (r.stderr.strip().splitlines()[-1][:200]
                              if r.stderr.strip() else
                              f"rc={r.returncode}, no output")}))

    # 3. per-stage ceilings, in-process, LAST (first JAX use in this
    # process; an unknown chip raises in chip_specs, CPU has no peak)
    from paddle_tpu.observability.program_inventory import chip_specs

    specs = chip_specs()
    if specs is None:
        sys.exit("resnet_mfu_audit: needs a chip; the CPU has no peak to "
                 "audit against")
    peak = specs["peak_tflops"]
    print(json.dumps({"device_kind": specs["device_kind"],
                      "nominal_peak_tflops": peak}))
    total_flops, total_time = 0.0, 0.0
    stage_out = {}
    for name, h, w, cin, cout, k, stride, count in STAGES:
        try:
            tfs = conv_ceiling(batch, h, w, cin, cout, k, stride)
        except Exception as e:
            stage_out[name] = f"error: {str(e)[:80]}"
            continue
        ho = -(-h // stride)
        wo = -(-w // stride)
        flops = 2.0 * batch * ho * wo * cin * cout * k * k * count
        stage_out[name] = round(tfs, 1)
        total_flops += flops
        total_time += flops / (tfs * 1e12)
    weighted = total_flops / total_time / 1e12 if total_time else 0.0
    ceiling_mfu = 100 * weighted / peak
    print(json.dumps({"stage_ceilings_tflops": stage_out,
                      "flop_weighted_ceiling_tflops": round(weighted, 1),
                      "ceiling_mfu_pct": round(ceiling_mfu, 1)}))

    # 4. verdict
    verdict = {"metric": "resnet50_mfu_verdict",
               "achieved_mfu_pct": best_mfu,
               "ceiling_mfu_pct": round(ceiling_mfu, 1)}
    if best_mfu is not None and ceiling_mfu > 0:
        verdict["achieved_over_ceiling_pct"] = round(
            100 * best_mfu / ceiling_mfu, 1)
        verdict["reading"] = (
            "gap to ceiling is the framework's to close; "
            "ceiling vs nominal peak is structural (channel mix)")
    print(json.dumps(verdict))


if __name__ == "__main__":
    main()
