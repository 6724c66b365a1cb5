"""The Pallas flash-attention kernels' share of their roofline, forward and
backward together: matmul FLOPs from shapes (``harness/costs.py``; the
forward kernel runs again in the backward pass where recomputation drops its
output, and every run is counted) over the chip's bf16 peak, over the
kernels' summed device time in the traced slice. Compute-bound at sequence
1024 x head size 128. Events are found by the kernels' names."""
import re

from perfbench.harness import costs, device, xplane

UNIT, SOURCE = "%", "device_trace"

# the instruction names the Pallas TPU flash kernels carry in a trace
# (chip run, PR 22): ``flash_attention.<n>`` forward (48 a step: 24 layers,
# each again in the backward pass), ``flash_mha_bwd_dkv_block_..`` and
# ``flash_mha_bwd_dq_block_..`` backward (24 a step each)
FWD = re.compile(r"^flash_attention(\.\d+)?$")
BWD = re.compile(r"^flash_mha_bwd_(dkv|dq)_")


def read(rec):
    trace, summary = rec.get("trace"), rec.get("trace_summary")
    if rec["kind"] != "train" or not trace or not summary:
        return None
    m = rec["model"]
    heads = m["num_heads"]
    flops = costs.flash_flops(rec["batch"], heads, rec["sequence"],
                              m["hidden_size"] // heads)
    # the backward pass is two kernels (dK/dV and dQ): each call is counted
    # with half of the backward FLOPs
    per_call = ((FWD, flops["fwd"]), (BWD, flops["bwd"] / 2))
    need = spent = 0.0
    for dev in trace["devices"].values():
        for name, s, e in dev["ops"]:
            if not summary["t0"] <= s < summary["t1"]:
                continue
            for rx, f in per_call:
                if rx.search(xplane.op_name(name)):
                    need += f
                    spent += e - s
                    break
    if spent <= 0:
        return None
    peak = device.peaks(rec["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / spent
