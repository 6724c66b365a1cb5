"""The decode program's share of its roofline: the least time the chip
needs for the bytes an ideal decode step must read (every weight once and
the live K/V of the occupied slots, ``harness/costs.py``) at the chip's HBM
bandwidth, over the device-busy time of the traced ``step()`` calls that
admitted nothing. Memory-bound: at 32 rows the matmuls are far under the
ridge point. The prefill and decode programs share one name in the trace
(``serving.SlotStep``), so decode steps are told apart by the benchmark's
own step records, not by program name."""
from perfbench.harness import costs, device, xplane

UNIT, SOURCE = "%", "device_trace"


def read(rec):
    trace = rec.get("trace")
    if rec["kind"] != "serve" or not trace or not rec.get("trace_summary"):
        return None
    off = trace["run_clock_offset_s"]
    t0, t1 = rec["trace_summary"]["t0"], rec["trace_summary"]["t1"]
    steps = [s for s in rec["steps"] if s[2] == 0 and s[3] > 0
             and t0 <= s[0] + off and s[1] + off <= t1]
    if not steps:
        return None
    busy = xplane.busy_within(trace, [(s[0] + off, s[1] + off)
                                      for s in steps])
    if busy <= 0:
        return None
    need = sum(costs.decode_step_min_bytes(
        rec["model"], rec["weight_bytes"], rec["cache_bytes"], s[6])
        for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / busy
