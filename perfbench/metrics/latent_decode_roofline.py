"""The latent-attention decode program's share of its roofline: the least
time the chip needs for the bytes an ideal decode step must read
(``harness/costs_joyai_flash.py``: attention, router, shared expert, dense
MLP and head-slice weights, the weights of the held experts a step could
touch, every layer's live latent rows as published) at the chip's HBM
bandwidth, over the device-busy time of the traced ``step()`` calls that
admitted nothing. Decode steps are told apart by the benchmark's own step
records, as in ``decode_roofline``."""
from perfbench.harness import costs_joyai_flash as costs
from perfbench.harness import device, hybrid_view, xplane

UNIT, SOURCE = "%", "device_trace"


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    if not steps or "kv_lora_rank" not in rec["model"]:
        return None
    busy = xplane.busy_within(rec["trace"], [(s[0], s[1]) for s in steps])
    if busy <= 0:
        return None
    need = sum(costs.decode_step_min_bytes(
        rec["model"], rec["weight_bytes"], rec["cache_bytes"], s[2], s[3])
        for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / busy
