"""Model FLOP/s utilisation: 6 x parameters x tokens/s over the chip's bf16
peak (``harness/costs.py``, ``harness/device.py``). The recompute's extra
forward is hardware work, not model work, and is not counted."""
from perfbench.harness import costs, device, train_view

UNIT, SOURCE = "%", "host_clock"


def read(rec):
    tps = train_view.tokens_per_s(rec) if rec["kind"] == "train" else None
    if tps is None:
        return None
    peak = device.peaks(rec["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * costs.train_model_flops_per_token(rec["model"]) * tps
            / (peak * rec["device_count_used"]))
