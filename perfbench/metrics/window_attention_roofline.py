"""The window layers' paged decode-attention kernel's share of its
roofline: the K and V of each occupied row's last ``sliding_window``
positions (``harness/costs_mimo_v2.py``; bound by bytes), at the chip's HBM
bandwidth, over the summed device time of the kernel's calls inside the
traced decode-only steps. The kernel is found by the name it carries in the
trace, ``paged_gqa_decode_window`` (``ops/pallas/paged_attention_gqa.py``).
It reads whole pages, so 128 of at most 144 positions is its ceiling."""
import re

from perfbench.harness import costs_mimo_v2 as costs
from perfbench.harness import device, hybrid_view

UNIT, SOURCE = "%", "device_trace"

KERNEL = re.compile(r"^paged_gqa_decode_window(\.\d+)?$")


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    if not steps:
        return None
    spent, _ = hybrid_view.kernel_seconds(rec, KERNEL, steps)
    if spent <= 0:
        return None
    cfg = rec["model"]
    layers = costs.count_layers(cfg, window=True)
    need = sum(layers * costs.window_layer_kv_bytes(
        cfg, s[2], s[3] / s[2], rec["cache_bytes"]) for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / spent
