"""The latent paged decode kernel's share of its roofline: every live
cached position's latent row as published (``kv_lora_rank +
qk_rope_head_dim`` elements a layer, 1,152 B in bfloat16, whatever the
padding or the implementation; ``harness/costs_joyai_flash.py``), once a
layer, at the chip's HBM bandwidth, over the summed device time of the
kernel's calls inside the traced decode-only steps. The kernel is found by
the name it carries in the trace, ``paged_mla_decode``
(``ops/pallas/paged_mla_decode.py``). Counted in bytes, the lesser bound: at
~60 FLOP/B the kernel reaches it only with the MXU at a quarter of its peak,
and a pool whose rows are padded to 640 lanes cannot pass 90 %."""
import re

from perfbench.harness import costs_joyai_flash as costs
from perfbench.harness import device, hybrid_view

UNIT, SOURCE = "%", "device_trace"

KERNEL = re.compile(r"^paged_mla_decode(\.\d+)?$")


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    if not steps or "kv_lora_rank" not in rec["model"]:
        return None
    spent, _ = hybrid_view.kernel_seconds(rec, KERNEL, steps)
    if spent <= 0:
        return None
    cfg = rec["model"]
    need = sum(cfg["num_hidden_layers"]
               * costs.latent_bytes(cfg, s[3], rec["cache_bytes"])
               for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / spent
