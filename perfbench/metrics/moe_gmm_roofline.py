"""``moe_expert_roofline`` for a configuration whose expert layers are named
by ``first_k_dense_replace`` (``harness/costs_joyai_flash.py``): the grouped
products' share of their roofline in decode steps, the weights of the held
experts that a step's rows reach, once, at the chip's HBM bandwidth, over the
summed device time of the grouped-matmul kernel's calls (``gmm`` in the
trace: ``megablox.gmm``, two calls a layer) inside the traced decode-only
steps. The shared expert is no part of it."""
import re

from perfbench.harness import costs_joyai_flash as costs
from perfbench.harness import device, hybrid_view

UNIT, SOURCE = "%", "device_trace"

KERNEL = re.compile(r"^gmm(\.\d+)?$")


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    if not steps or "first_k_dense_replace" not in rec["model"]:
        return None
    spent, _ = hybrid_view.kernel_seconds(rec, KERNEL, steps)
    if spent <= 0:
        return None
    cfg = rec["model"]
    need = sum(costs.expert_layers(cfg)
               * costs.expert_layer_bytes(cfg, s[2], rec["weight_bytes"])
               for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / spent
