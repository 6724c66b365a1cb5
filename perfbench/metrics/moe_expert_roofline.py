"""The expert layers' grouped products' share of their roofline in decode
steps: the weights of the held experts that a step's rows reach, once
(``harness/costs_mimo_v2.py``; bound by bytes: a handful of tokens an
expert), at the chip's HBM bandwidth, over the summed device time of the
grouped-matmul kernel's calls inside the traced decode-only steps.

The kernel is the Pallas grouped matmul that JAX ships (``megablox.gmm``,
selected by ``paddle_tpu/nn/moe.py::grouped_matmul`` on a TPU; where the
layer runs it inside a ``lax.cond`` the trace shows the branch as
``conditional`` around the same ``gmm`` events, which are what is summed); its
``pallas_call`` carries no name, so the trace shows it under its kernel
function's, ``gmm``. Two calls a layer (gate|up, down)."""
import re

from perfbench.harness import costs_mimo_v2 as costs
from perfbench.harness import device, hybrid_view

UNIT, SOURCE = "%", "device_trace"

KERNEL = re.compile(r"^gmm(\.\d+)?$")


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    if not steps:
        return None
    spent, events = hybrid_view.kernel_seconds(rec, KERNEL, steps)
    if spent <= 0:
        return None
    cfg = rec["model"]
    layers = costs.count_layers(cfg, moe=True)
    need = sum(layers * costs.expert_layer_bytes(cfg, s[2],
                                                 rec["weight_bytes"])
               for s in steps)
    bw = device.peaks(rec["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / spent
