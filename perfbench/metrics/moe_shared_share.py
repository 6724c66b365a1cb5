"""The shared expert's share of the expert products' device time in decode
steps: the device time of the operations that read the shared expert's
weights over that plus the grouped-matmul kernel's (``gmm``: the routed
experts held here). Routing, sorting and the scatter back are in neither.

A device operation's event is named by its HLO text, operand types
included, so the shared expert's products are the operations that name an
array of the shared expert's own weight shapes, ``[hidden_size, n_shared_experts *
moe_intermediate_size]`` (gate, up) or its transpose (down), which no other
weight of the model has (the routed experts' are stacked, three
dimensions)."""
import re

from perfbench.harness import hybrid_view

UNIT, SOURCE = "%", "device_trace"

GMM = re.compile(r"^gmm(\.\d+)?$")


def _shared_seconds(rec, steps, hidden: int, width: int) -> float:
    """Device seconds (mean over chips) of the operations inside ``steps``
    whose HLO text names an array of the shared expert's weight shapes."""
    types = (f"[{hidden},{width}]", f"[{width},{hidden}]")
    devices = rec["trace"]["devices"].values()
    total = 0.0
    for dev in devices:
        ops = sorted((s, e) for name, s, e in dev["ops"]
                     if any(t in name for t in types))
        i = 0
        for span in steps:
            while i < len(ops) and ops[i][0] < span[0]:
                i += 1
            while i < len(ops) and ops[i][0] < span[1]:
                total += ops[i][1] - ops[i][0]
                i += 1
    return total / max(len(devices), 1)


def read(rec):
    steps = hybrid_view.decode_steps(rec)
    cfg = rec.get("model") or {}
    if not steps or not cfg.get("n_shared_experts"):
        return None
    shared = _shared_seconds(
        rec, steps, cfg["hidden_size"],
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
    routed, _ = hybrid_view.kernel_seconds(rec, GMM, steps)
    if shared <= 0 or routed <= 0:
        return None
    return 100.0 * shared / (shared + routed)
