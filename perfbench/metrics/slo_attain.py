"""Share of the scored requests that met both limits of the traffic file:
first token within ``ttft_ms`` of the due time, and a mean gap between
tokens (as ``tpot_p50_ms`` takes it) within ``token_gap_ms``. A refused or
failed request, and one without a first token when scoring ended, missed."""
from perfbench.harness import serve_view as view

UNIT, SOURCE = "%", "host_clock"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    lim = rec["limits"]
    reqs = view.scored(rec)
    if not reqs:
        return None
    met = 0
    for r in reqs:
        if r.rejected is not None or r.first_s is None:
            continue
        if r.finished_s is not None and r.finish_reason != "length":
            continue
        gap = (r.last_s - r.first_s) / (r.tokens - 1) if r.tokens > 1 else 0.0
        met += ((r.first_s - r.due_s) * 1e3 <= lim["ttft_ms"]
                and gap * 1e3 <= lim["token_gap_ms"])
    return 100.0 * met / len(reqs)
