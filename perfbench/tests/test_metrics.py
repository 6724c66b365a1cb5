"""The metric arithmetic on hand-made records: open-loop requests are timed
from when they were due, not from when they were sent."""

import numpy as np

from perfbench.harness.load import LoadRequest
from perfbench.harness.spec import load_module
from perfbench.harness.stats import median, percentile


def _req(i, due, sent, first, last, tokens, want, **kw):
    r = LoadRequest(i, due, np.zeros(10, np.int32), want)
    r.sent_s, r.first_s, r.last_s, r.tokens = sent, first, last, tokens
    r.rid = i
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def _rec(requests, closed=False):
    return {"kind": "serve", "closed_loop": closed, "window": (5.0, 15.0),
            "score_end_s": 20.0, "requests": requests,
            "limits": {"ttft_ms": 1000, "token_gap_ms": 150},
            "steps": [(5.0, 5.1, 0, 16, 100, 0, 4000),
                      (5.1, 5.4, 2, 18, 140, 1, 4016),
                      (5.4, 5.5, 0, 18, 141, 0, 4600)],
            "tokens_at": [4.9, 5.0, 7.0, 14.999, 15.0, 16.0],
            "max_num_seqs": 32, "total_blocks": 1000, "preemptions": 2}


def _read(name, rec):
    return load_module("metrics", name).read(rec)


def test_percentiles():
    assert percentile([], 50) is None
    assert median([3, 1, 2]) == 2
    assert percentile(range(101), 90) == 90


def test_latencies_are_taken_from_the_due_time():
    reqs = [
        _req(0, 4.0, 4.0, 4.1, 4.5, 5, 5),              # ramp: not scored
        _req(1, 6.0, 6.3, 6.5, 8.5, 21, 21, finished_s=8.5,
             finish_reason="length"),                   # sent 0.3 s late
        _req(2, 7.0, 7.0, 7.2, 9.2, 11, 40),            # still decoding
        _req(3, 14.0, 14.1, None, None, 0, 8),          # no token yet
        _req(4, 15.0, 15.0, 15.1, 15.2, 2, 2),          # grace: not scored
    ]
    rec = _rec(reqs)
    # 0.5 s from the DUE time of #1 (0.2 s from its send), 0.2 s, and the
    # 6 s that #3 has waited so far
    assert _read("ttft_p50_ms", rec) == 500.0
    assert abs(_read("ttft_tail_ms", rec) - (500 + 0.8 * 5500)) < 1e-6
    assert abs(_read("tpot_p50_ms", rec) - 150.0) < 1e-9   # 100 and 200 ms
    assert abs(_read("gen_late_p99_ms", rec) - 296.0) < 1.0
    # #1 meets both limits; #2's gap is 200 ms; #3 has no token
    assert abs(_read("slo_attain", rec) - 100.0 / 3) < 1e-9
    assert _read("tokens_per_s", rec) is None      # open loop: not reported


def test_throughput_counts_tokens_stamped_inside_the_window():
    rec = _rec([], closed=True)
    assert _read("tokens_per_s", rec) == 3 / 10.0
    assert _read("ttft_p50_ms", rec) is None


def test_step_metrics():
    rec = _rec([])
    assert abs(_read("decode_step_p50_ms", rec) - 100.0) < 1e-6
    occ = (0.1 * 16 + 0.3 * 18 + 0.1 * 18) / (0.5 * 32) * 100
    assert abs(_read("slot_occupancy", rec) - occ) < 1e-9
    assert abs(_read("kv_pool_peak", rec) - 14.1) < 1e-9
    assert _read("preemptions", rec) == 2


def test_train_metrics():
    rec = {"kind": "train", "window_start_s": 0.0,
           "step_ends_s": [0.5, 1.0, 1.5, 2.0], "tokens_per_step": 4096,
           "model": {"vocab_size": 50304, "hidden_size": 2048,
                     "num_layers": 24, "max_position_embeddings": 2048},
           "device": {"kind": "TPU v5 lite"}, "device_count_used": 1}
    assert _read("tokens_per_s", rec) == 8192.0
    assert abs(_read("train_step_p50_ms", rec) - 500.0) < 1e-9
    mfu = 6 * 1_315_819_520 * 8192.0 / 197e12 * 100
    assert abs(_read("train_mfu", rec) - mfu) < 1e-9


def _verdict(reqs, now_s, closed=False):
    serve = load_module("runners", "serve")
    rec = _rec(reqs, closed)
    rec["limits"]["stalled_gap_ms"] = 450
    rec.update(score_end_s=now_s, reference_check={
        "ok": True, "err": 0.0, "scale": 1.0}, compiles_in_window=0,
        requests_failed_counter=0, lagging=serve.not_served(
            reqs, now_s, rec["limits"], None if closed else 5.0))
    return serve.verdict(rec)


def test_a_request_that_is_not_being_served_has_failed():
    done = dict(finished_s=8.5, finish_reason="length")
    served = [
        _req(1, 6.0, 6.0, 6.5, 8.5, 21, 21, **done),
        _req(2, 14.0, 14.0, 14.2, 19.9, 50, 200),      # decoding, on pace
        _req(3, 14.9, 14.9, 15.0, 19.95, 40, 200),
    ]
    ok, attempted, failed, _ = _verdict(served, 20.0)
    assert (ok, attempted, failed) == (True, 3, 0)
    # no first token five seconds after it was due; starved since its
    # third token; crawling at a token a second: failures, not latencies
    for bad in (_req(4, 14.0, 14.1, None, None, 0, 8),
                _req(4, 9.0, 9.0, 9.2, 9.4, 3, 64),
                _req(4, 9.0, 9.0, 9.2, 19.9, 11, 64)):
        ok, attempted, failed, notes = _verdict(served + [bad], 20.0)
        assert (ok, attempted, failed) == (False, 4, 1), notes
    # a request of the ramp that ended short is counted too
    ramp = _req(0, 1.0, 1.0, 1.1, 2.0, 5, 9, finished_s=2.0,
                finish_reason="stop")
    ok, attempted, failed, _ = _verdict(served + [ramp], 20.0)
    assert (ok, attempted, failed) == (False, 4, 1)


def test_a_closed_loop_queues_by_design_but_may_not_starve():
    done = dict(finished_s=8.5, finish_reason="length")
    reqs = [_req(1, 6.0, 6.0, 6.5, 8.5, 21, 21, **done),
            _req(2, 6.0, 6.0, None, None, 0, 64)]       # queued for a slot
    assert _verdict(reqs, 15.0, closed=True)[:3] == (True, 1, 0)
    reqs.append(_req(3, 6.0, 6.0, 7.0, 9.0, 10, 64))    # nothing since 9 s
    assert _verdict(reqs, 15.0, closed=True)[:3] == (False, 2, 1)
