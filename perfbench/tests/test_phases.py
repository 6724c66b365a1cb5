"""The device's idle time split over the scheduler's phases
(``harness/phases.py``) and the nine readers built on it: on hand-made
events, and on a TPU trace recorded on the chip with the program's
``serving.*`` spans in it (``perfbench/fixtures``, see its README)."""

import json
import os

import pytest

from perfbench.harness import phases, xplane
from perfbench.harness.spec import BENCH_DIR, REPO_DIR, load_module

FIXTURES = os.path.join(BENCH_DIR, "fixtures")
SHARES = ("idle_stage", "idle_launch", "idle_sync", "idle_commit",
          "idle_admit", "idle_accounting", "idle_outside_step")
READERS = SHARES + ("h2d_puts_per_step", "trace_stretch")


def _trace():
    """Two steps in a 2 s window. The device idles 10.4-11.0, while the host
    ends the first step (its own code, the commit, the accounting, its own
    code again) and is between steps; and 11.5-12.0, from inside the second
    step's decode launch to the window's end."""
    return {
        "devices": {0: {"ops": [("fusion.1", 10.0, 10.4),
                                ("fusion.1", 11.0, 11.5)]}},
        "spans": [
            ("bench.trace_window", 10.0, 12.0),
            ("bench.step", 10.0, 10.97),
            ("serving.step", 10.0, 10.95),
            ("serving.sampling_sync", 10.1, 10.41),
            ("serving.commit", 10.45, 10.55),
            ("bench.on_token", 10.46, 10.47),
            ("serving.account", 10.55, 10.85),
            ("bench.inject", 10.97, 10.99),
            ("bench.step", 10.99, 11.95),
            ("serving.step", 11.0, 11.9),
            ("serving.admit", 11.0, 11.04),
            ("serving.decode_step", 11.05, 11.8),
            ("serving.stage", 11.1, 11.3),
            ("shard_args", 11.1, 11.2),
            ("DevicePutWithSharding", 11.12, 11.18),
            ("shard_args", 11.2, 11.3),
            ("DevicePutWithSharding", 11.22, 11.28),
            ("serving.launch", 11.3, 11.45),
            ("PjitFunction(_traced)", 11.32, 11.4),
            ("serving.sampling_sync", 11.8, 11.88),
        ],
    }


def _rec(trace, steps=()):
    return {"kind": "serve", "trace": trace,
            "trace_summary": xplane.reduce(trace), "window": (0.0, 50.0),
            "score_end_s": 47.0, "steps": list(steps)}


def _read(name, rec):
    return load_module("metrics", name).read(rec)


def test_own_pieces_partition_nested_spans():
    pieces = phases.own_pieces([("a", 0.0, 10.0), ("b", 1.0, 4.0),
                                ("c", 2.0, 3.0), ("b", 4.0, 6.0),
                                ("d", 9.0, 12.0),     # outlives its parent
                                ("a", 20.0, 21.0)])
    assert pieces == [(0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"),
                      (3.0, 4.0, "b"), (4.0, 6.0, "b"), (6.0, 9.0, "a"),
                      (9.0, 10.0, "d"), (20.0, 21.0, "a")]
    assert phases.own_pieces([]) == []


def test_a_gap_through_several_phases_is_split_by_length():
    trace = _trace()
    summary = xplane.reduce(trace)
    by = phases.idle_by_phase(trace, summary)
    want = {
        # 10.4-11.0: the read's tail, the step's own code before the commit
        # and after the accounting, then the loop between the two steps
        "serving.sampling_sync": 0.01 + 0.08,
        "serving.commit": 0.10,
        "serving.account": 0.30,
        "serving.step": 0.04 + 0.10 + 0.02,
        # 11.5-12.0: the launch has returned, the decode span is still open
        "serving.decode_step": 0.30,
        "outside": 0.05 + 0.10,
    }
    assert set(by) == set(want)
    for name, s in want.items():
        assert by[name] == pytest.approx(s, abs=1e-9), name
    # the whole of each gap, where label_gaps gives 10.4-11.0 to the
    # accounting (its middle) alone
    assert sum(by.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], abs=1e-12)
    totals = dict(summary["idle_gaps"])
    assert totals["total:bench.step>serving.account"] == pytest.approx(0.6)


def test_a_gap_outside_every_step_is_outside():
    trace = {"devices": {0: {"ops": [("fusion.1", 1.0, 2.0)]},
                         1: {"ops": [("fusion.1", 1.0, 1.5)]}},
             "spans": [("bench.trace_window", 1.0, 3.0),
                       ("serving.step", 1.0, 2.0),
                       ("serving.commit", 1.5, 2.0)]}
    by = phases.idle_by_phase(trace, xplane.reduce(trace))
    # mean over the two chips: chip 1 also idles through the commit
    assert by == {"outside": pytest.approx(1.0),
                  "serving.commit": pytest.approx(0.25)}


def test_the_seven_shares_add_up_to_device_idle():
    rec = _rec(_trace())
    got = {name: _read(name, rec) for name in SHARES}
    assert got == {
        "idle_stage": 0.0, "idle_launch": pytest.approx(15.0),
        "idle_sync": pytest.approx(4.5), "idle_commit": pytest.approx(5.0),
        "idle_admit": 0.0, "idle_accounting": pytest.approx(23.0),
        "idle_outside_step": pytest.approx(7.5)}
    assert sum(got.values()) == pytest.approx(_read("device_idle", rec),
                                              abs=1e-9)


def test_uploads_are_counted_in_the_staging_of_decode_launches():
    trace = _trace()
    # a prefill's staging does not count, nor an upload outside a stage
    trace["spans"] += [("serving.prefill", 11.0, 11.04),
                       ("serving.stage", 11.0, 11.02),
                       ("DevicePutWithSharding", 11.0, 11.01),
                       ("DevicePutWithSharding", 11.5, 11.51)]
    assert _read("h2d_puts_per_step", _rec(trace)) == 2.0


def test_trace_stretch_compares_decode_only_steps_traced_and_untraced():
    trace = _trace()
    # untraced decode-only step() calls of the window: 0.75 s the median
    steps = [(1.0, 1.7, 0, 8, 10, 0, 100), (2.0, 2.75, 0, 8, 10, 0, 100),
             (3.0, 3.8, 0, 8, 10, 0, 100), (4.0, 9.0, 2, 8, 10, 0, 100)]
    rec = _rec(trace, steps)
    # traced: the first serving.step launched no decode, the second is 0.9 s
    assert phases.decode_only_step_ms(rec) == pytest.approx(900.0)
    assert _read("trace_stretch", rec) == pytest.approx(20.0)
    trace["spans"].append(("serving.prefill", 11.0, 11.04))
    assert _read("trace_stretch", _rec(trace, steps)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_reads_as_nothing(name):
    """The parent of PR 24, a training record, an untraced run: ``None``,
    no exception, so the result line leaves the metric out."""
    bare = _trace()
    bare["spans"] = [x for x in bare["spans"]
                     if not x[0].startswith("serving.")]
    assert _read(name, _rec(bare)) is None
    assert _read(name, dict(_rec(_trace()), kind="train")) is None
    assert _read(name, {"kind": "serve", "trace": None,
                        "trace_summary": None}) is None


def test_every_new_reader_is_entered_for_the_three_serving_cells():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        knee, tput = entries[name], entries[name + ".tput"]
        assert knee["workloads"] == ["chat_0p8knee", "longprompt_0p8knee"]
        assert knee["moves"] == "tpot_p50_ms"
        assert tput["workloads"] == ["chat_saturated"]
        assert tput["moves"] == "tokens_per_s"
        assert knee["better"] == tput["better"] == "lower"


def _fixtures():
    with open(os.path.join(FIXTURES, "expected_phases.json")) as f:
        return sorted(json.load(f).items())


@pytest.mark.parametrize("name,want", _fixtures())
def test_recorded_tpu_trace_splits_into_known_phases(name, want):
    trace = xplane.read(os.path.join(FIXTURES, name))
    summary = xplane.reduce(trace)
    by = phases.idle_by_phase(trace, summary)
    assert sum(by.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-9)
    assert set(by) == set(want["idle_s"])
    for span, s in want["idle_s"].items():
        assert by[span] == pytest.approx(s, rel=1e-6, abs=1e-12), span
    rec = _rec(trace)
    shares = {n: _read(n, rec) for n in SHARES}
    assert sum(shares.values()) == pytest.approx(_read("device_idle", rec),
                                                 abs=1e-9)
    assert shares == {n: pytest.approx(v, rel=1e-6, abs=1e-9)
                      for n, v in want["shares"].items()}
    assert _read("h2d_puts_per_step", rec) == pytest.approx(
        want["h2d_puts_per_step"])
    assert phases.decode_only_step_ms(rec) == pytest.approx(
        want["decode_only_step_ms"], rel=1e-6)
    # the label of a gap now names the program's phase
    assert any(">serving." in k for k, _ in summary["idle_gaps"])
