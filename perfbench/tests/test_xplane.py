"""The reduction from a device trace to busy time, top operations and
attributed idle gaps: on hand-made events, and on TPU traces recorded on the
chip (``perfbench/fixtures``, see its README)."""

import json
import os

import pytest

from perfbench.harness import xplane
from perfbench.harness.spec import BENCH_DIR

FIXTURES = os.path.join(BENCH_DIR, "fixtures")


def test_union_and_gaps():
    busy = xplane.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (9.0, 12.0)],
                        0.5, 10.0)
    assert busy == [[0.5, 2.0], [3.0, 4.0], [9.0, 10.0]]
    assert xplane.gaps(busy, 0.5, 10.0) == [(2.0, 3.0), (4.0, 9.0)]
    assert xplane.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_reduce_attributes_gaps_to_the_covering_span():
    trace = {
        "devices": {0: {"ops": [
            ("fusion.1", 10.0, 10.4), ("fusion.2", 10.4, 10.5),
            ("fusion.1", 11.0, 11.4), ("copy.3", 11.9, 12.0)]}},
        "spans": [("bench.trace_window", 10.0, 12.0),
                  ("bench.step", 10.0, 10.9), ("bench.on_token", 10.6, 10.7),
                  ("bench.inject", 10.9, 11.0), ("bench.step", 11.0, 12.0),
                  ("shard_args", 11.5, 11.8)],
    }
    out = xplane.reduce(trace)
    assert abs(out["busy_s"] - 1.0) < 1e-9 and out["window_s"] == 2.0
    assert out["device_ops"][0][0] == "fusion.1"
    assert abs(out["device_ops"][0][1] - 0.8) < 1e-9
    totals = {k: v for k, v in out["idle_gaps"] if k.startswith("total:")}
    # the middle of 10.5-11.0 lies under the first bench.step (its
    # bench.on_token has ended), that of 11.4-11.9 under the second one's
    # shard_args
    assert abs(totals["total:bench.step"] - 0.5) < 1e-9
    assert abs(totals["total:bench.step>shard_args"] - 0.5) < 1e-9
    singles = [g for g in out["idle_gaps"] if not g[0].startswith("total:")]
    assert sorted(g[0] for g in singles) == ["bench.step",
                                             "bench.step>shard_args"]
    # device time inside host intervals, for telling decode steps apart
    assert abs(xplane.busy_within(trace, [(10.0, 10.9)]) - 0.5) < 1e-9


def test_op_key_and_op_name():
    text = ("%convert.295 = f32[4096,16,16,128]{3,2,1,0:T(8,128)} convert("
            "bf16[4096,16,16,128]{3,2,1,0:T(8,128)(2,1)} %fusion.38)")
    assert xplane.op_key(text) == "convert f32[4096,16,16,128]"
    assert xplane.op_name(text) == "convert.295"
    assert xplane.op_key(
        "%fusion.7.remat = (bf16[4,8]{1,0}, f32[2]{0}) fusion(f32[4] %x), "
        "kind=kLoop, calls=%f") == "fusion.Loop bf16[4,8]"
    assert xplane.op_key("bench.step") == "bench.step"


def test_reduce_refuses_a_trace_without_device_operations():
    assert xplane.reduce({"devices": {}, "spans": []}) is None
    assert xplane.reduce({"devices": {0: {"ops": []}},
                          "spans": []}) is None


def _fixtures():
    with open(os.path.join(FIXTURES, "expected.json")) as f:
        return sorted(json.load(f).items())


@pytest.mark.parametrize("name,want", _fixtures())
def test_recorded_tpu_trace_reduces_to_known_numbers(name, want):
    trace = xplane.read(os.path.join(FIXTURES, name))
    assert sorted(trace["devices"]) == want["devices"]
    out = xplane.reduce(trace)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    idle = 100.0 * (1 - out["busy_s"] / out["window_s"])
    assert idle == pytest.approx(want["idle_percent"], rel=1e-6)
    assert [k for k, _ in out["device_ops"][:3]] == want["top3_ops"]
    assert [k for k, _ in out["idle_gaps"]] == want["idle_gap_labels"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_flash_kernels_are_found_by_name_in_the_recorded_train_trace():
    from perfbench.harness.spec import load_module

    trace = xplane.read(os.path.join(FIXTURES,
                                     "train_step_2layers.xplane.pb.gz"))
    reader = load_module("metrics", "flash_roofline")
    names = [xplane.op_name(n) for n, _, _ in trace["devices"][0]["ops"]]
    fwd = sum(bool(reader.FWD.search(n)) for n in names)
    bwd = sum(bool(reader.BWD.search(n)) for n in names)
    # 2 layers: forward runs twice a layer (once more under recomputation),
    # backward is two kernels a layer
    assert fwd > 0 and fwd == bwd
    rec = {"kind": "train", "trace": trace,
           "trace_summary": xplane.reduce(trace), "batch": 4,
           "sequence": 1024, "device": {"kind": "TPU v5 lite"},
           "model": {"num_heads": 16, "hidden_size": 2048}}
    # what the run that recorded the fixture printed (chip run, PR 22)
    assert reader.read(rec) == pytest.approx(30.078569906286262, rel=1e-9)
