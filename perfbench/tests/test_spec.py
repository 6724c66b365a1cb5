"""``BENCHMARK.json`` against the contract's limits and against the metric
files; and a fifth cell arrives as new files plus entries, whether it brings
its own runner and metric or reuses the readers that are there."""

import json
import os
import re
import shutil

import numpy as np

from perfbench.harness.spec import BENCH_DIR, REPO_DIR, Cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        return json.load(f)


def _inside_the_contract(b: dict) -> None:
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(b, indent=1)) < 65536
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 2 <= len(b["workloads"]) <= 24
    assert len(b["end_to_end"]) <= 16 and len(b["per_layer"]) <= 128
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    cells = {w["name"] for w in b["workloads"]}
    assert len(cells) == len(b["workloads"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        reports = [m for m in b["end_to_end"]
                   if w["name"] in m.get("workloads", cells)]
        assert {"setup_s"} < {m["name"] for m in reports}
        layer = [m for m in b["per_layer"]
                 if w["name"] in m.get("workloads", cells)]
        assert layer
        # a per-layer metric only where the metric it moves is reported
        for m in layer:
            assert m["moves"] in {r["name"] for r in reports}, (w, m)
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


def _entries_agree_with_their_readers(b: dict, path: str,
                                      bench_dir: str) -> None:
    """Every metric entry resolves to a reader file, and states the unit and
    the source that reader declares. (Layer, ``moves`` and cells are the
    entry's alone: a reader serves whatever cell names it.)"""
    cell = Cell(b["workloads"][0]["name"], path, bench_dir)
    layers = {}
    for m in b["end_to_end"] + b["per_layer"]:
        reader = cell.reader(m["name"])
        assert callable(reader.read)
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"]), m
        if "layer" in m:    # one reader, one layer, whatever the entry
            assert layers.setdefault(m["name"].split(".")[0],
                                     m["layer"]) == m["layer"], m


def test_benchmark_json_is_inside_the_contract():
    assert os.path.getsize(os.path.join(REPO_DIR, "BENCHMARK.json")) < 65536
    _inside_the_contract(_bench())


def test_metric_entries_agree_with_their_readers():
    _entries_agree_with_their_readers(
        _bench(), os.path.join(REPO_DIR, "BENCHMARK.json"), BENCH_DIR)


def _copy_of_the_benchmark(tmp_path):
    bench_dir = tmp_path / "perfbench"
    for d in ("configs", "traffic", "runners", "generators", "metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench_dir / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    return bench_dir, before


def test_a_cell_of_a_new_kind_is_new_files_and_entries(tmp_path):
    """A throw-away configuration, traffic mix, runner, generator and
    per-layer metric, added beside copies of the files that are there:
    nothing that is there is edited, and the new cell resolves and reads."""
    bench_dir, before = _copy_of_the_benchmark(tmp_path)
    (bench_dir / "configs" / "toy_router.json").write_text(
        json.dumps({"kind": "toy_route", "replicas": 4}))
    (bench_dir / "traffic" / "toy_sessions.json").write_text(
        json.dumps({"kind": "toy_sessions", "turns": 3}))
    (bench_dir / "runners" / "toy_route.py").write_text(
        "def run(ctx):\n    return {'kind': 'toy', 'hits': 3, 'asked': 4,"
        " 'setup_s': 1.0}\n")
    (bench_dir / "generators" / "toy_sessions.py").write_text(
        "def make(traffic, seed, vocab_size, seconds):\n"
        "    return [seed] * traffic['turns']\n")
    (bench_dir / "metrics" / "toy_hit_share.py").write_text(
        "UNIT, SOURCE = '%', 'program_counter'\n"
        "def read(rec):\n    return 100.0 * rec['hits'] / rec['asked']\n")
    (bench_dir / "metrics" / "toy_routed_per_s.py").write_text(
        "UNIT, SOURCE = 'requests/s', 'host_clock'\n"
        "def read(rec):\n    return float(rec['asked'])\n")
    b = _bench()
    b["configs"].append({"name": "toy_router", "source": "none",
                         "file": "perfbench/configs/toy_router.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "toy_cell", "config": "toy_router",
                           "traffic": "toy_sessions", "chips": 1,
                           "why": "test"})
    b["end_to_end"].append({"name": "toy_routed_per_s", "unit": "requests/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["toy_cell"]})
    b["per_layer"].append({"name": "toy_hit_share", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "Router", "moves": "toy_routed_per_s",
                           "workloads": ["toy_cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    _inside_the_contract(b)
    _entries_agree_with_their_readers(b, str(path), str(bench_dir))

    cell = Cell("toy_cell", str(path), str(bench_dir))
    rec = cell.runner().run(None)
    assert cell.generator().make(cell.traffic, 5, 10, 1.0) == [5, 5, 5]
    names = [e["name"] for e in cell.metric_entries(traced=True)]
    assert names == ["toy_hit_share"]
    assert cell.reader("toy_hit_share").read(rec) == 75.0
    assert [e["name"] for e in cell.metric_entries(traced=False)] == [
        "setup_s", "toy_routed_per_s"]
    # the old cells still resolve, and no file that was there changed
    assert Cell("chat_0p8knee", str(path), str(bench_dir)).config["kind"] \
        == "serve"
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_cell_that_reuses_the_readers_is_one_data_file_and_entries(
        tmp_path):
    """The bursty chat cell of ``PERF.md`` section 7: a new traffic file for
    the generator that is there, the configuration that is there, and every
    reader the chat cell uses entered again as ``<reader>.bursty``. No file
    that is there is edited; the load is drawn and every reader reads."""
    bench_dir, before = _copy_of_the_benchmark(tmp_path)
    mix = json.loads((bench_dir / "traffic" / "chat_0p8knee.json")
                     .read_text())
    mix["arrivals"]["bursts"] = {"every_s": 8, "for_s": 2, "factor": 3}
    (bench_dir / "traffic" / "chat_bursty_0p8knee.json").write_text(
        json.dumps(mix))
    b = _bench()
    b["workloads"].append({"name": "chat_bursty_0p8knee",
                           "config": "gpt3_1p3b_serve_bf16",
                           "traffic": "chat_bursty_0p8knee", "chips": 1,
                           "why": "test"})
    reused = [m for m in b["per_layer"]
              if "chat_0p8knee" in m.get("workloads", [])]
    assert len(reused) >= 13
    for m in reused:
        b["per_layer"].append(dict(m, name=m["name"] + ".bursty",
                                   workloads=["chat_bursty_0p8knee"]))
    tpot = next(m for m in b["end_to_end"] if m["name"] == "tpot_p50_ms")
    b["end_to_end"].append(dict(tpot, name="tpot_p50_ms.bursty",
                                workloads=["chat_bursty_0p8knee"]))
    for m in b["per_layer"][-len(reused):]:
        m["moves"] = "tpot_p50_ms.bursty"
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    _inside_the_contract(b)
    _entries_agree_with_their_readers(b, str(path), str(bench_dir))

    cell = Cell("chat_bursty_0p8knee", str(path), str(bench_dir))
    assert cell.runner().__name__.endswith("serve")
    load = cell.generator().make(cell.traffic, 7, 1000, 16.0)
    due = np.array([r.due_s for r in load.schedule])
    w0, w1 = load.phases.window
    in_window = due[(due >= w0) & (due < w1)]
    assert len(in_window) == round(2.4 * 16)         # the same mean rate
    in_burst = ((in_window - w0) % 8) < 2
    assert 0.4 < in_burst.mean() < 0.6               # 3x2 / (3x2 + 6) = 1/2

    reqs = load.schedule
    for i, r in enumerate(reqs):    # every request served on time
        r.sent_s, r.first_s, r.rid = r.due_s, r.due_s + 0.1, i
        r.tokens, r.last_s = r.out_tokens, r.due_s + 0.1 * r.out_tokens
        r.finished_s, r.finish_reason, r.admit_s = r.last_s, "length", r.due_s
    rec = {"kind": "serve", "closed_loop": False, "window": (w0, w1),
           "score_end_s": load.phases.end_s, "requests": reqs,
           "lagging": {}, "limits": cell.traffic["limits"], "setup_s": 30.0,
           "steps": [(w0, w0 + 0.1, 0, 16, 100, 0, 4000)], "tokens_at": [],
           "max_num_seqs": 32, "total_blocks": 1000, "preemptions": 0}
    traced = {e["name"]: cell.reader(e["name"]).read(rec)
              for e in cell.metric_entries(traced=True)}
    assert set(traced) == {m["name"] + ".bursty" for m in reused}
    assert abs(traced["ttft_p50_ms.bursty"] - 100.0) < 1e-6
    assert traced["slo_attain.bursty"] == 100.0
    assert traced["device_idle.bursty"] is None      # no trace: left out
    untraced = {e["name"]: cell.reader(e["name"]).read(rec)
                for e in cell.metric_entries(traced=False)}
    assert set(untraced) == {"setup_s", "tpot_p50_ms.bursty"}
    assert abs(untraced["tpot_p50_ms.bursty"] - 100.0) < 1e-6
    assert all(p.read_bytes() == data for p, data in before.items())
