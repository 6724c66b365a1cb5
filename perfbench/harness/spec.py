"""From a cell's name in ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, one traffic mix, one runner,
one generator or one metric sits in a file of its own, found by name:

    workloads[].config   -> <bench>/configs/<config>.json    (has ``kind``)
    workloads[].traffic  -> <bench>/traffic/<traffic>.json   (has ``kind``)
    config ``kind``      -> <bench>/runners/<kind>.py        (``run(ctx)``)
    traffic ``kind``     -> <bench>/generators/<kind>.py
    metric ``name``      -> <bench>/metrics/<name up to the first '.'>.py

A metric named ``x.y`` is the reader ``x`` entered again: for cells in which
it moves another end-to-end metric (``BENCHMARK.json`` allows one ``moves`` to
an entry, and a per-layer metric only where that metric is reported), or for
a cell added later. A reader knows no cell: layer, ``moves`` and cells are the
entry's. So a later PR adds a cell, a runner, a generator or a metric with new
files and new entries, and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The cell, or a file it names, does not resolve."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_module(kind_dir: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind_dir>/<name>.py`` by its path."""
    path = os.path.join(bench_dir, kind_dir, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind_dir[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind_dir}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_class(path: str):
    """The class a configuration file names (``package.module.Class``)."""
    import importlib

    mod, name = path.rsplit(".", 1)
    return getattr(importlib.import_module(mod), name)


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, workload: str, benchmark_json: str = None,
                 bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.benchmark = load_json(
            benchmark_json or os.path.join(REPO_DIR, "BENCHMARK.json"))
        entries = [w for w in self.benchmark["workloads"]
                   if w["name"] == workload]
        if len(entries) != 1:
            raise SpecError(f"workload {workload!r} is not in BENCHMARK.json "
                            f"exactly once")
        self.entry = entries[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        self.config = load_json(os.path.join(
            bench_dir, "configs", self.config_name + ".json"))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        for what, d in (("configuration", self.config),
                        ("traffic mix", self.traffic)):
            if "kind" not in d:
                raise SpecError(f"the {what} of {workload!r} has no 'kind'")

    def runner(self):
        return load_module("runners", self.config["kind"], self.bench_dir)

    def generator(self):
        return load_module("generators", self.traffic["kind"],
                           self.bench_dir)

    def metric_entries(self, traced: bool) -> list:
        """This cell's ``per_layer`` entries (traced run) or ``end_to_end``
        entries (untraced run): those that list the cell, or list none."""
        group = "per_layer" if traced else "end_to_end"
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric_name: str):
        return load_module("metrics", metric_name.split(".")[0],
                           self.bench_dir)
