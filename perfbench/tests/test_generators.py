"""The load generators: the same seed gives the same schedule, lengths and
token ids; the work of a seed is fixed; a closed loop keeps its clients."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import draws
from perfbench.harness.spec import BENCH_DIR, REPO_DIR, Cell, load_module

VOCAB = 50304


def _traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def _make(name, seed, seconds=20):
    t = _traffic(name)
    return load_module("generators", t["kind"]).make(t, seed, VOCAB, seconds)


def _signature(load):
    return [(r.due_s, r.out_tokens, r.prompt.tolist()) for r in load.schedule]


@pytest.mark.parametrize("mix", ["chat_0p8knee", "longprompt_0p8knee",
                                 "chat_saturated"])
def test_same_seed_same_schedule_lengths_and_ids(mix):
    assert _signature(_make(mix, 7)) == _signature(_make(mix, 7))
    assert _signature(_make(mix, 7)) != _signature(_make(mix, 8))


@pytest.mark.parametrize("mix", ["chat_0p8knee", "longprompt_0p8knee"])
def test_open_loop_work_of_a_seed_is_fixed(mix):
    t = _traffic(mix)
    cls = t["classes"][0]
    counts, totals = set(), []
    for seed in range(4):
        load = _make(mix, seed, seconds=20)
        w0, w1 = load.phases.window
        due = [r for r in load.schedule if w0 <= r.due_s < w1]
        counts.add(len(due))
        totals.append(sum(len(r.prompt) for r in due))
        for r in load.schedule:
            assert (cls["prompt_tokens"]["min"] <= len(r.prompt)
                    <= cls["prompt_tokens"]["max"])
            assert 1 <= r.out_tokens <= cls["output_tokens"]["max"]
            assert len(r.prompt) + r.out_tokens <= 2048
            assert r.prompt.dtype == np.int32 and r.prompt.max() < VOCAB
        assert [r.due_s for r in load.schedule] == sorted(
            r.due_s for r in load.schedule)
    assert counts == {round(t["arrivals"]["rate_per_s"] * 20)}
    # stratified lengths: the window's prompt tokens agree across seeds
    assert max(totals) / min(totals) < 1.05


def test_open_loop_residents_enter_at_zero_with_residual_outputs():
    t = _traffic("chat_0p8knee")
    load = _make("chat_0p8knee", 3)
    first = load.schedule[: t["ramp"]["residents"]]
    assert all(r.due_s == 0.0 for r in first)
    rest = load.schedule[t["ramp"]["residents"]:]
    assert all(r.due_s > 0.0 for r in rest)


def test_open_loop_pops_in_due_order_and_only_what_is_due():
    load = _make("chat_0p8knee", 5)
    got = load.pop_due(2.0)
    assert got and all(r.due_s <= 2.0 for r in got)
    assert load.next_due_s() > 2.0
    assert load.pop_due(2.0) == []


def test_bursts_keep_the_count_and_move_arrivals_into_the_bursts():
    proc = {"process": "poisson", "rate_per_s": 3.0,
            "bursts": {"every_s": 8, "for_s": 2, "factor": 3}}
    t = draws.arrivals(proc, 0.0, 48.0, np.random.default_rng(0))
    assert len(t) == 144 and (np.diff(t) >= 0).all()
    assert t.min() >= 0.0 and t.max() < 48.0
    inside = int(((t % 8) < 2).sum())
    assert 60 <= inside <= 84      # half of them in a quarter of the time


def test_closed_loop_keeps_exactly_its_clients_outstanding():
    load = _make("chat_saturated", 11, seconds=30)
    clients = _traffic("chat_saturated")["clients"]
    assert load.outstanding_target == clients == 64
    outstanding = load.pop_due(0.0)
    assert len(outstanding) == clients
    assert sorted(r.client for r in outstanding) == list(range(clients))
    assert load.pop_due(0.1) == []
    now = 0.5
    for _ in range(200):          # finish one, its client sends the next
        done = outstanding.pop(0)
        load.finished(done, now)
        new = load.pop_due(now)
        assert len(new) == 1 and new[0].client == done.client
        assert new[0].due_s == now
        outstanding += new
        assert len(outstanding) == clients
        now += 0.1
    # after the run's end nobody sends again
    load.finished(outstanding[0], load.phases.end_s + 1)
    assert load.pop_due(load.phases.end_s + 1) == []


def test_closed_loop_clients_draw_their_own_sequences():
    a, b = _make("chat_saturated", 11), _make("chat_saturated", 11)
    ra, rb = a.pop_due(0.0), b.pop_due(0.0)
    # completing in another order does not change what a client sends next
    for r in ra:
        a.finished(r, 1.0)
    for r in reversed(rb):
        b.finished(r, 1.0)
    na = {r.client: r.prompt.tolist() for r in a.pop_due(1.0)}
    nb = {r.client: r.prompt.tolist() for r in b.pop_due(1.0)}
    assert na == nb


def test_train_job_batches_repeat_and_shift_labels_by_one():
    job = _make("train_pretrain", 4)
    ids, labels = job.batch_at(3)
    ids2, _ = _make("train_pretrain", 4).batch_at(3)
    assert (ids == ids2).all() and ids.shape == (4, 1024)
    assert (ids[:, 1:] == labels[:, :-1]).all()
    assert not (ids == job.batch_at(4)[0]).all()


def test_every_workload_resolves_to_files_that_exist():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        assert callable(cell.runner().run)
        assert callable(cell.generator().make)
        for traced in (False, True):
            entries = cell.metric_entries(traced)
            assert entries
            for e in entries:
                assert callable(cell.reader(e["name"]).read)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(REPO_DIR, c["file"]))
