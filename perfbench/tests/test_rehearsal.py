"""The CPU rehearsal: the real control flow at toy sizes, counts only, no
metric, ``correct`` false by construction; and no result without a TPU."""

import json

import pytest

from perfbench import run


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return out, json.loads(out[-1])


def test_without_a_tpu_there_is_no_result(capsys):
    rc = run.main(["--workload", "chat_0p8knee", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc != 0
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("cell,traced", [("chat_0p8knee", 0),
                                         ("chat_saturated", 1),
                                         ("train_pretrain", 0)])
def test_rehearsal_runs_the_control_flow_and_states_no_metric(
        capsys, cell, traced):
    rc = run.main(["--workload", cell, "--seed", "3", "--seconds", "2",
                   "--trace", str(traced), "--trace-seconds", "0.5",
                   "--rehearse-cpu"])
    assert rc == 0
    out, line = _last_line(capsys)
    assert line["correct"] is False and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert any("REHEARSAL" in x for x in out)
    counts = json.loads(next(x for x in out if "rehearsal counts" in x)
                        .split("counts: ", 1)[1])
    assert counts["compiles_in_window"] == 0
