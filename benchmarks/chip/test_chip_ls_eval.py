"""The ``ls_eval_ratio`` reader: the line search's lane evaluations per
trial its lanes asked for, on hand-built rounds with hand-counted
answers, across a restart of the job, and silent on rounds whose program
does not count its passes."""
import json
from pathlib import Path

import numpy as np
import pytest

import harness
from test_chip_bench import metrics, record

HERE = Path(__file__).resolve().parent
SPEC = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())


def counted(k, iters, trials, slots):
    return metrics(k, iters)._replace(ls_trials=np.asarray(trials),
                                      ls_slots=slots)


def window():
    """Two rounds of one job, then the first round of its restart."""
    return record([
        counted(1, [4, 2, 2, 1], [7, 3, 2, 1], 16),
        counted(2, [1, 1, 1, 1], [1, 1, 2, 1], 6),
        counted(1, [3, 3, 3, 3], [6, 3, 3, 4], 24),
    ])


def test_ls_eval_ratio_over_rounds_and_restarts():
    got = harness.load_reader("ls_eval_ratio")(window())
    assert got == pytest.approx((16 + 6 + 24) / (13 + 5 + 16))


def test_ls_eval_ratio_is_one_when_every_slot_is_a_trial():
    rounds = [counted(2, [2, 1, 1, 3], [3, 1, 2, 4], 10)]
    assert harness.load_reader("ls_eval_ratio")(record(rounds)) == 1.0


def test_ls_eval_ratio_silent_without_the_count():
    read = harness.load_reader("ls_eval_ratio")
    # a program that counts trials but not its passes
    assert read(record([counted(2, [1, 1, 1, 1], [1, 1, 1, 1], None)])) is None
    assert read(record([metrics(2, [1, 1, 1, 1])])) is None
    assert read(record([])) is None
    # one round of the window lacks its count: no partial ratio
    rounds = window().window.rounds
    bare = rounds[1]._replace(ls_slots=None)
    assert read(record([rounds[0], bare, rounds[2]])) is None


def test_ls_eval_ratio_is_listed_in_the_benchmark():
    metric = {m["name"]: m for m in SPEC["per_layer"]}["ls_eval_ratio"]
    assert metric["moves"] == "round_s"
    assert metric["source"] == "program_counter"
    assert metric["layer"] == "worker phase"
    assert "workloads" not in metric
