"""The uniform-load cell (K_w=50) and its ``floor_frac`` reader: at a tiny
size on the CPU the cell's job is correct with every lane at 50
iterations or more, a program that drops the floor fails the check, and
the reader counts the iterations past each lane's tolerance by hand,
from the program's own rounds, and not at all where the program keeps no
such table."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import harness
from repro.problems.base import BatchedShardProblem
from test_chip_bench import metrics

HERE = Path(__file__).resolve().parent
SPEC = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
UNIFORM = "logreg-paper-uniform.w64"
PAPER = "logreg-paper.w64"
SEED = 2**31 + 211


def tiny(name):
    cell = harness.load_cell(name)
    cfg = dict(cell.config, n_samples=2_000, n_features=400, density=0.02)
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, n_workers=8))


def over(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


def run(cell, monkeypatch):
    """``harness.run`` off the chip, keeping every round it drove."""
    seen = []
    real = harness.step

    def step(sched):
        m, done = real(sched)
        seen.append(m)
        return m, done

    monkeypatch.setattr(harness, "step", step)
    res = harness.run(cell, SEED, 0.2, False, t_start=0.0,
                      require_chip=False)
    return res, seen


def test_cell_differs_from_the_paper_cell_by_k_w_alone():
    uni, paper = harness.load_cell(UNIFORM), harness.load_cell(PAPER)
    assert uni.config["fista"] == dict(paper.config["fista"], min_iters=50)
    same = {k: v for k, v in uni.config.items()
            if k not in ("name", "source", "deployment", "fista", "assumed")}
    assert same == {k: paper.config[k] for k in same}
    assert uni.traffic == paper.traffic
    assert (uni.compare_rounds, uni.check_lanes) == (
        paper.compare_rounds, paper.check_lanes)
    # iter_gap is tighter: a floor of 50 makes each round's count larger
    assert uni.limits == dict(paper.limits, iter_gap=0.005)


def test_tiny_uniform_run_is_correct_at_fifty_iterations(monkeypatch):
    res, rounds = run(tiny(UNIFORM), monkeypatch)
    assert res["correct"] is True and over(res) == []
    assert res["checks"]["iter_gap"]["value"] == 0.0
    assert len(rounds) >= 3
    for m in rounds:
        assert min(int(k) for k in m.inner_iters) >= 50
        assert np.all(m.tol_iters < m.inner_iters)


def test_fault_floor_ignored_fails_iter_gap(monkeypatch):
    """A program that stops each lane at its tolerance, as at K_w=1,
    reports fewer iterations than the paper's rule makes."""
    real = BatchedShardProblem.solve_all

    def solve_all(self, *args, **kwargs):
        if self.fista.min_iters != 1:
            self.fista = dataclasses.replace(self.fista, min_iters=1)
            self._batched_solver_cache = None
        return real(self, *args, **kwargs)

    monkeypatch.setattr(BatchedShardProblem, "solve_all", solve_all)
    res, _ = run(tiny(UNIFORM), monkeypatch)
    assert res["correct"] is False
    assert over(res) == ["iter_gap"]


def record(rounds):
    cell = harness.load_cell(UNIFORM)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, n_workers=4))
    win = harness.Window(rounds=rounds, seconds=2.0, restarts=1, compiles=0)
    return harness.RunRecord(cell=cell, window=win, stage_s=1.0, trace=None,
                             peak=None)


def counted(k, iters, tol):
    return metrics(k, iters)._replace(tol_iters=np.asarray(tol))


def test_floor_frac_over_rounds_and_restarts():
    # two rounds of one job, then the first round of its restart
    rounds = [counted(1, [50, 50, 52, 50], [20, 31, 52, 12]),
              counted(2, [50, 50, 50, 50], [9, 50, 11, 14]),
              counted(1, [60, 55, 50, 50], [60, 55, 40, 50])]
    floor = (30 + 19 + 0 + 38) + (41 + 0 + 39 + 36) + (0 + 0 + 10 + 0)
    iters = 202 + 200 + 215
    assert harness.load_reader("floor_frac")(record(rounds)) == \
        pytest.approx(floor / iters)


def test_floor_frac_reads_zero_where_lanes_stop_at_tolerance():
    rounds = [counted(2, [14, 9, 19, 3], [14, 9, 19, 3])]
    assert harness.load_reader("floor_frac")(record(rounds)) == 0.0


@pytest.mark.parametrize("name, positive", [(PAPER, False), (UNIFORM, True)],
                         ids=["k_w-1", "k_w-50"])
def test_floor_frac_on_the_programs_rounds(name, positive):
    spec = harness.experiment_spec(tiny(name), SEED)
    _, sched = harness.stage(spec)
    rounds = [harness.step(sched)[0] for _ in range(3)]
    got = harness.load_reader("floor_frac")(record(rounds))
    assert (got > 0.5) if positive else (got == 0.0)


def test_floor_frac_silent_without_the_programs_table():
    read = harness.load_reader("floor_frac")
    assert read(record([metrics(2, [1, 1, 1, 1])])) is None
    assert read(record([])) is None
    # one round of the window lacks its table: no partial share
    rounds = [counted(1, [50] * 4, [10] * 4), metrics(2, [50] * 4),
              counted(3, [50] * 4, [20] * 4)]
    assert read(record(rounds)) is None


def test_floor_frac_is_listed_in_the_benchmark():
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    m = per_layer["floor_frac"]
    assert (m["moves"], m["source"], m["layer"], m["better"]) == (
        "round_s", "program_counter", "worker phase", "lower")
    assert "workloads" not in m
    assert callable(harness.load_reader("floor_frac"))
