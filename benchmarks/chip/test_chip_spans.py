"""The readers of the program's own spans and counters (``host_ms``,
``sync_ms``, ``ls_per_iter``): on hand-built rounds with hand-counted
answers, across a restart of the job, and silent on rounds whose program
keeps no such tables."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import harness
from repro.runtime.scheduler import RoundMetrics
from test_chip_bench import metrics as bare_metrics

HERE = Path(__file__).resolve().parent
SPEC = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CELL = SPEC["workloads"][0]["name"]
READERS = ("host_ms", "sync_ms", "ls_per_iter")


def metrics(k, iters, span_s=None, ls_trials=None):
    iters = np.asarray(iters)
    w = len(iters)
    return RoundMetrics(k=k, sim_time=0.0, r_norm=1.0, s_norm=1.0, rho=1.0,
                        t_comp=np.zeros(w), t_comm=np.zeros(w),
                        t_idle=np.zeros(w), inner_iters=iters, n_respawns=0,
                        slowest10=np.zeros(w, bool), span_s=span_s,
                        ls_trials=(None if ls_trials is None
                                   else np.asarray(ls_trials)))


def record(rounds):
    cell = harness.load_cell(CELL)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, n_workers=4))
    win = harness.Window(rounds=rounds, seconds=2.0, restarts=1, compiles=0)
    return harness.RunRecord(cell=cell, window=win, stage_s=1.0, trace=None,
                             peak=None)


def spans(round_s, solve_wait, q_wait, master_wait, rho_wait):
    return {"round": round_s, "round.respawn": 0.001,
            "round.solve": solve_wait + q_wait + 0.002,
            "round.q.wait": q_wait, "round.solve.wait": solve_wait,
            "round.master": master_wait + rho_wait + 0.001,
            "round.master.wait": master_wait, "round.rho.wait": rho_wait,
            "round.bill": 0.0005}


def window():
    """Two rounds of one job, then the first round of its restart."""
    return record([
        metrics(1, [4, 2, 2, 1], spans(4.0, 3.9, 0.002, 0.001, 0.003),
                [7, 3, 2, 1]),
        metrics(2, [1, 1, 1, 1], spans(3.0, 2.95, 0.001, 0.002, 0.001),
                [1, 1, 2, 1]),
        metrics(1, [3, 3, 3, 3], spans(5.0, 4.8, 0.003, 0.003, 0.002),
                [6, 3, 3, 4]),
    ])


def test_host_ms_is_round_less_its_waits():
    own = [4.0 - (3.9 + 0.002 + 0.001 + 0.003),
           3.0 - (2.95 + 0.001 + 0.002 + 0.001),
           5.0 - (4.8 + 0.003 + 0.003 + 0.002)]
    assert harness.load_reader("host_ms")(window()) == pytest.approx(
        1e3 * sum(own) / 3)


def test_sync_ms_is_every_wait_but_the_solve():
    waits = [0.002 + 0.001 + 0.003, 0.001 + 0.002 + 0.001,
             0.003 + 0.003 + 0.002]
    assert harness.load_reader("sync_ms")(window()) == pytest.approx(
        1e3 * sum(waits) / 3)


def test_host_sync_and_solve_wait_make_the_round():
    rec = window()
    solve = np.mean([m.span_s["round.solve.wait"] for m in rec.window.rounds])
    whole = np.mean([m.span_s["round"] for m in rec.window.rounds])
    total = (harness.load_reader("host_ms")(rec)
             + harness.load_reader("sync_ms")(rec) + 1e3 * solve)
    assert total == pytest.approx(1e3 * whole)


def test_ls_per_iter_over_rounds_and_restarts():
    got = harness.load_reader("ls_per_iter")(window())
    assert got == pytest.approx((13 + 5 + 16) / (9 + 4 + 12))
    assert got >= 1


@pytest.mark.parametrize("name", READERS)
def test_readers_silent_without_the_programs_tables(name):
    read = harness.load_reader(name)
    assert read(record([bare_metrics(2, [1, 1, 1, 1])])) is None
    assert read(record([])) is None
    # one round of the window lacks its table: no partial mean
    rounds = window().window.rounds
    bare = rounds[1]._replace(span_s=None, ls_trials=None)
    assert read(record([rounds[0], bare, rounds[2]])) is None


def test_readers_are_listed_in_the_benchmark():
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in READERS:
        assert per_layer[name]["moves"] == "round_s"
        assert callable(harness.load_reader(name))
    assert per_layer["host_ms"]["source"] == "program_span"
    assert per_layer["ls_per_iter"]["source"] == "program_counter"
