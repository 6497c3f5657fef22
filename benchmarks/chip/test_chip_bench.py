"""CPU checks of the chip benchmark: its files, its refusal to run off the
chip, the cells' jobs at a tiny size, the window's accounting and the
metric readers."""
import dataclasses
import json
import re
from pathlib import Path

import numpy as np
import pytest

import bench
import harness
import reference
from repro.runtime.scheduler import RoundMetrics

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/bench.py"]
    assert SPEC["paths"] == ["benchmarks/chip"]
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert {m["name"] for m in SPEC["end_to_end"]} == {"round_s", "setup_s"}
    for m in SPEC["per_layer"]:
        assert m["moves"] in {"round_s", "setup_s"}


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(cfg):
    path = REPO / cfg["file"]
    assert path == HERE / "configs" / f"{cfg['name']}.json"
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    for key in ("n_samples", "n_features", "density", "lam1", "dtype",
                "admm", "fista", "assumed"):
        assert key in data
    assert data["dtype"] == "float32"


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = harness.load_cell(name)
    assert set(cell.limits) == set(reference.CHECKS)
    assert all(v > 0 for v in cell.limits.values())
    assert cell.compare_rounds >= 2 and cell.check_lanes >= 1
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"round_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))


def test_peaks_table_names_the_v5e():
    peaks = harness.device_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.device_peaks("cpu")


@pytest.fixture()
def env(monkeypatch):
    """``bench.main`` sets the cache directory in the environment."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unset-by-test")
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    return monkeypatch


def test_command_refuses_without_a_tpu(env, capsys):
    with pytest.raises(SystemExit) as exc:
        bench.main(["--workload", CELLS[0], "--seed", "2147483701",
                    "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_command_refuses_uncompiled_kernels(env, capsys, mode):
    env.setenv("REPRO_PALLAS", mode)
    with pytest.raises(SystemExit) as exc:
        bench.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                    "--trace", "1"])
    assert "REPRO_PALLAS" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell("no-such-cell")


def tiny(cell: harness.Cell, **traffic) -> harness.Cell:
    """The cell's job at a size the CPU runs in seconds."""
    cfg = dict(cell.config, n_samples=1_000, n_features=300, density=0.02)
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, n_workers=4,
                                            **traffic))


@pytest.mark.parametrize("name", CELLS)
def test_cell_specs_build_through_the_api(name, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cell = tiny(harness.load_cell(name))
    spec = harness.experiment_spec(cell, seed=2**31 + 17)
    assert spec.problem == "logreg" and spec.max_rounds == 100
    assert spec.scheduler.kernel == cell.traffic["scheduler"].get("kernel",
                                                                   "xla")
    problem, sched = harness.stage(spec)
    m, done = harness.step(sched)
    assert m.k == 1 and len(m.inner_iters) == 4 and not done


def test_window_counts_rounds_across_restarts():
    cell = tiny(harness.load_cell(CELLS[0]))
    cfg = dict(cell.config, admm=dict(cell.config["admm"], max_iters=2))
    spec = harness.experiment_spec(dataclasses.replace(cell, config=cfg), 3)
    problem, sched = harness.stage(spec)
    harness.step(sched)                    # round 1 compiles, in set-up
    seen = []
    win = harness.run_window(spec, problem, sched, 0.5,
                             lambda s, m, r: seen.append((m.k, r)))
    assert win.seconds >= 0.5 and len(win.rounds) == len(seen) >= 5
    assert seen[:5] == [(2, 0), (1, 1), (2, 1), (1, 2), (2, 2)]
    assert win.restarts == len(seen) // 2


def test_tiny_run_is_correct_and_prints_checks_last():
    cell = tiny(harness.load_cell(CELLS[0]))
    res = harness.run(cell, 2**31 + 5, 0.2, False, t_start=0.0,
                      require_chip=False)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(reference.CHECKS)
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert res["metrics"]["round_s"]["unit"] == "s"
    assert res["attempted"] == res["window_rounds"] >= 1
    assert res["failed"] == 0 and res["window_compiles"] == 0
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in res["device"]


def metrics(k, iters):
    iters = np.asarray(iters)
    w = len(iters)
    return RoundMetrics(k=k, sim_time=0.0, r_norm=1.0, s_norm=1.0, rho=1.0,
                        t_comp=np.zeros(w), t_comm=np.zeros(w),
                        t_idle=np.zeros(w), inner_iters=iters, n_respawns=0,
                        slowest10=np.zeros(w, bool))


def record(rounds, trace=None, stage_s=1.5):
    cell = harness.load_cell(CELLS[0])
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, n_workers=4))
    win = harness.Window(rounds=rounds, seconds=2.0, restarts=1, compiles=0)
    return harness.RunRecord(cell=cell, window=win, stage_s=stage_s,
                             trace=trace,
                             peak=harness.device_peaks("TPU v5 lite"))


def test_lane_util_over_rounds_and_restarts():
    read = harness.load_reader("lane_util")
    # two rounds of one job, then the first round of its restart
    rounds = [metrics(1, [4, 2, 2, 0]), metrics(2, [1, 1, 1, 1]),
              metrics(1, [3, 3, 3, 3])]
    assert read(record(rounds)) == pytest.approx((8 + 4 + 12) / (16 + 4 + 12))
    assert read(record([])) is None


def fake_trace(solve_s=2.0, other_s=0.004, busy_s=1.8, window_s=2.0):
    import trace_reduce
    return trace_reduce.Summary(
        window_s=window_s, busy_s=busy_s,
        program_s={"jit_run_all": solve_s, "jit_mean": other_s},
        solve_program="jit_run_all", top_ops=[], idle_gaps=[])


def test_trace_readers_on_a_summary():
    rounds = [metrics(2, [10, 12, 12, 11]), metrics(3, [9, 9, 9, 9])]
    rec = record(rounds, fake_trace())
    assert harness.load_reader("device_idle_frac")(rec) == pytest.approx(0.1)
    assert harness.load_reader("master_ms")(rec) == pytest.approx(2.0)
    assert harness.load_reader("stage_s")(rec) == 1.5
    roof = harness.load_reader("solve_roofline")(rec)
    import work
    least, _ = work.solve_least_seconds(rec.cell.config, 4,
                                        [m.inner_iters for m in rounds],
                                        rec.peak)
    assert roof == pytest.approx(100 * least / 2.0)
    assert 0 < roof < 100


def test_trace_readers_are_silent_without_a_trace():
    rec = record([metrics(2, [1, 1, 1, 1])])
    for name in ("device_idle_frac", "master_ms", "solve_roofline"):
        assert harness.load_reader(name)(rec) is None


def test_window_counts_what_compiles_inside_it():
    cell = tiny(harness.load_cell(CELLS[0]))
    spec = harness.experiment_spec(cell, 11)
    problem, sched = harness.stage(spec)
    counter = harness.CompileCounter()
    try:
        win = harness.run_window(spec, problem, sched, 0.0,
                                 lambda s, m, r: None, counter)
    finally:
        counter.close()
    assert len(win.rounds) == 1 and win.compiles > 0   # round 1 compiled
