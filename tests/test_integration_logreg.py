"""End-to-end integration: the paper's workload through the full runtime.

A 1/75-scale instance of the paper's problem (same generator, same
tolerances-to-scale) must converge through the simulated serverless pool,
survive failures, and produce the utilization metrics the paper reports.
The FULL-scale instance (N=600k, d=10k, W=64, f64) runs in
benchmarks/fig3_convergence.py (k=36 vs the paper's <=23; see
EXPERIMENTS.md §Paper).
"""
import numpy as np
import pytest

from repro.configs.logreg_paper import scaled
from repro.core.admm import AdmmOptions
from repro.core.fista import FistaOptions
from repro.problems import LogRegProblem
from repro.runtime import PoolConfig, Scheduler, SchedulerConfig


@pytest.fixture(scope="module")
def setup():
    cfg = scaled(8_000, 512, density=0.02, lam1=1.0)
    prob = LogRegProblem(cfg, fista=FistaOptions(min_iters=1, eps_grad=1e-3))
    return cfg, prob


def test_end_to_end_converges_with_modest_accuracy(setup):
    cfg, prob = setup
    sched = Scheduler(prob, SchedulerConfig(
        n_workers=8,
        admm=AdmmOptions(rho0=1.0, max_iters=60,
                         eps_primal=5e-2, eps_dual=5e-2),
        pool=PoolConfig(seed=0)))
    z = sched.solve()
    assert sched.k < 60
    obj = prob.objective(z, 8)
    obj0 = prob.objective(z * 0, 8)
    assert obj < 0.8 * obj0                      # real progress
    # residual trace decayed monotonically-ish (allow adaptation bumps)
    rs = [m.r_norm for m in sched.history[1:]]
    assert rs[-1] < rs[0] / 50


def test_metrics_reproduce_paper_structure(setup):
    """idle = comm + proc; delay = comm + comp (paper Section II-B)."""
    cfg, prob = setup
    sched = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=10),
        pool=PoolConfig(seed=1)))
    m = sched.run_round()
    # all components positive and idle excludes own compute
    assert np.all(m.t_idle >= -1e-9)
    assert np.all(m.t_comp > 0)
    assert np.all(m.t_comm > 0)
    # round wall time = compute + idle for every worker (definitionally)
    total = m.t_comp + m.t_idle
    np.testing.assert_allclose(total, total[0], rtol=1e-6)


def test_survives_failures_and_matches_failure_free_solution(setup):
    cfg, prob = setup
    a = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=25),
        pool=PoolConfig(seed=2)))
    za = a.solve(max_rounds=25)
    b = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=25),
        pool=PoolConfig(seed=3, fail_rate_per_round=0.1, lifetime_s=20.0)))
    zb = b.solve(max_rounds=25)
    assert b.n_respawns > 3
    # failures cost TIME (cold restarts) but not CORRECTNESS: state is
    # preserved across respawns, so the math is identical
    np.testing.assert_array_equal(np.asarray(za), np.asarray(zb))
    assert b.sim_time > a.sim_time


def test_checkpoint_restart_identical_trajectory(setup, tmp_path):
    from repro import checkpoint as ck
    cfg, prob = setup
    base = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=16), pool=PoolConfig(seed=4)))
    for _ in range(16):
        base.run_round()

    first = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=16), pool=PoolConfig(seed=4)))
    for _ in range(8):
        first.run_round()
    state = {"z": first.z, "x": first.x, "u": first.u,
             "rho": np.float32(first.rho)}
    ck.save(state, tmp_path, 8)

    second = Scheduler(prob, SchedulerConfig(
        n_workers=8, admm=AdmmOptions(max_iters=16), pool=PoolConfig(seed=4)))
    restored, _ = ck.restore(state, tmp_path)
    second.z, second.x, second.u = restored["z"], restored["x"], restored["u"]
    second.rho = float(restored["rho"])
    for _ in range(8):
        second.run_round()
    np.testing.assert_allclose(np.asarray(second.z), np.asarray(base.z),
                               rtol=1e-5, atol=1e-6)
