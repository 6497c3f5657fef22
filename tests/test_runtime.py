"""Serverless runtime: scheduler modes, elasticity, faults, timing model."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.logreg_paper import scaled
from repro.core.admm import AdmmOptions
from repro.core.fista import FistaOptions
from repro.problems import LogRegProblem
from repro.runtime import PoolConfig, Scheduler, SchedulerConfig
from repro.runtime.pool import LambdaPool, master_drain

CFG = scaled(2048, 128, density=0.05, lam1=0.3)
ADMM = AdmmOptions(max_iters=40)


@pytest.fixture(scope="module")
def problem():
    return LogRegProblem(CFG, fista=FistaOptions(min_iters=1, eps_grad=1e-3))


def _residual(sched):
    return sched.history[-1].r_norm


def test_sync_converges(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=0)))
    sched.solve(max_rounds=40)
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 20


def test_replicated_exactly_matches_sync(problem):
    s1 = Scheduler(problem, SchedulerConfig(
        n_workers=4, admm=ADMM, pool=PoolConfig(seed=1)))
    z1 = s1.solve(max_rounds=15)
    s2 = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="replicated", replication=2, admm=ADMM,
        pool=PoolConfig(seed=7, straggler_frac=0.4, straggler_slowdown=6.0)))
    z2 = s2.solve(max_rounds=15)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_drop_slowest_still_converges(problem):
    """Partial barrier trades residual floor for round time — consistent
    with the paper's warning that dropping stragglers costs accuracy for
    generic optimization (§V-A); the stale-cache mean still makes steady
    progress on the objective."""
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="drop_slowest", drop_frac=0.25, admm=ADMM,
        pool=PoolConfig(seed=2, straggler_frac=0.2)))
    z = sched.solve(max_rounds=40)
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 1.5
    assert problem.objective(z, 8) < 0.8 * problem.objective(z * 0, 8)


def test_async_converges(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="async_", async_batch=4, staleness_bound=4,
        admm=ADMM, pool=PoolConfig(seed=3)))
    z = sched.solve(max_rounds=60)
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 3
    assert problem.objective(z, 8) < 0.8 * problem.objective(z * 0, 8)


def test_failures_and_lifetimes_respawn(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM,
        pool=PoolConfig(seed=4, fail_rate_per_round=0.05, lifetime_s=30.0)))
    sched.solve(max_rounds=30)
    assert sched.n_respawns > 0
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 5


def test_elastic_rescale_continues_converging(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=4, admm=ADMM, pool=PoolConfig(seed=5)))
    for _ in range(5):
        sched.run_round()
    sched.rescale(8)
    assert sched.x.shape[0] == 8
    sched.solve(max_rounds=30)
    assert sched.history[-1].r_norm < sched.history[4].r_norm


def test_elastic_shrink_rescale(problem):
    """The shrink direction (W=8 -> 4): shard remap, retired slots gone,
    respawn accounting, and continued convergence."""
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=7)))
    for _ in range(5):
        sched.run_round()
    spawns_before = sched.pool.total_spawns
    r_before = sched.history[-1].r_norm
    sched.rescale(4)
    # state remapped to the 4 surviving shards
    assert sched.x.shape[0] == 4
    assert sched.u.shape[0] == 4
    assert sched.omega_table.shape[0] == 4
    assert sched.n_logical == 4
    # retired slots are really gone; survivors were respawned once each
    assert set(sched.pool.workers) == set(range(4))
    assert sched.pool.total_spawns == spawns_before + 4
    m = sched.run_round()
    assert m.t_comp.shape == (4,)
    assert m.n_workers == 4
    sched.solve(max_rounds=30)
    assert sched.history[-1].r_norm < r_before


def test_shrink_rescale_respects_replication_quantum(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="replicated", replication=2, admm=ADMM,
        pool=PoolConfig(seed=8)))
    sched.run_round()
    with pytest.raises(ValueError, match="r | W"):
        sched.rescale(5)
    sched.rescale(4)
    assert sched.n_logical == 2
    assert set(sched.pool.workers) == set(range(4))


def test_cold_start_bulk_queue_grows():
    """Fig 8: the slowest cold start grows with bulk size; the fastest
    stays flat."""
    pc = PoolConfig(seed=0)
    pool = LambdaPool(pc)
    w16 = pool.spawn_bulk(list(range(16)), 0.0)
    pool2 = LambdaPool(pc)
    w256 = pool2.spawn_bulk(list(range(256)), 0.0)
    slow16 = max(w.cold_start_s for w in w16)
    slow256 = max(w.cold_start_s for w in w256)
    fast16 = min(w.cold_start_s for w in w16)
    fast256 = min(w.cold_start_s for w in w256)
    assert slow256 > slow16 * 2
    assert abs(fast256 - fast16) < 2.0


def test_master_drain_queuing_cliff():
    """Fan-in queuing grows superlinearly past ~W-bar workers per master."""
    t_proc = 0.01
    # all messages arrive at once
    d64 = master_drain([(0.0, i) for i in range(64)], 4, t_proc)
    d256 = master_drain([(0.0, i) for i in range(256)], 16, t_proc)
    assert max(d256.values()) >= max(d64.values())


def test_metrics_shapes(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=6)))
    m = sched.run_round()
    assert m.t_comp.shape == (8,)
    assert m.t_idle.shape == (8,)
    assert np.all(m.t_comp > 0)
    assert m.slowest10.sum() >= 1
