"""Closed-loop autoscaler: policy decisions and the scheduler hook."""
import numpy as np
import pytest

from repro.configs.logreg_paper import scaled
from repro.core.admm import AdmmOptions
from repro.core.fista import FistaOptions
from repro.problems import LogRegProblem
from repro.runtime import (AutoscaleConfig, Autoscaler, PoolConfig,
                           ProviderConfig, Scheduler, SchedulerConfig)

CFG = scaled(2048, 128, density=0.05, lam1=0.3)
ADMM = AdmmOptions(max_iters=40)


@pytest.fixture(scope="module")
def problem():
    return LogRegProblem(CFG, fista=FistaOptions(min_iters=1, eps_grad=1e-3))


def feed(scaler, n, *, eff=0.5, queue=0.1):
    for _ in range(n):
        scaler.observe(round_wall_s=1.0, t_comp_mean=eff,
                       t_fanin_wait=queue)


# ---------------------------------------------------------------------------
# decide() unit tests
# ---------------------------------------------------------------------------


def test_target_efficiency_grows_when_compute_bound():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=3, window=3,
                                   max_workers=64))
    feed(s, 3, eff=0.9)
    assert s.decide(16) == 32


def test_target_efficiency_shrinks_when_idle_bound():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=3, window=3))
    feed(s, 3, eff=0.2)
    assert s.decide(16) == 8


def test_holds_inside_band():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=3, window=3))
    feed(s, 5, eff=0.6)
    assert s.decide(16) is None


def test_queue_depth_policy_directions():
    grow = Autoscaler(AutoscaleConfig(policy="queue_depth",
                                      cooldown_rounds=3, window=3,
                                      max_workers=128))
    feed(grow, 3, queue=0.01)
    assert grow.decide(32) == 64
    shrink = Autoscaler(AutoscaleConfig(policy="queue_depth",
                                        cooldown_rounds=3, window=3))
    feed(shrink, 3, queue=0.5)
    assert shrink.decide(32) == 16


def test_cooldown_blocks_early_decisions():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=5, window=3))
    feed(s, 4, eff=0.9)           # window full but cooldown not elapsed
    assert s.decide(16) is None
    feed(s, 1, eff=0.9)
    assert s.decide(16) == 32


def test_bounds_and_replication_quantum():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=3, window=3,
                                   min_workers=4, max_workers=24),
                   quantum=3)
    feed(s, 3, eff=0.9)
    assert s.decide(12) == 24                   # capped, 3 | 24
    s2 = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                    cooldown_rounds=3, window=3,
                                    min_workers=4), quantum=3)
    feed(s2, 3, eff=0.1)
    assert s2.decide(12) == 6                   # 12//2=6, 3 | 6
    s3 = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                    cooldown_rounds=3, window=3,
                                    min_workers=8))
    feed(s3, 3, eff=0.1)
    assert s3.decide(8) is None                 # already at the floor


def test_quantized_floor_never_undercuts_min_workers():
    """min_workers=4 with quantum=3: the effective floor is 6 (the next
    quantum multiple), so a shrink from 6 holds rather than proposing 3."""
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=3, window=3,
                                   min_workers=4), quantum=3)
    feed(s, 3, eff=0.1)
    assert s.decide(6) is None
    s2 = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                    cooldown_rounds=3, window=3,
                                    min_workers=4), quantum=3)
    feed(s2, 3, eff=0.1)
    assert s2.decide(12) == 6                   # shrink stops at the floor


def test_antiflap_damps_reversal():
    cfg = AutoscaleConfig(policy="target_efficiency", cooldown_rounds=2,
                          window=2, max_workers=64)
    s = Autoscaler(cfg)
    feed(s, 2, eff=0.9)
    assert s.decide(16) == 32
    feed(s, 2, eff=0.2)                 # immediate regret: wants 16 back
    assert s.decide(32) is None         # vetoed: < 2x cooldown
    feed(s, 2, eff=0.2)
    assert s.decide(32) == 16           # allowed after the longer wait


def test_decisions_log_and_window_reset():
    s = Autoscaler(AutoscaleConfig(policy="target_efficiency",
                                   cooldown_rounds=2, window=2,
                                   max_workers=64))
    feed(s, 2, eff=0.9)
    s.decide(16)
    assert len(s.decisions) == 1
    assert s.decide(32) is None         # window cleared by the resize


def test_bad_policy_rejected():
    with pytest.raises(ValueError, match="policy"):
        AutoscaleConfig(policy="chaos")


# ---------------------------------------------------------------------------
# the scheduler hook
# ---------------------------------------------------------------------------


def test_autoscaler_shrinks_oversized_fleet_and_converges(problem):
    """W=16 on a tiny instance runs at ~0.72 efficiency vs ~0.85 at W=8:
    a 75%-utilization target makes the controller shrink it, and the run
    must keep converging through the resize."""
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=16, admm=ADMM,
        autoscale=AutoscaleConfig(policy="target_efficiency",
                                  min_workers=4, max_workers=16,
                                  cooldown_rounds=4, window=3,
                                  eff_low=0.75, eff_high=0.95),
        pool=PoolConfig(seed=0, provider=ProviderConfig(enabled=True))))
    sched.solve(max_rounds=40)
    assert sched.autoscaler is not None
    assert len(sched.autoscaler.decisions) >= 1
    assert all(4 <= w <= 16
               for _, _, w, _ in sched.autoscaler.decisions)
    assert sched.cfg.n_workers < 16                  # it did shrink
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 5
    # metrics track the varying fleet size
    sizes = {m.n_workers for m in sched.history}
    assert 16 in sizes and sched.cfg.n_workers in sizes


def test_autoscale_off_never_rescales(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=1)))
    sched.solve(max_rounds=10)
    assert sched.autoscaler is None
    assert sched.cfg.n_workers == 8


def test_cost_meter_accrues_monotonically(problem):
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=2)))
    sched.solve(max_rounds=8)
    costs = [m.cost_usd for m in sched.history]
    assert costs[0] > 0.0
    assert all(b >= a for a, b in zip(costs, costs[1:]))
    assert sched.meter.total_usd() == pytest.approx(costs[-1])
    assert sched.meter.requests == sched.pool.total_spawns


def test_master_billed_continuously_across_rescale(problem):
    """The coordinator is billed from t=0 through init ramps, rounds, AND
    rescale stalls: master_seconds must track sim_time exactly."""
    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, admm=ADMM, pool=PoolConfig(seed=3)))
    for _ in range(3):
        sched.run_round()
    sched.rescale(4)
    sched.run_round()
    assert sched.meter.master_seconds == pytest.approx(sched.sim_time)


def test_respawn_init_not_billed_by_default(problem):
    """Lambda's rule: init time is unbilled unless bill_cold_init — the
    flag's delta must be exactly the summed start latencies, with the
    respawn-heavy run's round billing carved accordingly."""
    from repro.runtime.billing import BillingConfig
    runs = {}
    for flag in (False, True):
        sched = Scheduler(problem, SchedulerConfig(
            n_workers=8, admm=ADMM,
            billing=BillingConfig(bill_cold_init=flag),
            pool=PoolConfig(seed=4, lifetime_s=30.0)))
        sched.solve(max_rounds=6)
        runs[flag] = sched
    assert runs[True].n_respawns > 0            # the respawn path ran
    init_s = sum(s for s, _ in runs[True].pool.spawn_log)
    mem = runs[True].cfg.billing.mem_gb
    delta = runs[True].meter.gb_seconds - runs[False].meter.gb_seconds
    assert delta == pytest.approx(mem * init_s)


def test_async_respawn_init_not_billed_by_default(problem):
    """Same contract on the async path: launch() carves respawn init out
    of the invocation span, so the flag's delta is exactly mem*init."""
    from repro.runtime.billing import BillingConfig
    runs = {}
    for flag in (False, True):
        sched = Scheduler(problem, SchedulerConfig(
            n_workers=8, mode="async_", async_batch=4, staleness_bound=4,
            admm=ADMM, billing=BillingConfig(bill_cold_init=flag),
            pool=PoolConfig(seed=4, lifetime_s=4.0),
            respawn_before_deadline_s=1.0))
        sched.solve(max_rounds=24)
        runs[flag] = sched
    assert runs[True].n_respawns > 0
    init_s = sum(s for s, _ in runs[True].pool.spawn_log)
    mem = runs[True].cfg.billing.mem_gb
    delta = runs[True].meter.gb_seconds - runs[False].meter.gb_seconds
    assert delta == pytest.approx(mem * init_s)
