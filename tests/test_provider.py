"""Provider model: keep-alive policies, capacity, throttle, billing.

The load-bearing test is the EQUIVALENCE ANCHOR: with the provider
disabled (the default) — and even enabled-but-empty — the spawn path
must be byte-identical to the seed cold-only model, so every calibrated
figure (fig8, fig4) reproduces exactly.
"""
import numpy as np
import pytest

from repro.runtime.billing import BillingConfig, BillingMeter
from repro.runtime.pool import LambdaPool, PoolConfig
from repro.runtime.provider import Provider, ProviderConfig

WARM = ProviderConfig(enabled=True)


# ---------------------------------------------------------------------------
# equivalence anchors (the PR-1 "flat equivalence" discipline)
# ---------------------------------------------------------------------------


def test_disabled_path_matches_enabled_empty_pool():
    """Provider off vs provider on with an empty warm pool: identical
    draws, identical workers (the provider uses its OWN RNG)."""
    off = LambdaPool(PoolConfig(seed=0))
    on = LambdaPool(PoolConfig(seed=0, provider=WARM))
    w_off = off.spawn_bulk(list(range(32)), at=0.0)
    w_on = on.spawn_bulk(list(range(32)), at=0.0)
    for a, b in zip(w_off, w_on):
        assert a.cold_start_s == b.cold_start_s
        assert a.speed == b.speed
        assert not b.warm_start


def test_fig8_cold_anchor_values():
    """The seed's Fig 8 numbers, pinned literally (RandomState contract
    makes them stable): a provider-era regression would move these."""
    pool = LambdaPool(PoolConfig(seed=0))
    cs = np.array([w.cold_start_s
                   for w in pool.spawn_bulk(list(range(4)), 0.0)])
    np.testing.assert_allclose(
        [cs.min(), cs.max()], [2.650035367010236, 3.14233128047215],
        rtol=1e-12)
    pool64 = LambdaPool(PoolConfig(seed=0))
    cs64 = np.array([w.cold_start_s
                     for w in pool64.spawn_bulk(list(range(64)), 0.0)])
    np.testing.assert_allclose(
        [cs64.min(), cs64.max()], [2.568303406920579, 4.849511516367219],
        rtol=1e-12)


# ---------------------------------------------------------------------------
# warm reuse
# ---------------------------------------------------------------------------


def test_retire_then_respawn_hits_warm_pool():
    pool = LambdaPool(PoolConfig(seed=1, provider=WARM))
    first = pool.spawn_bulk(list(range(4)), at=0.0)
    speeds = sorted(w.speed for w in first)
    pool.retire(list(range(4)), at=100.0)
    again = pool.spawn_bulk(list(range(4)), at=110.0)
    assert all(w.warm_start for w in again)
    assert all(w.cold_start_s < 1.0 for w in again)
    # sandbox speeds are sticky: the same four multipliers come back
    assert sorted(w.speed for w in again) == pytest.approx(speeds)
    st = pool.provider.stats
    assert st.warm_hits == 4 and st.cold_misses == 4


def test_replacement_spawn_reuses_own_sandbox():
    """spawn_bulk over a live slot releases its sandbox first — the
    respawn-at-lifetime path lands warm."""
    pool = LambdaPool(PoolConfig(seed=2, provider=WARM))
    pool.spawn_bulk([0], at=0.0)
    w = pool.spawn_bulk([0], at=50.0)[0]
    assert w.warm_start and w.generation == 1 and w.env_uses == 2


def test_crashed_sandbox_not_reused_warm():
    """Failure injection tears the sandbox down — only clean lifetime
    exits feed the keep-alive pool."""
    pool = LambdaPool(PoolConfig(seed=2, provider=WARM))
    pool.spawn_bulk([0], at=0.0)
    pool.crash(0)
    w = pool.spawn_bulk([0], at=50.0)[0]
    assert not w.warm_start
    assert pool.provider.stats.warm_hits == 0


def test_scheduler_failure_respawns_are_cold():
    from repro.configs.logreg_paper import scaled
    from repro.core.admm import AdmmOptions
    from repro.core.fista import FistaOptions
    from repro.runtime import Scheduler, SchedulerConfig
    from repro.problems import LogRegProblem
    prob = LogRegProblem(scaled(2048, 128, density=0.05, lam1=0.3),
                         fista=FistaOptions(min_iters=1, eps_grad=1e-3))
    sched = Scheduler(prob, SchedulerConfig(
        n_workers=4, admm=AdmmOptions(max_iters=6),
        pool=PoolConfig(seed=5, fail_rate_per_round=1.0, provider=WARM)))
    sched.solve(max_rounds=6)
    assert sched.n_respawns > 0
    assert sched.pool.warm_frac() == 0.0        # every respawn was a crash


def test_keepalive_ttl_expiry():
    prov = ProviderConfig(enabled=True, keepalive_s=60.0)
    pool = LambdaPool(PoolConfig(seed=3, provider=prov))
    pool.spawn_bulk([0], at=0.0)
    pool.retire([0], at=10.0)
    w = pool.spawn_bulk([0], at=10.0 + 61.0)[0]
    assert not w.warm_start
    assert pool.provider.stats.expirations == 1


def test_max_env_age_recycles_old_sandboxes():
    prov = ProviderConfig(enabled=True, max_env_age_s=100.0)
    pool = LambdaPool(PoolConfig(seed=3, provider=prov))
    pool.spawn_bulk([0], at=0.0)
    pool.retire([0], at=150.0)          # sandbox born at 0, too old
    assert pool.provider.idle == []
    assert not pool.spawn_bulk([0], at=151.0)[0].warm_start


# ---------------------------------------------------------------------------
# eviction policy zoo (driving Provider directly)
# ---------------------------------------------------------------------------


def _stock(prov):
    """Three sandboxes with distinct eviction-relevant histories."""
    prov.release(cid=0, created_at=0.0, uses=1, speed=1.0, at=10.0)
    prov.release(cid=1, created_at=0.0, uses=5, speed=1.0, at=20.0)
    prov.release(cid=2, created_at=0.0, uses=3, speed=1.0, at=30.0)


def _survivors(policy):
    cfg = ProviderConfig(enabled=True, policy=policy,
                         warm_capacity_mb=2 * 3008)   # room for two idle
    prov = Provider(cfg)
    _stock(prov)
    return {w.cid for w in prov.idle}


def test_fixed_ttl_evicts_oldest_idle():
    assert _survivors("fixed_ttl") == {1, 2}


def test_lru_evicts_least_recently_used():
    # last_used == released_at here, so LRU matches FIFO — differentiate
    # by re-touching cid 0 via acquire/release
    cfg = ProviderConfig(enabled=True, policy="lru",
                         warm_capacity_mb=3 * 3008)
    prov = Provider(cfg)
    _stock(prov)
    w = prov.acquire(at=40.0)           # LIFO: pops cid 2
    assert w.cid == 2
    prov.release(cid=2, created_at=0.0, uses=w.uses, speed=1.0, at=41.0)
    # pool full at 3; a fourth release evicts the LRU victim: cid 0
    prov.release(cid=3, created_at=0.0, uses=1, speed=1.0, at=42.0)
    assert {c.cid for c in prov.idle} == {1, 2, 3}


def test_least_used_evicts_min_use_count():
    assert _survivors("least_used") == {1, 2}   # cid 0 has uses=1


def test_greedy_dual_evicts_lowest_priority_and_inflates_clock():
    cfg = ProviderConfig(enabled=True, policy="greedy_dual",
                         warm_capacity_mb=2 * 3008)
    prov = Provider(cfg)
    _stock(prov)
    # priority ~ uses * saved/size at clock 0: cid 0 (uses=1) is lowest
    assert {w.cid for w in prov.idle} == {1, 2}
    assert prov.stats.evictions == 1
    assert prov._gd_clock > 0.0         # clock advanced to victim priority


def test_zero_capacity_pool_keeps_nothing():
    cfg = ProviderConfig(enabled=True, warm_capacity_mb=0)
    prov = Provider(cfg)
    assert not prov.release(cid=0, created_at=0.0, uses=1, speed=1.0,
                            at=1.0)
    assert prov.idle == []


def test_bad_policy_rejected():
    with pytest.raises(ValueError, match="policy"):
        ProviderConfig(policy="magic")


# ---------------------------------------------------------------------------
# cold-provision throttle (account burst limits)
# ---------------------------------------------------------------------------


def test_burst_throttle_delays_excess_cold_spawns():
    prov = ProviderConfig(enabled=True, burst_concurrency=2,
                          refill_per_s=1.0)
    pool = LambdaPool(PoolConfig(seed=0, provider=prov))
    ws = pool.spawn_bulk(list(range(4)), at=0.0)
    base = LambdaPool(PoolConfig(seed=0)).spawn_bulk(list(range(4)), at=0.0)
    extra = [w.cold_start_s - b.cold_start_s for w, b in zip(ws, base)]
    assert extra == pytest.approx([0.0, 0.0, 1.0, 2.0])
    assert pool.provider.stats.throttle_wait_s == pytest.approx(3.0)


def test_throttle_bucket_refills_over_time():
    prov = ProviderConfig(enabled=True, burst_concurrency=1,
                          refill_per_s=1.0)
    pool = LambdaPool(PoolConfig(seed=0, provider=prov))
    pool.spawn_bulk([0], at=0.0)                  # drains the bucket
    w = pool.spawn_bulk([1], at=10.0)[0]          # refilled by then
    assert pool.provider.stats.throttle_wait_s == 0.0
    assert w.cold_start_s < 4.0


# ---------------------------------------------------------------------------
# billing meter
# ---------------------------------------------------------------------------


def test_billing_meter_hand_math():
    cfg = BillingConfig(mem_gb=2.0, gb_second_usd=1e-5, per_request_usd=1e-6,
                        egress_usd_per_gb=0.01, master_usd_per_s=1e-4)
    m = BillingMeter(cfg)
    m.record_duration(100.0, n_workers=4)   # 800 GB-s
    m.record_requests(10)
    m.record_bytes(5e8)                     # 0.5 GB
    m.record_master(50.0)
    b = m.cost()
    assert b.compute_usd == pytest.approx(800 * 1e-5)
    assert b.request_usd == pytest.approx(10 * 1e-6)
    assert b.egress_usd == pytest.approx(0.5 * 0.01)
    assert b.master_usd == pytest.approx(50 * 1e-4)
    assert b.total_usd == pytest.approx(sum(b[:4]))
    assert m.summary()["gb_seconds"] == pytest.approx(800.0)


def test_bill_cold_init_flag():
    base = BillingMeter(BillingConfig())
    with_init = BillingMeter(BillingConfig(bill_cold_init=True))
    assert base.cfg.bill_cold_init is False
    assert with_init.cfg.bill_cold_init is True
