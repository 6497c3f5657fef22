"""Hierarchical compressed fan-in (runtime.reduce + scheduler wiring)."""
import numpy as np
import pytest

from repro.configs.logreg_paper import scaled
from repro.core.admm import AdmmOptions
from repro.core.fista import FistaOptions
from repro.optim import compression as C
from repro.problems import LogRegProblem
from repro.runtime import PoolConfig, Scheduler, SchedulerConfig, TreeConfig
from repro.runtime.pool import LambdaPool, master_drain
from repro.runtime.reduce import (flat_equivalent, root_ingest_count,
                                  tree_drain, tree_shape)

CFG = scaled(2048, 128, density=0.05, lam1=0.3)
ADMM = AdmmOptions(max_iters=30)


@pytest.fixture(scope="module")
def problem():
    return LogRegProblem(CFG, fista=FistaOptions(min_iters=1, eps_grad=1e-3))


# -- drain-kernel properties -------------------------------------------------


def test_flat_tree_reproduces_master_drain_exactly():
    """The degenerate single-level tree IS the flat master."""
    pc = PoolConfig()
    rng = np.random.RandomState(0)
    for W in (4, 16, 64, 256):
        arrivals = [(float(t), i) for i, t in enumerate(rng.rand(W) * 3)]
        n_masters = -(-W // pc.workers_per_master)
        flat = master_drain(arrivals, n_masters, pc.t_master_proc_s,
                            pc.t_ingest_s)
        leaf, root = tree_drain(arrivals, flat_equivalent(pc, W), hop_s=0.0)
        assert leaf == flat
        assert root == max(flat.values())


def test_tree_shape_and_root_load():
    assert tree_shape(256, 16) == [16, 1]
    assert tree_shape(1024, 16) == [64, 4, 1]
    assert tree_shape(8, 16) == [1]
    # root serial ingest stops scaling with W
    assert root_ingest_count(256, 16) == 16
    assert root_ingest_count(1024, 16) == 4
    assert root_ingest_count(8, 16) == 8


def test_tree_depth_reduces_root_ingest_time():
    """256 simultaneous arrivals: the flat router serializes all of them;
    the tree's root only sees fanout-many combined messages."""
    pc = PoolConfig()
    arrivals = [(0.0, i) for i in range(256)]
    flat = max(master_drain(arrivals, 16, pc.t_master_proc_s,
                            pc.t_ingest_s).values())
    _, tree = tree_drain(arrivals, TreeConfig(fanout=16), hop_s=0.005)
    assert tree < flat / 3


def test_degenerate_fanout_rejected():
    with pytest.raises(ValueError):
        TreeConfig(fanout=1)
    with pytest.raises(ValueError):
        tree_shape(16, 1)


def test_tree_drain_empty_and_single():
    leaf, root = tree_drain([], TreeConfig(), hop_s=0.1)
    assert leaf == {} and root == 0.0
    from repro.runtime.reduce import DEFAULT_T_INGEST_S, DEFAULT_T_PROC_S
    leaf, root = tree_drain([(1.0, 7)], TreeConfig(), hop_s=0.1)
    assert set(leaf) == {7} and root == pytest.approx(
        1.0 + DEFAULT_T_INGEST_S + DEFAULT_T_PROC_S)
    # explicit combiner costs are honored
    _, fast = tree_drain([(1.0, 7)], TreeConfig(t_ingest_s=1e-4,
                                                t_proc_s=1e-4), hop_s=0.1)
    assert fast == pytest.approx(1.0 + 2e-4)


# -- scheduler wiring --------------------------------------------------------


def test_scheduler_degenerate_tree_matches_flat(problem):
    """fanin='tree' with a one-node tree sized like the flat master gives
    bit-identical math AND identical round timings."""
    W = 8
    pc = PoolConfig(seed=0)
    n_masters = -(-W // pc.workers_per_master)
    flat = Scheduler(problem, SchedulerConfig(
        n_workers=W, admm=ADMM, pool=pc))
    tree = Scheduler(problem, SchedulerConfig(
        n_workers=W, admm=ADMM, pool=pc, fanin="tree",
        tree=TreeConfig(fanout=W, node_masters=n_masters)))
    z1 = flat.solve(max_rounds=10)
    z2 = tree.solve(max_rounds=10)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    t1 = [m.sim_time for m in flat.history]
    t2 = [m.sim_time for m in tree.history]
    np.testing.assert_allclose(t1, t2)


def test_tree_fanin_same_math_faster_fanin(problem):
    """The fan-in path changes TIME, never math: z trajectories match."""
    W = 8
    mk = lambda fanin: Scheduler(problem, SchedulerConfig(
        n_workers=W, admm=ADMM, pool=PoolConfig(seed=0), fanin=fanin,
        tree=TreeConfig(fanout=4)))
    s_flat, s_tree = mk("flat"), mk("tree")
    z1 = s_flat.solve(max_rounds=10)
    z2 = s_tree.solve(max_rounds=10)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_compressed_admm_still_converges(problem):
    """The lossy codec path on the paper's problem family: residual drops
    and the objective lands within tolerance of the dense run."""
    W, rounds = 8, 30
    objs = {}
    for method in ("none", "topk", "qsgd"):
        s = Scheduler(problem, SchedulerConfig(
            n_workers=W, admm=ADMM, pool=PoolConfig(seed=0),
            fanin="tree", compress=method, topk_frac=0.05))
        z = s.solve(max_rounds=rounds)
        assert s.history[-1].r_norm < s.history[1].r_norm / 1.5, method
        objs[method] = problem.objective(z, W)
    assert objs["topk"] <= objs["none"] * 1.02
    assert objs["qsgd"] <= objs["none"] * 1.02


def test_replicated_composes_with_tree_and_compression(problem):
    """FRS replication under the tree with compressed ω still matches the
    unreplicated run EXACTLY (replicas share a codec slot, round-robin
    dealing spreads them over combiners, first responder wins)."""
    base = Scheduler(problem, SchedulerConfig(
        n_workers=4, admm=ADMM, pool=PoolConfig(seed=1),
        fanin="tree", compress="topk"))
    z1 = base.solve(max_rounds=12)
    repl = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="replicated", replication=2, admm=ADMM,
        fanin="tree", compress="topk",
        pool=PoolConfig(seed=7, straggler_frac=0.4, straggler_slowdown=6.0)))
    z2 = repl.solve(max_rounds=12)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_dropped_messages_roll_back_codec_state(problem):
    """Partial barrier + compression: a dropped message must not advance
    the master's synchronized view (its content rides a later delta
    instead of being smuggled in for free); convergence still holds."""
    import jax.numpy as jnp
    codec = C.OmegaCodec("topk", 16, topk_frac=0.25)
    snap = codec.snapshot()
    v = codec.encode(0, jnp.arange(16, dtype=jnp.float32))
    assert float(jnp.abs(v).sum()) > 0
    codec.rollback_except(snap, delivered=set())      # master saw nothing
    v2 = codec.encode(0, jnp.arange(16, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))

    sched = Scheduler(problem, SchedulerConfig(
        n_workers=8, mode="drop_slowest", drop_frac=0.25, admm=ADMM,
        compress="topk", fanin="tree",
        pool=PoolConfig(seed=2, straggler_frac=0.2)))
    z = sched.solve(max_rounds=30)
    assert sched.history[-1].r_norm < sched.history[1].r_norm / 1.5
    assert problem.objective(z, 8) < 0.8 * problem.objective(z * 0, 8)


def test_compression_shrinks_wire_bytes(problem):
    dense = Scheduler(problem, SchedulerConfig(n_workers=4, admm=ADMM))
    topk = Scheduler(problem, SchedulerConfig(n_workers=4, admm=ADMM,
                                              compress="topk"))
    qsgd = Scheduler(problem, SchedulerConfig(n_workers=4, admm=ADMM,
                                              compress="qsgd"))
    assert topk.msg_bytes < dense.msg_bytes / 5
    assert qsgd.msg_bytes < dense.msg_bytes / 5
    # wire_d override: paper-scale messages from a reduced instance
    paper = Scheduler(problem, SchedulerConfig(n_workers=4, admm=ADMM,
                                               wire_d=10_000))
    assert paper.msg_bytes == C.message_bytes("none", 10_000)


def test_msg_cost_scales_with_bytes():
    pool = LambdaPool(PoolConfig())
    ref = pool.cfg.ref_msg_bytes
    # calibration anchor: the paper's dense message costs the constant
    assert pool.msg_cost(0.008, ref) == pytest.approx(0.008)
    # compressed messages ingest cheaper, but never below the fixed floor
    small = pool.msg_cost(0.008, 100)
    assert small < 0.008 / 2
    assert small > 0.008 * pool.cfg.ingest_frac_fixed


def test_qsgd_roundtrip_error_bounded():
    rng = np.random.RandomState(0)
    x = rng.randn(512).astype(np.float32)
    for bits in (2, 4, 8):
        levels, scale = C.qsgd_compress(x, bits)
        xh = np.asarray(C.qsgd_decompress(levels, scale, bits))
        s = (1 << (bits - 1)) - 1
        # nearest-level rounding: per-coordinate error <= scale/(2s)
        assert np.max(np.abs(xh - x)) <= float(scale) / (2 * s) + 1e-6
