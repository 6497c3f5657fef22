"""The program's own spans and counters: the host spans of one scheduler
round (``repro.runtime.spans``, ``Scheduler.step``) as a profiler trace
shows them and as ``RoundMetrics.span_s`` holds them; FISTA's count of
line-search trials; and a profiler session leaving the arithmetic alone."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build
from repro.core.fista import FistaOptions, fista, fista_fixed
from repro.runtime import SchedulerConfig, spans

REPO = Path(__file__).resolve().parents[1]

# the spans of one batched synchronous round, each with its parent
PARENT = {
    "round": None,
    "round.respawn": "round",
    "round.solve": "round",
    "round.q.wait": "round.solve",
    "round.solve.wait": "round.solve",
    "round.timing": "round",
    "round.commit": "round",
    "round.fanin": "round",
    "round.master": "round",
    "round.master.wait": "round.master",
    "round.rho.wait": "round.master",
    "round.bill": "round",
}


def trace_reduce():
    """The benchmark's trace reader, loaded from its file."""
    path = REPO / "benchmarks" / "chip" / "trace_reduce.py"
    spec = importlib.util.spec_from_file_location("chip_trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # its dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


def tiny_spec(engine="batched"):
    return ExperimentSpec(
        problem="logreg",
        problem_kwargs=dict(n_samples=400, n_features=30, density=0.1),
        scheduler=SchedulerConfig(n_workers=4, engine=engine),
        max_rounds=10)


def test_round_spans_in_the_trace_and_in_span_s(tmp_path):
    tr = trace_reduce()
    _, warm = build(tiny_spec())
    warm.step()                        # compiles outside the trace
    rounds = 3
    with jax.profiler.trace(str(tmp_path)):
        _, sched = build(tiny_spec())
        ms = [sched.step()[0] for _ in range(rounds)]
    events = tr.read_events(tr.find_xplane(str(tmp_path)))
    mine = {name for name in PARENT} | {"build"}
    threads = {thread: [e for e in evs if e[0] in mine]
               for thread, evs in events.host.items()}
    threads = {t: evs for t, evs in threads.items() if evs}
    assert len(threads) == 1           # all on the thread that stepped
    evs = next(iter(threads.values()))
    assert [e[0] for e in evs].count("build") == 1
    by_name = {name: [e for e in evs if e[0] == name] for name in PARENT}
    for name, parent in PARENT.items():
        assert len(by_name[name]) == rounds, name
        if parent is None:
            continue
        for _, s, e in by_name[name]:
            assert any(ps <= s and e <= pe for _, ps, pe in by_name[parent])
    for m in ms:
        assert set(m.span_s) == set(PARENT)
        assert sched.history[m.k - 1] is m
        for parent in PARENT:
            kids = [m.span_s[n] for n, p in PARENT.items() if p == parent]
            assert sum(kids) <= m.span_s[parent]
        assert m.ls_trials.shape == (4,)
        assert np.all(m.ls_trials >= m.inner_iters)
        assert m.tol_iters.shape == (4,)
        assert np.all(m.tol_iters <= m.inner_iters)
        # every trial is one lane evaluation of a pass
        assert isinstance(m.ls_slots, int)
        assert m.ls_slots >= int(m.ls_trials.sum())


def test_replicated_counters_cover_every_replica():
    """In replicated mode the r replicas of a logical worker each carry
    its solve's trials and its line-search evaluations, so the counters
    of W = 8, r = 2 are those of the 4 logical workers' synchronous round
    taken twice."""
    _, sync = build(tiny_spec())
    _, repl = build(ExperimentSpec(
        problem="logreg",
        problem_kwargs=dict(n_samples=400, n_features=30, density=0.1),
        scheduler=SchedulerConfig(n_workers=8, engine="batched",
                                  mode="replicated", replication=2),
        max_rounds=10))
    one, _ = sync.step()
    two, _ = repl.step()
    assert np.array_equal(two.ls_trials, np.repeat(one.ls_trials, 2))
    assert two.ls_slots == 2 * one.ls_slots
    assert two.ls_slots >= int(two.ls_trials.sum())


def test_rounds_outside_step_carry_no_tables():
    _, sched = build(tiny_spec())
    m = sched.run_round()
    assert m.span_s is None and m.ls_trials is None and m.tol_iters is None
    assert m.ls_slots is None
    assert spans.current() is None
    _, loop = build(tiny_spec(engine="loop"))
    m, _ = loop.step()
    assert m.ls_trials is None and m.tol_iters is None and m.ls_slots is None
    assert "round.solve" in m.span_s


@pytest.mark.parametrize("fixed, min_iters, k",
                         [(None, 1, 2), (None, 5, 5), (6, 1, 6)],
                         ids=["adaptive", "min-iters-5", "fixed-6"])
def test_line_search_trials_counted(fixed, min_iters, k):
    """F(x) = 4||x||^2 has Lipschitz constant 8: from l0=1 with eta=2 the
    first iteration tries 1, 2, 4, 8; every later one accepts 8 at once."""
    def vg(x):
        return 4.0 * jnp.vdot(x, x), 8.0 * x

    opts = FistaOptions(l0=1.0, eta=2.0, min_iters=min_iters)
    x0 = jnp.array([1.0, -2.0, 0.5])
    _, st = (fista(vg, x0, opts) if fixed is None
             else fista_fixed(vg, x0, fixed, opts))
    assert int(st.k) == k
    assert int(st.n_ls) == 4 + (k - 1)


def test_profiler_session_changes_no_number(tmp_path):
    _, plain = build(tiny_spec())
    m0, _ = plain.step()
    _, traced = build(tiny_spec())
    with jax.profiler.trace(str(tmp_path)):
        m1, _ = traced.step()
    assert m0.r_norm == m1.r_norm and m0.s_norm == m1.s_norm
    assert np.array_equal(np.asarray(plain.x), np.asarray(traced.x))
    assert np.array_equal(np.asarray(plain.z), np.asarray(traced.z))
    assert np.array_equal(m0.ls_trials, m1.ls_trials)
