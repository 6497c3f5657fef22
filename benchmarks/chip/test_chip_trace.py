"""The reduction from a profiler trace to numbers: on made-up events with
hand-computed answers, and on a small trace recorded on one TPU v5e
(``testdata/tiny.xplane.pb.gz``: a 4-worker job at N=2,048, d=256, run
by ``harness.run`` with its trace on, 0.2 s of window)."""
from pathlib import Path

import pytest

import harness
import trace_reduce as tr

RECORDED = (Path(__file__).resolve().parent / "testdata"
            / "tiny.xplane.pb.gz")
MS = 1_000_000


def events():
    """A 100 ms window: the solve program 0-40 ms (a while op holding two
    fusions), a master program 60-70 ms, an op after the window."""
    host = {"/host:CPU/python3": [("bench.window", 0, 100 * MS),
                                  ("Scheduler.step", 0, 80 * MS),
                                  ("block_until_ready", 40 * MS, 45 * MS),
                                  ("Scheduler.step", 85 * MS, 100 * MS)],
            "/host:CPU/pjrt": [("runtime", 0, 100 * MS)]}
    ops = {"/device:TPU:0": [
        ("%while.1 = (f32[4,8]{1,0}, s32[]) while(%t)", 0, 40 * MS),
        ("%fusion.2 = f32[32]{0} fusion(f32[4,8]{1,0} %a), kind=kLoop",
         5 * MS, 14 * MS),
        ("%scatter.3 = f32[4,8]{1,0} scatter(f32[4,8]{1,0} %b)",
         20 * MS, 33 * MS),
        ("%reduce = f32[8]{0} reduce(f32[4,8]{1,0} %c)", 60 * MS, 70 * MS),
        ("%late = f32[8]{0} copy(f32[8]{0} %d)", 120 * MS, 130 * MS)]}
    modules = {"/device:TPU:0": [("jit_run_all(77)", 0, 40 * MS),
                                 ("jit__mean(5)", 60 * MS, 70 * MS),
                                 ("jit__mean(5)", 120 * MS, 130 * MS)]}
    return tr.Events(host=host, ops=ops, modules=modules)


def test_reduce_by_hand():
    s = tr.reduce(events(), "bench.window")
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.050)
    assert s.program_s == pytest.approx({"jit_run_all": 0.040,
                                         "jit__mean": 0.010})
    assert s.top_ops == [("while.1 f32[4,8] while", pytest.approx(0.018)),
                         ("scatter.3 f32[4,8] scatter", pytest.approx(0.013)),
                         ("reduce f32[8] reduce", pytest.approx(0.010)),
                         ("fusion.2 f32[32] fusion", pytest.approx(0.009))]
    # gaps: 40-60 ms under Scheduler.step (50 ms, the block ended at 45),
    # 70-100 ms (middle 85 ms: the second step)
    assert s.idle_gaps == [("Scheduler.step", pytest.approx(0.030)),
                           ("Scheduler.step", pytest.approx(0.020))]
    bd = s.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= tr.TOP and len(bd["idle_gaps"]) <= tr.TOP


def test_reduce_refuses_what_it_cannot_find():
    with pytest.raises(tr.TraceError, match="jit_other"):
        tr.reduce(events(), "bench.window", solve_program="jit_other")
    with pytest.raises(tr.TraceError, match="host span"):
        tr.reduce(events(), "no.such.span")
    empty = events()
    empty.ops = {"/device:TPU:0": []}
    with pytest.raises(tr.TraceError, match="no device operation"):
        tr.reduce(empty, "bench.window")


def test_interval_helpers():
    assert tr.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    assert tr.gaps([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]
    assert tr.self_times([("w", 0, 10), ("a", 1, 3), ("b", 3, 5),
                          ("d", 20, 25)]) == {"w": 6, "a": 2, "b": 2, "d": 5}
    assert tr.innermost([("a", 0, 10), ("b", 2, 4)], 3) == "b"
    assert tr.innermost([("a", 0, 10)], 11) == "host"
    assert tr.program_name("jit_run_all(13337224793294073643)") == \
        "jit_run_all"


def test_recorded_chip_trace():
    s = tr.summarize(str(RECORDED), harness.WINDOW_SPAN)
    assert 0 < s.busy_s <= s.window_s
    assert s.program_s[tr.SOLVE_PROGRAM] > 0
    assert s.program_s[tr.SOLVE_PROGRAM] <= s.busy_s * (1 + 1e-9)
    assert 0 < len(s.top_ops) <= tr.TOP
    assert all(t > 0 for _, t in s.top_ops)
    assert [t for _, t in s.top_ops] == sorted((t for _, t in s.top_ops),
                                               reverse=True)
    assert 0 < len(s.idle_gaps) <= tr.TOP
    assert sum(t for _, t in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
    assert all(isinstance(name, str) and name for name, _ in s.idle_gaps)
