"""The workers' batched solve against its roofline, in percent: the least
time of every useful loss+gradient pass of the window's rounds (each
lane's FISTA iterations times the least time of one pass over its shard
in sparse form, ``work.py``) over the solve program's device time in the
profiler trace.  Bytes decide the least time on every known chip
(``work.least_seconds``)."""

import work


def read(run):
    t = run.trace
    if t is None or run.peak is None or not run.window.rounds:
        return None
    least, _ = work.solve_least_seconds(
        run.cell.config, run.cell.traffic["n_workers"],
        [m.inner_iters for m in run.window.rounds], run.peak)
    return 100.0 * least / t.program_s[t.solve_program]
