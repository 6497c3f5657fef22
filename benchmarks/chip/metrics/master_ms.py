"""Device milliseconds per round of every program but the workers' batched
solve: the master's z-update and residuals, the omega table and the dual
bookkeeping that ``Scheduler`` runs op by op around the solve.  From the
profiler trace of the window."""


def read(run):
    t = run.trace
    if t is None or not run.window.rounds:
        return None
    other = sum(s for name, s in t.program_s.items()
                if name != t.solve_program)
    return 1e3 * other / len(run.window.rounds)
