"""Milliseconds per round the host spends in blocking device-to-host
reads other than the wait for the workers' solve: the program's
``*.wait`` spans but ``round.solve.wait`` (the reads of q, of the
master's residuals and of the new penalty), averaged over the window's
rounds.  From ``RoundMetrics.span_s``."""

WAIT = ".wait"
SOLVE_WAIT = "round.solve.wait"


def read(run):
    waits = []
    for m in run.window.rounds:
        spans = getattr(m, "span_s", None)
        if not spans or "round" not in spans:
            return None
        waits.append(sum(s for name, s in spans.items()
                         if name.endswith(WAIT) and name != SOLVE_WAIT))
    return 1e3 * sum(waits) / len(waits) if waits else None
