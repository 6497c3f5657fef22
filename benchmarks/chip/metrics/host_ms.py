"""Host milliseconds per round that are the host's own work: the
program's ``round`` span less every ``*.wait`` span inside it (the
blocking device-to-host reads), averaged over the window's rounds.  From
the spans the scheduler times in each round (``RoundMetrics.span_s``)."""

WAIT = ".wait"


def read(run):
    own = []
    for m in run.window.rounds:
        spans = getattr(m, "span_s", None)
        if not spans or "round" not in spans:
            return None
        own.append(spans["round"] - sum(s for name, s in spans.items()
                                        if name.endswith(WAIT)))
    return 1e3 * sum(own) / len(own) if own else None
