"""Share of the batched solve's lane-iterations that did useful work: the
sum of every lane's FISTA iterations over the window's rounds, against W
times the slowest lane's in each round.  The vmapped while-loop runs every
lane as long as its slowest one; the rest is masked.  Read from the
program's per-round counters (``RoundMetrics.inner_iters``)."""


def read(run):
    used = lanes = 0
    for m in run.window.rounds:
        it = [int(i) for i in m.inner_iters]
        used += sum(it)
        lanes += len(it) * max(it)
    return used / lanes if lanes else None
