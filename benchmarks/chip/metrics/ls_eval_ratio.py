"""Lane evaluations the workers' line search made per trial its lanes
asked for: the sum over the window's rounds of the evaluations its passes
made, every slot of every pass counted (``RoundMetrics.ls_slots``), over
the sum over those rounds and the lanes of their trials
(``RoundMetrics.ls_trials``).  1 where each pass evaluates only lanes
that are searching; a pass over all W lanes for one that is counts W."""


def read(run):
    slots = trials = 0
    for m in run.window.rounds:
        used = getattr(m, "ls_slots", None)
        ls = getattr(m, "ls_trials", None)
        if used is None or ls is None:
            return None
        slots += int(used)
        trials += sum(int(t) for t in ls)
    return slots / trials if trials else None
