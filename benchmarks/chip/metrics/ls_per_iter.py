"""Line-search trials per FISTA iteration of the workers' solve: the sum
over the window's rounds and lanes of the trials each lane made
(``RoundMetrics.ls_trials``) over the sum of their iterations
(``RoundMetrics.inner_iters``).  Each iteration makes one trial at
least; every trial is one pass of the loss over the lane's shard."""


def read(run):
    trials = iters = 0
    for m in run.window.rounds:
        ls = getattr(m, "ls_trials", None)
        if ls is None:
            return None
        trials += sum(int(t) for t in ls)
        iters += sum(int(i) for i in m.inner_iters)
    return trials / iters if iters else None
