"""Share of the workers' FISTA iterations that only the K_w floor asked
for: the sum over the window's rounds and lanes of each lane's iterations
past the first one at which its tolerance held (``RoundMetrics.inner_iters``
less ``RoundMetrics.tol_iters``) over the sum of their iterations.  0 where
every lane stops at its tolerance (K_w = 1)."""


def read(run):
    floor = iters = 0
    for m in run.window.rounds:
        tol = getattr(m, "tol_iters", None)
        if tol is None:
            return None
        floor += sum(int(k) - int(t) for k, t in zip(m.inner_iters, tol))
        iters += sum(int(k) for k in m.inner_iters)
    return floor / iters if iters else None
