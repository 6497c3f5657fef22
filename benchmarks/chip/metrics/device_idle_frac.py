"""Share of the window in which no operation ran on the device: one less
the union of the device's operation intervals over the window's length,
from the profiler trace.  While the host runs ``Scheduler.step``'s
per-worker pass and waits on its reads, this is what it costs."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
