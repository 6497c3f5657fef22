"""Fixtures shared by the benchmark's CPU tests."""
import pytest

import harness


@pytest.fixture
def run_seen(monkeypatch):
    """``harness.run`` off the chip with a 0.2-s window, keeping every
    round it drove: ``run_seen(cell, seed)`` gives (result, rounds)."""
    seen = []
    real = harness.step

    def step(sched):
        m, done = real(sched)
        seen.append(m)
        return m, done

    monkeypatch.setattr(harness, "step", step)

    def run(cell, seed):
        seen.clear()
        res = harness.run(cell, seed, 0.2, False, t_start=0.0,
                          require_chip=False)
        return res, list(seen)

    return run
