"""The correctness check fails what it has to fail: its control (the
reference in bfloat16 put in the program's place) and a run whose timed
path is broken underneath, for each fault a one-chip cell can have, and
for worker solves that stop short of the paper's stopping rule.  At a
tiny size on the CPU; the harness's look for a chip is skipped, the rest
of the run is the benchmark's own."""
import dataclasses

import jax.numpy as jnp
import pytest

import calibrate
import harness
import reference
from repro.problems.base import BatchedShardProblem
from repro.runtime.scheduler import Scheduler

SEED = 2**31 + 101


def tiny_cell(name="logreg-paper.w64") -> harness.Cell:
    cell = harness.load_cell(name)
    cfg = dict(cell.config, n_samples=2_000, n_features=400, density=0.02)
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, n_workers=8))


def run(cell):
    return harness.run(cell, SEED, 0.2, False, t_start=0.0,
                       require_chip=False)


def over(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


def test_sound_run_passes():
    res = run(tiny_cell())
    assert res["correct"] is True and over(res) == []


def test_control_in_bfloat16_fails():
    cell = tiny_cell()
    nums = calibrate.control_numbers(cell, SEED)
    failed = [k for k, v in nums.items() if not v <= cell.limits[k]]
    assert failed, nums


def test_sound_reference_in_float32_passes():
    cell = tiny_cell()
    W = cell.traffic["n_workers"]
    data = reference.generate(cell.config, W, SEED)
    states = reference.rounds(cell.config, W, data, 3)
    pairs = zip([reference.initial_state(cell.config, W)] + states[:-1],
                states)
    nums = reference.compare(cell.config, data, list(pairs))
    assert all(v <= cell.limits[k] for k, v in nums.items()), nums


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    real = Scheduler.step

    def frozen(self, on_round=None):
        if self.k == 0:
            return real(self, on_round)
        self.k += 1
        return self.history[-1]._replace(k=self.k), False

    monkeypatch.setattr(Scheduler, "step", frozen)
    res = run(tiny_cell())
    assert res["correct"] is False
    assert "x_gap" in over(res)


def test_fault_half_the_batch_left_out(monkeypatch):
    real = Scheduler._master_z_update

    def half(self, omega_bar, q_sum, n_eff, adapt_rho=True):
        rest = jnp.mean(self.omega_table[: n_eff // 2], axis=0)
        return real(self, rest, q_sum, n_eff, adapt_rho)

    monkeypatch.setattr(Scheduler, "_master_z_update", half)
    res = run(tiny_cell())
    assert res["correct"] is False
    assert "z_gap" in over(res)


@pytest.mark.parametrize("lanes", [slice(None), slice(5, 6)],
                         ids=["every-lane", "one-lane"])
def test_fault_answer_altered_where_produced(monkeypatch, lanes):
    real = BatchedShardProblem.solve_all

    def altered(self, *args, **kwargs):
        xs, iters = real(self, *args, **kwargs)
        return xs.at[lanes].multiply(1.01), iters

    monkeypatch.setattr(BatchedShardProblem, "solve_all", altered)
    res = run(tiny_cell())
    assert res["correct"] is False
    assert over(res) == ["x_gap"]


def early_stop(fixed_inner=None, eps_grad_times=1.0):
    """``solve_all`` whose FISTA stops by another rule than the
    configuration's: after ``fixed_inner`` iterations, or at an eps_grad
    ``eps_grad_times`` as loose.  It reports the iterations it made."""
    real = BatchedShardProblem.solve_all

    def solve_all(self, *args, **kwargs):
        if not getattr(self, "_planted", False):
            self._planted = True
            self.fixed_inner = fixed_inner
            self.fista = dataclasses.replace(
                self.fista, eps_grad=self.fista.eps_grad * eps_grad_times)
            self._batched_solver_cache = None
        return real(self, *args, **kwargs)

    return solve_all


@pytest.mark.parametrize("fault", [dict(fixed_inner=1),
                                   dict(eps_grad_times=10.0)],
                         ids=["one-iteration", "eps_grad-x10"])
def test_fault_solve_stops_early(monkeypatch, fault):
    monkeypatch.setattr(BatchedShardProblem, "solve_all", early_stop(**fault))
    res = run(tiny_cell())
    assert res["correct"] is False
    assert over(res) == ["iter_gap"]
