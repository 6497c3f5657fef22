"""The correctness check fails what it has to fail: its control (the
reference in bfloat16 put in the program's place) and a run whose timed
path is broken underneath, for each fault a one-chip cell can have, for
worker solves that stop short of the paper's stopping rule, and for a
misreported dual residual; it passes the float32 rounding of z late in a
job.  At a tiny size on the CPU; the harness's look for a chip is
skipped, the rest of the run is the benchmark's own."""
import dataclasses

import jax.numpy as jnp
import numpy as np
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


def test_fault_dual_residual_misreported(monkeypatch):
    """The scheduler reports an s_norm a thousandth too large, its z and
    penalty sound."""
    real = Scheduler._master_z_update

    def off(self, *args, **kwargs):
        r_norm, s_norm = real(self, *args, **kwargs)
        return r_norm, s_norm * (1 + 1e-3)

    monkeypatch.setattr(Scheduler, "_master_z_update", off)
    res = run(tiny_cell())
    assert res["correct"] is False
    assert over(res) == ["s_gap"]


def test_f32_rounding_of_z_late_in_the_job_is_no_dual_gap():
    """A late round: z moved by a thousandth of its norm, and the
    program's z is the float64 master's rounded to float32, every
    coordinate up by 0.3-0.5 of an ulp.  Against the master's own dual
    residual the program's reads that rounding over ||dz||; against the
    residual of its own z's it reads its float32 arithmetic alone."""
    cell = tiny_cell()
    W, rho = 8, 1.7
    thr = cell.config["lam1"] / (W * rho)
    rng = np.random.default_rng(SEED)
    v = (rng.uniform(1.0, 1.1, 4_000) + thr).astype(np.float32)
    t = (1 - thr / v.astype(np.float64)) * v
    z = t.astype(np.float32)
    ulps = (z - t) / np.spacing(z)
    assert np.all((ulps > 0.3) & (ulps < 0.5))
    z_prev = (t * (1 - 1e-3)).astype(np.float32)
    assert np.linalg.norm(t - z_prev) / np.linalg.norm(t) == \
        pytest.approx(1e-3, rel=1e-3)
    zeros = np.zeros((W, z.size), np.float32)
    prev = reference.State(x=np.tile(z_prev, (W, 1)), u=zeros, z=z_prev,
                           r_norm=1.0, s_norm=1.0, rho=rho)
    s_norm = float(rho * jnp.linalg.norm(jnp.asarray(z) - z_prev)
                   * np.sqrt(W))
    cur = reference.State(x=np.tile(v, (W, 1)), u=zeros, z=z, r_norm=0.0,
                          s_norm=s_norm, rho=rho)
    ref = reference.master_step(cell.config, prev, cur.x)
    np.testing.assert_array_equal(ref.z, z)
    old = abs(s_norm - ref.s_norm) / ref.s_norm
    assert old > cell.limits["s_gap"]
    assert reference.dual_gap(prev, cur) < 1e-6


def test_judged_rounds_end_with_the_windows_last(run_seen):
    res, seen = run_seen(tiny_cell(), SEED)
    assert len(seen) == 1 + res["window_rounds"] > 3
    assert res["judged_rounds"][:3] == [1, 2, 3]
    assert res["judged_rounds"][-1] == seen[-1].k
    assert len(res["judged_rounds"]) <= 4
    assert list(res)[-1] == "checks"
