"""FISTA local solver: oracle checks against closed forms and scipy."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fista import FistaOptions, fista, fista_fixed, fista_lanes


def quad_vg(A, b):
    def vg(x):
        r = A @ x - b
        return 0.5 * jnp.vdot(r, r), A.T @ r
    return vg


def test_quadratic_exact_solution(rng):
    A = jnp.asarray(rng.randn(20, 8), jnp.float32)
    b = jnp.asarray(rng.randn(20), jnp.float32)
    x_star = np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0]
    # f32 limits the achievable gradient norm (f-value-based stopping
    # saturates near machine eps; the f64 path is exercised by the paper-
    # scale benchmark) — 1e-3 is the f32-realistic target here
    x, info = fista(quad_vg(A, b), jnp.zeros(8),
                    FistaOptions(eps_grad=1e-3, max_iters=2000))
    np.testing.assert_allclose(x, x_star, atol=5e-3)


def test_monotone_with_backtracking(rng):
    A = jnp.asarray(rng.randn(30, 10) * 3, jnp.float32)
    b = jnp.asarray(rng.randn(30), jnp.float32)
    vg = quad_vg(A, b)
    # l0 far too small forces backtracking; monotone safeguard keeps descent
    f_prev = float(vg(jnp.zeros(10))[0])
    x = jnp.zeros(10)
    for n in (1, 2, 4, 8, 16):
        x_n, info = fista_fixed(vg, jnp.zeros(10), n, FistaOptions(l0=1e-3))
        f_n = float(vg(x_n)[0])
        assert f_n <= f_prev + 1e-5
        f_prev = f_n


def test_min_iters_honored(rng):
    A = jnp.asarray(rng.randn(5, 3), jnp.float32)
    b = jnp.asarray(rng.randn(5), jnp.float32)
    # start AT optimum: must still run min_iters (paper's K_w semantics)
    x_star = jnp.asarray(
        np.linalg.lstsq(np.asarray(A), np.asarray(b), rcond=None)[0],
        jnp.float32)
    _, info = fista(quad_vg(A, b), x_star, FistaOptions(min_iters=5))
    assert int(info.k) >= 5


def test_logistic_vs_scipy(rng):
    from scipy.optimize import minimize
    from repro.kernels.ref import logistic_value_and_grad
    A = jnp.asarray(rng.randn(64, 12), jnp.float32)
    b = jnp.asarray(np.sign(rng.randn(64)), jnp.float32)
    rho, center = 0.5, jnp.asarray(rng.randn(12) * 0.1, jnp.float32)
    vg = logistic_value_and_grad(A, b)

    def aug(x):
        f, g = vg(x)
        d = x - center
        return f + 0.5 * rho * jnp.vdot(d, d), g + rho * d

    x, _ = fista(aug, jnp.zeros(12), FistaOptions(eps_grad=1e-5,
                                                  max_iters=3000))
    ref = minimize(lambda xn: float(aug(jnp.asarray(xn, jnp.float32))[0]),
                   np.zeros(12), method="L-BFGS-B",
                   jac=lambda xn: np.asarray(
                       aug(jnp.asarray(xn, jnp.float32))[1], np.float64))
    assert float(aug(x)[0]) <= ref.fun * (1 + 1e-3) + 1e-3


# ---------------------------------------------------------------------------
# The iteration before F(x_{k+1}) was taken from the line search: the
# accepted point was evaluated a second time after ``_backtrack``.  Kept
# verbatim as the oracle that the reuse changes no number.
# ---------------------------------------------------------------------------

from typing import NamedTuple


class FistaState(NamedTuple):
    """The iteration's state before ``k_tol`` was counted."""
    x: jnp.ndarray
    y: jnp.ndarray
    t: jnp.ndarray
    lip: jnp.ndarray
    f_x: jnp.ndarray
    g_norm: jnp.ndarray
    rel_impr: jnp.ndarray
    k: jnp.ndarray
    n_ls: jnp.ndarray


def _oracle_backtrack(vg, y, f_y, g_y, lip, opts: FistaOptions):
    gsq = jnp.vdot(g_y, g_y).real

    def cond(carry):
        lip, j, ok = carry
        return jnp.logical_and(~ok, j < opts.max_backtracks)

    def body(carry):
        lip, j, _ = carry
        x_try = y - g_y / lip
        f_try, _ = vg(x_try)
        ok = f_try <= f_y - 0.5 * gsq / lip + 1e-12 * jnp.abs(f_y)
        lip_next = jnp.where(ok, lip, lip * opts.eta)
        return (lip_next, j + 1, ok)

    lip, j, _ = jax.lax.while_loop(cond, body, (lip, jnp.int32(0), jnp.asarray(False)))
    return lip, j


def _oracle_fista(value_and_grad, x0, opts: FistaOptions = FistaOptions()):
    f0, _ = value_and_grad(x0)
    ft = f0.dtype
    init = FistaState(
        x=x0, y=x0, t=jnp.asarray(1.0, ft), lip=jnp.asarray(opts.l0, ft),
        f_x=f0, g_norm=jnp.asarray(jnp.inf, ft),
        rel_impr=jnp.asarray(jnp.inf, ft), k=jnp.int32(0),
        n_ls=jnp.int32(0))

    def cond(st: FistaState):
        not_min = st.k < opts.min_iters
        under_max = st.k < opts.max_iters
        grad_big = st.g_norm > opts.eps_grad
        impr_big = st.rel_impr > opts.eps_fval
        return jnp.logical_and(under_max,
                               jnp.logical_or(not_min,
                                              jnp.logical_and(grad_big, impr_big)))

    def body(st: FistaState):
        f_y, g_y = value_and_grad(st.y)
        lip, n_try = _oracle_backtrack(value_and_grad, st.y, f_y, g_y, st.lip, opts)
        x_new = st.y - g_y / lip
        f_new, _ = value_and_grad(x_new)
        # monotone safeguard (MFISTA-lite): never accept an increase over x_k
        worse = f_new > st.f_x
        x_new = jnp.where(worse, st.x, x_new)
        f_new = jnp.where(worse, st.f_x, f_new)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * st.t * st.t))
        y_new = x_new + ((st.t - 1.0) / t_new) * (x_new - st.x)
        rel = (st.f_x - f_new) / jnp.maximum(jnp.abs(st.f_x), 1e-30)
        return FistaState(
            x=x_new, y=y_new, t=t_new, lip=lip, f_x=f_new,
            g_norm=jnp.linalg.norm(g_y), rel_impr=rel, k=st.k + 1,
            n_ls=st.n_ls + n_try)

    final = jax.lax.while_loop(cond, body, init)
    return final.x, final


def _oracle_fista_fixed(value_and_grad, x0, n_iters: int, opts: FistaOptions = FistaOptions()):
    def body(st: FistaState, _):
        f_y, g_y = value_and_grad(st.y)
        lip, n_try = _oracle_backtrack(value_and_grad, st.y, f_y, g_y, st.lip, opts)
        x_new = st.y - g_y / lip
        f_new, _ = value_and_grad(x_new)
        worse = f_new > st.f_x
        x_new = jnp.where(worse, st.x, x_new)
        f_new = jnp.where(worse, st.f_x, f_new)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * st.t * st.t))
        y_new = x_new + ((st.t - 1.0) / t_new) * (x_new - st.x)
        rel = (st.f_x - f_new) / jnp.maximum(jnp.abs(st.f_x), 1e-30)
        return FistaState(x=x_new, y=y_new, t=t_new, lip=lip, f_x=f_new,
                          g_norm=jnp.linalg.norm(g_y), rel_impr=rel,
                          k=st.k + 1, n_ls=st.n_ls + n_try), None

    f0, _ = value_and_grad(x0)
    ft = f0.dtype
    init = FistaState(x=x0, y=x0, t=jnp.asarray(1.0, ft),
                      lip=jnp.asarray(opts.l0, ft), f_x=f0,
                      g_norm=jnp.asarray(jnp.inf, ft),
                      rel_impr=jnp.asarray(jnp.inf, ft), k=jnp.int32(0),
                      n_ls=jnp.int32(0))
    final, _ = jax.lax.scan(body, init, None, length=n_iters)
    return final.x, final


def _quad(seed, scale=1.0):
    r = np.random.RandomState(seed)
    A = jnp.asarray(r.randn(40, 12) * scale, jnp.float32)
    b = jnp.asarray(r.randn(40), jnp.float32)
    return quad_vg(A, b), jnp.zeros(12, jnp.float32)


def _adaptive(opts, scale=1.0):
    def run(new):
        vg, x0 = _quad(1, scale)
        x, st = jax.jit(lambda x0: (fista if new else _oracle_fista)(
            vg, x0, opts))(x0)
        return x, st.k, st.n_ls, st.lip, st.f_x
    return run


def _fixed(new):
    vg, x0 = _quad(2, 3.0)
    opts = FistaOptions(l0=1e-3)
    x, st = jax.jit(lambda x0: (fista_fixed if new else _oracle_fista_fixed)(
        vg, x0, 12, opts))(x0)
    return x, st.k, st.n_ls, st.lip, st.f_x


def _batched_logreg(new, monkeypatch):
    """W=3 lanes of uneven shards (padded, masked) through the batched
    engine's worker body, ``solve_augmented`` under ``vmap``."""
    from repro.core import fista as fista_mod
    from repro.problems import base
    impl = fista if new else _oracle_fista

    def run_fista(vg, x0, opts):
        # hand lip and F(x) out through solve_augmented's x slot; the
        # oracle counts no k_tol
        x, st = impl(vg, x0, opts)
        if not new:
            st = types.SimpleNamespace(**st._asdict(), k_tol=st.k)
        return (x, st.lip, st.f_x), st

    monkeypatch.setattr(fista_mod, "fista", run_fista)
    p = base.make("logreg", n_samples=1000, n_features=64, density=0.1,
                  fista=dict(min_iters=1, eps_grad=1e-3))
    (batch, mask), W = p.batch_shards(3), 3
    r = np.random.RandomState(3)
    xs = jnp.asarray(r.randn(W, 64) * 0.1, jnp.float32)
    us = jnp.asarray(r.randn(W, 64) * 0.05, jnp.float32)
    z = jnp.asarray(r.randn(64) * 0.1, jnp.float32)

    @jax.jit
    def run_all(batch, mask, xs, us):
        def one(shard, m, x0, u):
            vg = p._loss_value_and_grad(shard, m)
            return base.solve_augmented(vg, x0, z - u, jnp.float32(1.0),
                                        None, p.fista)
        return jax.vmap(one)(batch, mask, xs, us)

    (x, lip, f_x), k, n_ls, _ = run_all(batch, mask, xs, us)
    return x, k, n_ls, lip, f_x


_EQUIV_CASES = {
    "quadratic": _adaptive(FistaOptions(eps_grad=1e-3, max_iters=400)),
    "heavy_backtracking": _adaptive(FistaOptions(l0=1e-3, max_iters=200),
                                    scale=3.0),
    "line_search_runs_out": _adaptive(FistaOptions(
        max_backtracks=2, l0=1e-6, min_iters=15, max_iters=60), scale=3.0),
    "no_backtracks": _adaptive(FistaOptions(max_backtracks=0, l0=400.0,
                                            max_iters=60), scale=3.0),
    "min_iters": _adaptive(FistaOptions(min_iters=5, eps_grad=10.0)),
    "fista_fixed": _fixed,
}


@pytest.mark.parametrize("case", list(_EQUIV_CASES) + ["batched_logreg"])
def test_reused_trial_value_is_bitwise_the_recomputed_one(case, monkeypatch):
    """Taking F(x_{k+1}) from the accepted line-search trial changes no
    number: x, k, the trial count, L and F(x) equal the oracle's bit for
    bit, on every lane."""
    if case == "batched_logreg":
        new = _batched_logreg(True, monkeypatch)
        old = _batched_logreg(False, monkeypatch)
    else:
        new, old = _EQUIV_CASES[case](True), _EQUIV_CASES[case](False)
    for name, a, b in zip(("x", "k", "n_ls", "lip", "f_x"), new, old):
        a, b = np.atleast_1d(a), np.atleast_1d(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), (
            name, a, b)


@pytest.mark.parametrize("solver", ["fista", "fista_fixed"])
def test_value_and_grad_traced_three_times(solver):
    """An iteration evaluates F at y and at its line-search trials only:
    value_and_grad is traced for x0, y and the trial (the iteration
    before this reuse traced a fourth, F(x_new) again)."""
    vg, x0 = _quad(4)
    run = {"fista": (fista, _oracle_fista),
           "fista_fixed": (lambda v, x, o: fista_fixed(v, x, 3, o),
                           lambda v, x, o: _oracle_fista_fixed(v, x, 3, o)),
           }[solver]
    counts = []
    for impl in run:
        n = [0]

        def counted(x):
            n[0] += 1
            return vg(x)

        jax.make_jaxpr(lambda x0: impl(counted, x0, FistaOptions()))(x0)
        counts.append(n[0])
    assert counts == [3, 4]


# ---------------------------------------------------------------------------
# k_tol: the iteration at which the paper's tolerance first held.  It only
# counts; the iterates are those of the solve without it.
# ---------------------------------------------------------------------------

def _solve(opts, seed=1, scale=1.0):
    vg, x0 = _quad(seed, scale)
    return jax.jit(lambda x0: fista(vg, x0, opts))(x0)


@pytest.mark.parametrize("opts, scale", [
    (FistaOptions(eps_grad=1e-3, max_iters=400), 1.0),
    (FistaOptions(l0=1e-3, max_iters=200), 3.0),
    (FistaOptions(max_backtracks=2, l0=1e-6, max_iters=60), 3.0),
    (FistaOptions(eps_grad=1e-9, eps_fval=0.0, max_iters=25), 1.0),
], ids=["quadratic", "heavy_backtracking", "line_search_runs_out",
        "max_iters_cap"])
def test_k_tol_is_k_at_one_iteration_floor(opts, scale):
    """With K_w = 1 a solve stops at its tolerance (or at max_iters, where
    the tolerance never held), so k_tol and k agree."""
    _, st = _solve(opts, scale=scale)
    assert int(st.k_tol) == int(st.k) >= 1


@pytest.mark.parametrize("eps_grad", [1e-1, 1e-2])
def test_k_tol_under_a_floor_of_50_is_the_unfloored_k(eps_grad):
    """Under min_iters=50 the solve makes the min_iters=1 solve's iterates
    bit for bit until that one stops, and k_tol marks where it stopped."""
    base = dict(eps_grad=eps_grad, max_iters=400)
    x1, st1 = _solve(FistaOptions(min_iters=1, **base))
    k1 = int(st1.k)
    _, st50 = _solve(FistaOptions(min_iters=50, **base))
    assert k1 < 50 and int(st50.k) == 50
    assert int(st50.k_tol) == k1
    for m in sorted({1, k1 // 2, k1}):
        _, a = _solve(FistaOptions(min_iters=50, **dict(base, max_iters=m)))
        _, b = _solve(FistaOptions(min_iters=1, **dict(base, max_iters=m)))
        for name in ("x", "y", "t", "lip", "f_x", "n_ls", "k"):
            va = np.atleast_1d(np.asarray(getattr(a, name)))
            vb = np.atleast_1d(np.asarray(getattr(b, name)))
            assert np.array_equal(va.view(np.uint8), vb.view(np.uint8)), name
    assert np.array_equal(np.asarray(x1), np.asarray(_solve(FistaOptions(
        min_iters=50, **dict(base, max_iters=k1)))[0]))


def test_k_tol_in_a_fixed_scan():
    """fista_fixed has no stopping rule but counts k_tol all the same."""
    vg, x0 = _quad(1)
    opts = FistaOptions(eps_grad=1e-1)
    _, st1 = fista(vg, x0, opts)
    _, stf = fista_fixed(vg, x0, int(st1.k) + 7, opts)
    assert int(stf.k_tol) == int(st1.k) and int(stf.k) == int(st1.k) + 7


@pytest.mark.parametrize("min_iters", [1, 50])
def test_solve_all_reports_tol_iters_in_its_one_read(min_iters, monkeypatch):
    """The batched engine's ``solve_all`` reads k, the trials, k_tol and
    the line search's lane evaluations in one ``device_get`` and records
    k_tol as the round's ``tol_iters``."""
    from repro.problems import base
    from repro.runtime import spans
    p = base.make("logreg", n_samples=1000, n_features=64, density=0.1,
                  fista=dict(min_iters=min_iters, eps_grad=1e-2))
    W = 3
    r = np.random.RandomState(5)
    xs = jnp.asarray(r.randn(W, 64) * 0.1, jnp.float32)
    us = jnp.asarray(r.randn(W, 64) * 0.05, jnp.float32)
    z = jnp.asarray(r.randn(64) * 0.1, jnp.float32)
    reads = []
    real = jax.device_get

    def counted(tree):
        reads.append(len(tree))
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counted)
    with spans.record() as rec:
        _, ks = p.solve_all(xs, us, z, 1.0)
    assert reads == [4]
    tol = rec.counters["tol_iters"]
    assert tol.shape == (W,) and tol.dtype == np.int32
    assert np.all(tol >= 1) and np.all(tol <= ks)
    if min_iters == 1:
        assert np.array_equal(tol, ks)
    else:
        assert np.all(ks == 50) and np.all(tol < 50)


# ---------------------------------------------------------------------------
# fista_lanes: W solves in one program, the line search's retries over the
# lanes still searching.  Lane by lane it is ``vmap`` of the one-lane solve.
# ---------------------------------------------------------------------------


def _lane_quad(d, x):
    A, b = d
    return quad_vg(A, b)(x)


def _quad_lanes(W, seed=7, spread=3.0):
    """W least-squares lanes whose curvatures differ by up to 2**spread,
    so their line searches need different numbers of trials."""
    r = np.random.RandomState(seed)
    scale = 2.0 ** (spread * r.rand(W))[:, None, None]
    A = jnp.asarray(r.randn(W, 30, 10) * scale, jnp.float32)
    b = jnp.asarray(r.randn(W, 30), jnp.float32)
    return _lane_quad, (A, b), jnp.zeros((W, 10), jnp.float32)


def _vmapped(vg, data, x0, opts, n_iters=None):
    def one(d, x):
        f = functools.partial(vg, d)
        if n_iters is None:
            return fista(f, x, opts)
        return fista_fixed(f, x, n_iters, opts)
    return jax.jit(jax.vmap(one))(data, x0)


def _lanes(vg, data, x0, opts, n_iters=None):
    return jax.jit(lambda d, x: fista_lanes(vg, d, x, opts, n_iters))(
        data, x0)


def _logreg_lanes(W):
    """The batched engine's lanes: masked logreg shards, the last padded
    (W does not divide the rows), through the engine's own solver."""
    from repro.problems import base
    p = base.make("logreg", n_samples=61 * W + 5, n_features=48,
                  density=0.1, fista=dict(min_iters=1, eps_grad=1e-4))
    batch, mask = p.batch_shards(W)
    assert float(mask[-1].sum()) < mask.shape[1]
    r = np.random.RandomState(W)
    xs = jnp.asarray(r.randn(W, 48) * 0.1, jnp.float32)
    us = jnp.asarray(r.randn(W, 48) * 0.05, jnp.float32)
    z = jnp.asarray(r.randn(48) * 0.1, jnp.float32)
    rho = jnp.float32(0.5)

    def vg(lane, x):
        shard, m, center = lane
        return base.augmented(p._loss_value_and_grad(shard, m),
                              center, rho)(x)
    return vg, (batch, mask, z - us), xs, p.fista


_LANE_CASES = {
    "curvatures": lambda: (*_quad_lanes(16), FistaOptions(
        eps_grad=1e-3, max_iters=300), None),
    "more_failing_than_a_pass": lambda: (*_quad_lanes(64, spread=6.0),
                                         FistaOptions(l0=0.5, max_iters=80),
                                         None),
    "every_lane_failing": lambda: (*_quad_lanes(16, spread=0.5),
                                   FistaOptions(l0=1e-4, max_iters=40), None),
    "line_search_runs_out": lambda: (*_quad_lanes(16), FistaOptions(
        max_backtracks=2, l0=1e-6, min_iters=15, max_iters=60), None),
    "no_backtracks": lambda: (*_quad_lanes(16), FistaOptions(
        max_backtracks=0, l0=4000.0, max_iters=60), None),
    "min_iters_50": lambda: (*_quad_lanes(16), FistaOptions(
        min_iters=50, eps_grad=1e-2), None),
    "fista_fixed": lambda: (*_quad_lanes(16), FistaOptions(l0=1e-3), 25),
    "logreg_w4": lambda: (*_logreg_lanes(4), None),
    "logreg_w64": lambda: (*_logreg_lanes(64), None),
}


@pytest.mark.parametrize("case", list(_LANE_CASES))
def test_lanes_are_bitwise_the_vmapped_solve(case):
    """Lane by lane, ``fista_lanes`` makes the iterates, iteration count,
    trials and k_tol of ``vmap`` over the one-lane ``fista`` (or
    ``fista_fixed``), bit for bit."""
    vg, data, x0, opts, n_iters = _LANE_CASES[case]()
    x, st, slots = _lanes(vg, data, x0, opts, n_iters)
    x_ref, ref = _vmapped(vg, data, x0, opts, n_iters)
    for name, a, b in (("x", x, x_ref), ("k", st.k, ref.k),
                       ("n_ls", st.n_ls, ref.n_ls),
                       ("k_tol", st.k_tol, ref.k_tol),
                       ("lip", st.lip, ref.lip), ("f_x", st.f_x, ref.f_x)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), name
    # a pass evaluates each lane still searching once at least
    assert int(slots) >= int(np.sum(ref.n_ls))
    if case != "no_backtracks":
        assert np.any(np.asarray(ref.n_ls) > np.asarray(ref.k))


def _planted(ms, n_lanes):
    """One iteration of lanes F_w(x) = c_w/2 ||x - 1||^2 from x = 0 with
    c_w = 0.75 * 2**m_w: from L = 1 the search doubles L up to 2**m_w, so
    lane w makes m_w + 1 trials (the arithmetic is exact in f32)."""
    c = jnp.asarray(0.75 * 2.0 ** np.asarray(ms, np.float64), jnp.float32)

    def vg(c, x):
        dx = x - 1.0
        return 0.5 * c * jnp.vdot(dx, dx), c * dx

    x, st, slots = _lanes(vg, c, jnp.zeros((n_lanes, 4), jnp.float32),
                          FistaOptions(), 1)
    assert np.array_equal(np.asarray(st.n_ls), np.asarray(ms) + 1)
    return int(slots)


def test_ls_slots_counts_every_slot_of_every_pass():
    """W=16: every pass takes the first 2 (``16 // 8``) lanes still
    searching, fill slots counted."""
    # lanes 0-9 need 1 trial, 10-12 need 2, 13-14 need 4 and 15 needs 8:
    # passes take (0, 1) ... (8, 9), (10, 11) twice, (12, 13), (12, 13),
    # (13, 14), (13, 14), (14, 15), (14, 15), then lane 15 and a fill
    # slot six times: 19 passes, 38 slots
    assert _planted([0] * 10 + [1, 1, 1, 3, 3, 7], 16) == 38
    # every lane needs 4 trials: 32 passes of two
    assert _planted([3] * 16, 16) == 4 * 16
