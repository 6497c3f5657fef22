"""Conformance suite for the workload registry: every registered problem
honors the WorkerProblem contract the scheduler relies on.

One parametrized pass over ``repro.problems.available()``:
  * shards partition the dataset (sizes sum to n_samples),
  * ``solve`` decreases the augmented objective,
  * ``prox_h`` is the true prox of ``h_value`` (variational check),
  * the loss ignores padded rows exactly,
  * a 4-worker end-to-end run through ``repro.api`` converges,
plus the registry mechanics (unknown/duplicate names, plugin decorator).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import problems
from repro.api import ExperimentSpec, run
from repro.core.admm import AdmmOptions
from repro.runtime import PoolConfig, SchedulerConfig

# small instances per registered workload (real math, test-sized).
# newton_sketch is registered but NOT conformance-tested here: it is a
# second-order problem (no FISTA solve / prox contract) with its own
# suite in tests/test_newton.py.
SMALL = {
    "logreg": dict(n_samples=512, n_features=48, density=0.1, lam1=0.3,
                   fista=dict(min_iters=1, eps_grad=1e-3)),
    "logreg_l2": dict(n_samples=512, n_features=48, density=0.1,
                      lam2=1e-2,
                      fista=dict(min_iters=1, eps_grad=1e-3)),
    "lasso": dict(n_samples=512, n_features=48),
    "svm": dict(n_samples=512, n_features=48, density=0.1),
    "softmax": dict(n_samples=384, n_features=16, n_classes=4),
    # the nuisance role is the FISTA/prox workload; the combine role's
    # extra surface (handoff, residual shards) is tests/test_phases.py
    "double_ml": dict(n_samples=512, n_features=24, n_folds=4, fold=1,
                      target="y", lam1=0.02),
}
NAMES = sorted(SMALL)


def test_builtin_registry_is_covered():
    """Every built-in workload has a SMALL instance in this suite (a new
    registered workload must add one to be conformance-tested)."""
    assert set(problems.available()) >= set(NAMES)
    builtin = {"logreg", "logreg_l2", "lasso", "svm", "softmax",
               "double_ml"}
    assert builtin <= set(NAMES)


@pytest.fixture(scope="module", params=NAMES)
def named_problem(request):
    return request.param, problems.make(request.param,
                                        **SMALL[request.param])


def test_shard_partition_sums_to_n_samples(named_problem):
    name, p = named_problem
    total = p.n_samples(0, 1)
    assert total > 0
    for W in (2, 3, 4, 7):
        sizes = [p.n_samples(w, W) for w in range(W)]
        assert sum(sizes) == total, (name, W)
        assert min(sizes) > 0


def test_solve_decreases_augmented_objective(named_problem):
    name, p = named_problem
    d = p.n_features
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    u = jnp.asarray(rng.normal(size=d) * 0.05, jnp.float32)
    x0 = jnp.zeros((d,), jnp.float32)
    rho = 1.0

    def aug(x):
        dx = np.asarray(x) - np.asarray(z - u)
        return p.local_value(0, 2, x) + 0.5 * rho * float(dx @ dx)

    x_new, iters = p.solve(0, 2, x0, z, u, rho)
    assert iters >= 1
    assert np.all(np.isfinite(np.asarray(x_new)))
    assert aug(x_new) < aug(x0), name


def test_prox_h_minimizes_h_plus_quadratic(named_problem):
    """Variational characterization: p* = argmin_y h(y) + ||y-v||^2/(2t)
    must beat v itself and random perturbations of p*."""
    name, p = named_problem
    d = p.n_features
    rng = np.random.default_rng(1)
    v = jnp.asarray(rng.normal(size=d), jnp.float32)
    t = 0.3
    pstar = p.prox_h(v, t)

    def F(y):
        dy = np.asarray(y) - np.asarray(v)
        return p.h_value(y) + float(dy @ dy) / (2 * t)

    f_star = F(pstar)
    assert f_star <= F(v) + 1e-5
    for _ in range(5):
        delta = jnp.asarray(rng.normal(size=d) * 0.01, jnp.float32)
        assert f_star <= F(pstar + delta) + 1e-5, name


def test_loss_ignores_padding(named_problem):
    """The one loss hook serves both engines: an all-ones row mask is the
    unmasked loss, and rows padded as ``batch_shards`` pads them (zeros,
    mask 0) change neither value nor gradient."""
    name, p = named_problem
    assert isinstance(p, problems.FistaShardProblem), name
    shard = p._shard(0, 2)
    n = int(jax.tree_util.tree_leaves(shard)[0].shape[0])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=p.n_features) * 0.2, jnp.float32)
    f, g = p._loss_value_and_grad(shard)(x)

    f1, g1 = p._loss_value_and_grad(shard, jnp.ones((n,), jnp.float32))(x)
    np.testing.assert_allclose(f1, f, rtol=1e-6, err_msg=name)
    np.testing.assert_allclose(g1, g, rtol=1e-6, err_msg=name)

    pad = 5
    padded = jax.tree_util.tree_map(
        lambda a: np.pad(np.asarray(a),
                         [(0, pad)] + [(0, 0)] * (np.ndim(a) - 1)), shard)
    mask = jnp.asarray(np.r_[np.ones(n), np.zeros(pad)], jnp.float32)
    fp, gp = p._loss_value_and_grad(
        jax.tree_util.tree_map(jnp.asarray, padded), mask)(x)
    np.testing.assert_allclose(fp, f, rtol=1e-6, err_msg=name)
    np.testing.assert_allclose(gp, g, rtol=1e-6, err_msg=name)


def test_end_to_end_four_workers_converges(named_problem):
    name, p = named_problem
    res = run(ExperimentSpec(
        problem=name, problem_kwargs=SMALL[name],
        scheduler=SchedulerConfig(n_workers=4,
                                  admm=AdmmOptions(max_iters=12),
                                  pool=PoolConfig(seed=0))), problem=p)
    rs = [t["r_norm"] for t in res.trace]
    assert np.all(np.isfinite(rs))
    assert rs[-1] < rs[1] / 1.5, (name, rs)
    # real progress on the objective, not just consensus
    obj = p.objective(res.z, 4)
    obj0 = p.objective(np.zeros_like(res.z), 4)
    assert obj < obj0, name


# -- registry mechanics -----------------------------------------------------

def test_make_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown problem"):
        problems.make("definitely_not_registered")


def test_register_duplicate_raises():
    with pytest.raises(ValueError, match="already registered"):
        problems.register("logreg", lambda **kw: None)


def test_register_decorator_plugin_roundtrip():
    @problems.register("_conformance_tmp")
    def factory(**kw):
        return problems.make("lasso", **SMALL["lasso"])

    try:
        assert "_conformance_tmp" in problems.available()
        p = problems.make("_conformance_tmp")
        assert p.n_features == SMALL["lasso"]["n_features"]
    finally:
        problems.unregister("_conformance_tmp")
    assert "_conformance_tmp" not in problems.available()


def test_softmax_is_matrix_valued_on_the_wire():
    kw = SMALL["softmax"]
    p = problems.make("softmax", **kw)
    assert p.n_features == kw["n_features"] * kw["n_classes"]
