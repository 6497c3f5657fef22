"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, lowers
and compiles each kernel at the paper's widths and refuses what the chip
would refuse (a tile past the scoped-VMEM limit, a misaligned slice).
Interpret-mode tests cannot see either.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
under pytest-xdist every worker imports every test file.  Keep these
tests in this one file, so that one worker loads it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import problems
from repro.kernels import logistic_vjp as lv_k
from repro.kernels import ops
from repro.kernels import soft_threshold as st_k

# the paper's worker shard at W=64: 9375 rows x 10,000 features, which
# the wrapper pads to 9472 x 10112 (128-row tiles)
N, D = 9375, 10_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """f32 shapes placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                               sharding=one_chip)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("lanes", [None, 2], ids=["single", "vmapped"])
@pytest.mark.parametrize("loss", ["logistic", "hinge"])
def test_margin_kernel_compiles_at_paper_width(spec, loss, lanes):
    """The wrapper's row-tile rule keeps the double-buffered A tile
    inside the default scoped VMEM at D=10112 (256 rows do not fit)."""
    assert ops.margin_layout(N, D) == (9472, 10112, 128)
    one = functools.partial(ops._margin_impl, loss=loss, gamma=0.5,
                            mode="pallas")
    if lanes is None:
        args = (spec(N, D), spec(N), spec(D), spec(N))
        fn = one
    else:
        args = (spec(lanes, N, D), spec(lanes, N), spec(lanes, D),
                spec(lanes, N))
        fn = jax.vmap(one)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("d", [6144, 12288, 24576])
def test_margin_kernel_compiles_at_tile_rule_edges(spec, d):
    """The widest D each row tile is chosen for (256, 128, 64 rows)."""
    n_p, d_p, br = ops.margin_layout(512, d)
    assert br * d_p * 4 <= lv_k.A_TILE_BYTES < 2 * br * d_p * 4
    one = functools.partial(ops._margin_impl, loss="logistic", gamma=0.0,
                            mode="pallas")
    text = _compiled_text(one, spec(n_p, d_p), spec(n_p), spec(d),
                          spec(n_p))
    assert "tpu_custom_call" in text


def test_soft_threshold_compiles_at_paper_width(spec):
    dp = ops.margin_layout(1, D)[1]
    text = _compiled_text(st_k.soft_threshold_pallas, spec(1, dp),
                          spec(1, dp), spec(1, 1))
    assert "tpu_custom_call" in text


def test_staged_kernel_solve_holds_no_padded_copy(spec, monkeypatch):
    """The batched pallas solve on the smoke run's cut (W=64, 1500 rows
    per lane, d=10,000): with the shards staged in the kernel layout the
    program keeps no padded copy of A.  Padding inside each loss call
    left ~12 GB of temporaries, three hoisted copies of the batch."""
    monkeypatch.setenv("REPRO_PALLAS", "pallas")   # the TPU branch of ops
    W, n, d = 64, 1500, D
    n_p, d_p, _ = ops.margin_layout(n, d)
    prob = problems.make("logreg", n_samples=W * n, n_features=d,
                         density=0.001, lam1=1.0, fista={}, fixed_inner=5)
    run_all = prob._batched_solver(((W, n_p, d_p), (W, n_p)), "pallas")
    compiled = run_all.lower((spec(W, n_p, d_p), spec(W, n_p)),
                             spec(W, n_p), spec(W, d), spec(d),
                             spec(W, d), spec()).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
