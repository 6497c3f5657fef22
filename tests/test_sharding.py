"""Sharding rules: every spec divides every leaf for all archs x meshes.

Uses AbstractMesh so no 256-device backend is needed — this is the cheap
regression net in front of the (expensive) compile-everything dry-run.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, LM_SHAPES, get_config, input_specs
from repro.configs.base import cell_is_applicable
from repro.models import model as M
from repro.optim import optimizers as opt_mod
from repro.parallel import sharding

POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(spec_tree, shape_tree, mesh, where=""):
    def one(kp, spec, leaf):
        assert len(spec) <= len(leaf.shape), (where, kp, spec, leaf.shape)
        for i, part in enumerate(spec):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            size = math.prod(mesh.shape[a] for a in axes)
            assert leaf.shape[i] % size == 0, (
                where, jax.tree_util.keystr(kp), i, leaf.shape, spec)
    jax.tree_util.tree_map_with_path(
        one, spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_and_moment_specs_divide(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        functools.partial(M.init_params, cfg=cfg), jax.random.PRNGKey(0))
    for fsdp in (False, True):
        specs = sharding.param_spec_tree(cfg, shapes, mesh, fsdp=fsdp)
        _check_divisible(specs, shapes, mesh, f"{arch} params fsdp={fsdp}")
    z = sharding.zero1_spec_tree(cfg, shapes, mesh)
    _check_divisible(z, shapes, mesh, f"{arch} zero1")


@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_specs_divide(arch, mesh):
    cfg = get_config(arch)
    for shape in LM_SHAPES:
        if shape.kind == "train":
            continue
        ok, _ = cell_is_applicable(cfg, shape)
        if not ok:
            continue
        cache = M.init_cache(cfg, shape.global_batch, shape.seq_len,
                             abstract=True)
        specs = sharding.cache_spec_tree(cfg, cache, mesh)
        _check_divisible(specs, cache, mesh, f"{arch} {shape.name} cache")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_specs_divide(arch):
    cfg = get_config(arch)
    for shape in LM_SHAPES:
        ok, _ = cell_is_applicable(cfg, shape)
        if not ok:
            continue
        specs_in = input_specs(cfg, shape)
        b = sharding.batch_spec_tree(specs_in, POD)
        _check_divisible(b, specs_in, POD, f"{arch} {shape.name} batch")


def test_zero1_upgrades_replicated_leaves():
    cfg = get_config("qwen2_7b")
    shapes = jax.eval_shape(
        functools.partial(M.init_params, cfg=cfg), jax.random.PRNGKey(0))
    z = sharding.zero1_spec_tree(cfg, shapes, POD)
    # norm scales (d,) should be data-sharded in the moment tree
    ln_spec = z["blocks"]["ln1"]
    assert any(s is not None for s in ln_spec)


def test_row_col_roles():
    cfg = get_config("granite_8b")
    shapes = jax.eval_shape(
        functools.partial(M.init_params, cfg=cfg), jax.random.PRNGKey(0))
    specs = sharding.param_spec_tree(cfg, shapes, POD)
    assert specs["blocks"]["attn"]["wq"]["w"][-1] == "model"   # col
    assert specs["blocks"]["attn"]["wo"]["w"][-2] == "model"   # row
    assert specs["blocks"]["mlp"]["w_down"]["w"][-2] == "model"
    assert specs["head"][0] == "model"                          # vocab par.
    assert specs["embed"][1] == "model"                         # d par.


def test_activation_rules_batch_guard():
    cfg = get_config("zamba2_1_2b")
    r = sharding.activation_rules(cfg, POD, global_batch=1)   # long_500k
    assert r["btd"][0] is None
    r = sharding.activation_rules(cfg, POD, global_batch=256)
    assert r["btd"][0] is not None
