"""Data pipelines: determinism, shard disjointness, sparse/dense equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # real hypothesis in CI; stub offline

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.configs.logreg_paper import scaled
from repro.data import lm as lm_data
from repro.data import logreg


CFG = scaled(64, 32, density=0.2, lam1=1.0)


def test_worker_shard_deterministic():
    A1, b1 = logreg.worker_shard(CFG, 1, 4)
    A2, b2 = logreg.worker_shard(CFG, 1, 4)
    np.testing.assert_array_equal(np.asarray(A1), np.asarray(A2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


def test_sparse_matches_dense():
    A, b = logreg.worker_shard(CFG, 0, 4)
    idx, vals, bs = logreg.worker_shard_sparse(CFG, 0, 4)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(bs))
    dense_from_sparse = np.zeros_like(np.asarray(A))
    for i in range(idx.shape[0]):
        dense_from_sparse[i, np.asarray(idx[i])] = np.asarray(vals[i])
    np.testing.assert_allclose(np.asarray(A), dense_from_sparse)


def test_sparse_vg_matches_dense_vg(rng):
    """The logreg workload's gather-format loss is the dense logistic
    loss on the same rows."""
    from repro.kernels import ref
    from repro.problems import LogRegProblem
    A, b = logreg.worker_shard(CFG, 2, 4)
    x = jnp.asarray(rng.randn(CFG.n_features) * 0.2, jnp.float32)
    f1, g1 = ref.logistic_value_and_grad(A, b)(x)
    p = LogRegProblem(CFG)
    f2, g2 = p._loss_value_and_grad(p._shard(2, 4))(x)
    np.testing.assert_allclose(f1, f2, rtol=1e-5)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


def test_data_cache_round_trips(tmp_path, monkeypatch):
    """Shards kept under REPRO_DATA_CACHE are read back as generated."""
    from repro.problems import LogRegProblem
    fresh = LogRegProblem(CFG)._shard(1, 3)
    monkeypatch.setenv("REPRO_DATA_CACHE", str(tmp_path))
    written = LogRegProblem(CFG)._shard(1, 3)
    assert len(list(tmp_path.iterdir())) == 1
    read = LogRegProblem(CFG)._shard(1, 3)
    for a, b, c in zip(fresh, written, read):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert a.dtype == c.dtype


@given(st.integers(1, 6), st.integers(1, 6))
@settings(max_examples=15, deadline=None)
def test_resharding_preserves_global_dataset(w1, w2):
    """Row identity is global: any (W, w) partition covers the same rows."""
    def rows(W):
        out = {}
        for w in range(W):
            lo, hi = logreg.shard_rows(CFG.n_samples, W, w)
            A, b = logreg.worker_shard(CFG, w, W)
            for i, g in enumerate(range(lo, hi)):
                out[g] = (np.asarray(A[i]), float(b[i]))
        return out
    r1, r2 = rows(w1), rows(w2)
    assert r1.keys() == r2.keys()
    for g in list(r1)[:10]:
        np.testing.assert_array_equal(r1[g][0], r2[g][0])
        assert r1[g][1] == r2[g][1]


def test_shards_partition_rows():
    seen = []
    for w in range(4):
        lo, hi = logreg.shard_rows(CFG.n_samples, 4, w)
        seen.extend(range(lo, hi))
    assert sorted(seen) == list(range(CFG.n_samples))


def test_row_stats_match_koh_kim_boyd():
    """Labels ~ ±1 w.p. 1/2; k = round(p*d) nonzeros per row."""
    cfg = scaled(2000, 50, density=0.2, lam1=1.0)
    A, b = logreg.worker_shard(cfg, 0, 1)
    nnz = (np.asarray(A) != 0).sum(axis=1)
    assert (nnz == round(cfg.density * cfg.n_features)).all()
    frac_pos = float((np.asarray(b) > 0).mean())
    assert 0.4 < frac_pos < 0.6


def test_lm_batch_deterministic_and_shaped():
    cfg = reduced(get_config("qwen2_7b"))
    shape = ShapeConfig("t", 16, 4, "train")
    b1 = lm_data.batch_for(cfg, shape, 3)
    b2 = lm_data.batch_for(cfg, shape, 3)
    b3 = lm_data.batch_for(cfg, shape, 4)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    assert b1["tokens"].shape == (4, 16)
    assert b1["labels"].shape == (4, 16)
    assert bool(jnp.all(b1["tokens"] < cfg.vocab_size))
    # next-token labels
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["labels"][:, :-1]))


def test_lm_worker_batch_slices_global():
    cfg = reduced(get_config("musicgen_large"))
    shape = ShapeConfig("t", 8, 8, "train")
    full = lm_data.batch_for(cfg, shape, 0)
    w1 = lm_data.worker_batch(cfg, shape, 0, 1, 4)
    np.testing.assert_array_equal(np.asarray(full["embeds"][2:4]),
                                  np.asarray(w1["embeds"]))
