"""Pallas kernels vs pure-jnp oracles, interpret=True, shape/dtype sweeps."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import logistic_vjp as lv_k
from repro.kernels import ref
from repro.kernels import soft_threshold as st_k


def _rand(rng, shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.randn(*shape) * scale, dtype)


# ---------------------------------------------------------------------------
# logistic_vjp
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,block", [(256, 128, 256), (512, 256, 256),
                                       (1024, 128, 512)])
def test_logistic_vjp_sweep(rng, n, d, block):
    a = _rand(rng, (n, d), scale=0.3)
    b = jnp.asarray(np.sign(rng.randn(n, 1)), jnp.float32)
    mask = jnp.ones((n, 1), jnp.float32)
    x = _rand(rng, (1, d), scale=0.1)
    loss_k, grad_k = lv_k.logistic_vjp_pallas(a, b, mask, x,
                                              block_rows=block,
                                              interpret=True)
    loss_r, grad_r = ref.logistic_vjp_ref(a, b, mask, x)
    np.testing.assert_allclose(loss_k, loss_r, rtol=2e-5)
    np.testing.assert_allclose(grad_k, grad_r, rtol=2e-4, atol=2e-4)


def test_logistic_vjp_padding_mask(rng):
    """Masked (padding) rows contribute nothing."""
    a = _rand(rng, (256, 128), scale=0.3)
    b = jnp.asarray(np.sign(rng.randn(256, 1)), jnp.float32)
    mask = jnp.zeros((256, 1), jnp.float32).at[:100].set(1.0)
    x = _rand(rng, (1, 128), scale=0.1)
    loss_k, grad_k = lv_k.logistic_vjp_pallas(a, b, mask, x, block_rows=256,
                                              interpret=True)
    loss_r, grad_r = ref.logistic_vjp_ref(a[:100], b[:100],
                                          jnp.ones((100, 1)), x)
    np.testing.assert_allclose(loss_k, loss_r, rtol=2e-5)
    np.testing.assert_allclose(grad_k, grad_r, rtol=2e-4, atol=2e-4)


def test_ops_wrapper_matches_data_oracle(rng, monkeypatch):
    """ops.fused_logistic_vjp == the dense closed form on odd shapes."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    from repro.kernels import ops
    A = _rand(rng, (111, 70), scale=0.3)
    b = jnp.asarray(np.sign(rng.randn(111)), jnp.float32)
    x = _rand(rng, (70,), scale=0.1)
    f_k, g_k = ops.fused_logistic_vjp(A, b, x)
    f_r, g_r = ref.logistic_value_and_grad(A, b)(x)
    np.testing.assert_allclose(f_k, f_r, rtol=2e-5)
    np.testing.assert_allclose(g_k, g_r, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n, d, layout", [
    (9375, 10_000, (9472, 10112, 128)),    # the paper's W=64 shard
    (1500, 10_000, (1536, 10112, 128)),
    (300, 6144, (512, 6144, 256)),         # widest D for 256-row tiles
    (300, 6145, (384, 6272, 128)),
    (300, 128, (512, 128, 256)),
    (13, 100, (16, 128, 16)),              # small shards tile to 8 rows
    (9472, 10112, (9472, 10112, 128)),     # a staged shard maps to itself
])
def test_margin_layout(n, d, layout):
    from repro.kernels import ops
    assert ops.margin_layout(n, d) == layout
    assert layout[2] * layout[1] * 4 <= lv_k.A_TILE_BYTES


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_ops_wrapper_takes_staged_layout(rng, monkeypatch, mode):
    """A batch staged by problems.base.margin_kernel_batch (rows padded
    with mask 0, zero columns past len(x)) gives exactly what the
    unstaged shards give."""
    monkeypatch.setenv("REPRO_PALLAS", mode)
    from repro.kernels import ops
    from repro.problems import base as pbase
    W, n, k, d = 3, 37, 4, 150
    idx = rng.randint(0, d, (W, n, k)).astype(np.int32)
    vals = rng.randn(W, n, k).astype(np.float32)
    b = jnp.asarray(np.sign(rng.randn(W, n)), jnp.float32)
    mask = jnp.asarray((rng.rand(W, n) < 0.8).astype(np.float32))
    x = _rand(rng, (W, d), scale=0.1)
    (A_s, b_s), m_s = pbase.margin_kernel_batch((idx, vals, b), mask, d)
    assert A_s.shape == (W,) + ops.margin_layout(n, d)[:2]
    A = jnp.asarray(np.stack([pbase.densify_sparse_rows(idx[w], vals[w], d)
                              for w in range(W)]))
    f, g = ops.fused_logistic_vjp(A, b, x, mask=mask)
    f_s, g_s = ops.fused_logistic_vjp(A_s, b_s, x, mask=m_s)
    assert g_s.shape == (W, d)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(g_s), np.asarray(g))


@pytest.mark.parametrize("name", ["logreg", "svm"])
def test_kernel_batch_is_staged(name):
    """The problems hand the kernel path its batch already in the kernel
    layout, so the wrapper never pads A inside the FISTA loop."""
    from repro import problems
    from repro.kernels import ops
    p = problems.make(name, n_samples=61, n_features=130)
    (A, b), mask = p.kernel_batch_shards(4)
    n_p, d_p, _ = ops.margin_layout(16, 130)          # 4 lanes of <= 16 rows
    assert A.shape == (4, n_p, d_p) and b.shape == mask.shape == (4, n_p)
    assert float(jnp.sum(mask)) == 61.0


# ---------------------------------------------------------------------------
# REPRO_PALLAS dispatch
# ---------------------------------------------------------------------------


def test_mode_dispatch_all_values(monkeypatch):
    """Every recognized REPRO_PALLAS value dispatches verbatim; empty
    falls back to the backend default; anything else RAISES (a typo must
    not silently run the jnp oracle while claiming kernel coverage)."""
    from repro.kernels import ops
    for value in ("ref", "interpret", "pallas"):
        monkeypatch.setenv("REPRO_PALLAS", value)
        assert ops._mode() == value
    monkeypatch.delenv("REPRO_PALLAS")
    assert ops._mode() == (
        "pallas" if jax.default_backend() == "tpu" else "ref")
    monkeypatch.setenv("REPRO_PALLAS", "interperet")   # the classic typo
    with pytest.raises(ValueError, match="interperet"):
        ops._mode()


# ---------------------------------------------------------------------------
# svm_vjp (smoothed hinge)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,gamma", [(256, 128, 0.5), (512, 128, 0.2)])
def test_svm_vjp_sweep(rng, n, d, gamma):
    a = _rand(rng, (n, d), scale=0.3)
    b = jnp.asarray(np.sign(rng.randn(n, 1)), jnp.float32)
    mask = jnp.zeros((n, 1), jnp.float32).at[:n - 37].set(1.0)
    x = _rand(rng, (1, d), scale=0.1)
    loss_k, grad_k = lv_k.svm_vjp_pallas(a, b, mask, x, gamma=gamma,
                                         block_rows=256, interpret=True)
    loss_r, grad_r = ref.svm_vjp_ref(a, b, mask, x, gamma)
    np.testing.assert_allclose(loss_k, loss_r, rtol=2e-5)
    np.testing.assert_allclose(grad_k, grad_r, rtol=2e-4, atol=2e-4)


def test_svm_ref_matches_problem_loss(rng):
    """The kernel oracle IS problems/svm.py's smoothed hinge: dense ref
    vs the problem's sparse gather-format loss on the same data."""
    from repro.problems import base as pbase
    from repro.problems.svm import SVMProblem
    p = SVMProblem(n_samples=40, n_features=16, seed=3)
    idx, vals, b = p._shard(0, 2)
    n = idx.shape[0]
    A = pbase.densify_sparse_rows(idx, vals, 16)
    x = _rand(rng, (16,), scale=0.2)
    f_sparse, g_sparse = p._loss_value_and_grad((idx, vals, b))(x)
    f_ref, g_ref = ref.svm_vjp_ref(jnp.asarray(A), b[:, None],
                                   jnp.ones((n, 1)), x[None, :],
                                   p.smoothing)
    np.testing.assert_allclose(float(f_ref[0, 0]), float(f_sparse),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_ref[0]), np.asarray(g_sparse),
                               rtol=1e-4, atol=1e-5)


def test_softmax_ref_matches_problem_loss(rng):
    """softmax_vjp_ref vs problems/softmax.py's loss on a real shard."""
    from repro.problems.softmax import SoftmaxProblem
    p = SoftmaxProblem(n_samples=30, n_features=8, n_classes=3, seed=1)
    A, y = p._shard(0, 2)
    x = _rand(rng, (8 * 3,), scale=0.2)
    f_prob, g_prob = p._loss_value_and_grad((A, y))(x)
    f_ref, g_ref = ref.softmax_vjp_ref(A, y, jnp.ones((A.shape[0], 1)),
                                       x.reshape(8, 3))
    np.testing.assert_allclose(float(f_ref[0, 0]), float(f_prob), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_ref).reshape(-1),
                               np.asarray(g_prob), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# soft_threshold
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [128, 512, 1024, 8320])
def test_soft_threshold_sweep(rng, d):
    # 8320 > the 8192 default block but is NOT a multiple of it — the
    # regression shape for _pick_block (the naive min(block, D) tiling
    # asserted out on exactly this case)
    omega = _rand(rng, (1, d))
    z_old = _rand(rng, (1, d))
    thr = jnp.asarray([[0.37]], jnp.float32)
    out_k = st_k.soft_threshold_pallas(omega, z_old, thr, interpret=True)
    out_r = ref.soft_threshold_ref(omega, z_old, thr)
    for k_arr, r_arr in zip(out_k, out_r):
        np.testing.assert_allclose(k_arr, r_arr, rtol=1e-5, atol=1e-6)


def test_soft_threshold_pick_block():
    assert st_k._pick_block(8192, 8192) == 8192
    assert st_k._pick_block(256, 8192) == 256
    # 8320 = 128 * 65: its largest 128-multiple divisor <= 8192 is 1664
    assert st_k._pick_block(8320, 8192) == 1664
    blk = st_k._pick_block(8320, 8192)
    assert 8320 % blk == 0 and blk % 128 == 0 and blk <= 8192


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (1, 256, 4, 4, 64, None),
    (2, 256, 4, 2, 64, None),        # GQA
    (1, 512, 2, 2, 64, 128),         # sliding window
    (1, 256, 8, 1, 64, None),        # MQA
])
def test_flash_attention_sweep(rng, B, S, H, KV, hd, window):
    q = _rand(rng, (B, S, H, hd), jnp.float32, 0.5)
    k = _rand(rng, (B, S, KV, hd), jnp.float32, 0.5)
    v = _rand(rng, (B, S, KV, hd), jnp.float32, 0.5)
    G = H // KV
    qr = (q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * KV, G * S, hd))
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    o = fa_k.flash_attention_pallas(qr, kr, vr, seq_q=S, causal=True,
                                    window=window, block_q=128, block_kv=128,
                                    interpret=True)
    o = (o.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)
         .reshape(B, S, H, hd))
    o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(o, o_ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Smax,H,KV,hd", [
    (2, 512, 4, 4, 64),
    (2, 512, 8, 2, 64),
    (1, 1024, 4, 1, 128),
])
def test_decode_attention_sweep(rng, B, Smax, H, KV, hd):
    q = _rand(rng, (B, 1, H, hd), jnp.float32, 0.5)
    kc = _rand(rng, (B, Smax, KV, hd), jnp.float32, 0.5)
    vc = _rand(rng, (B, Smax, KV, hd), jnp.float32, 0.5)
    positions = jnp.asarray([Smax // 3, Smax - 1][:B], jnp.int32)
    G = H // KV
    qr = q.reshape(B, KV, G, hd)
    kr = kc.transpose(0, 2, 1, 3)
    vr = vc.transpose(0, 2, 1, 3)
    o = dec_k.decode_attention_pallas(qr, kr, vr, positions, block_s=128,
                                      interpret=True)
    o = o.reshape(B, 1, H, hd)
    o_ref = ref.decode_attention_ref(q, kc, vc, positions)
    np.testing.assert_allclose(o, o_ref, rtol=2e-3, atol=2e-3)


def test_block_attention_matches_naive(rng):
    """The jnp flash-style sweep (the model's attention) vs naive oracle."""
    from repro.models import attention as attn
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q = _rand(rng, (B, S, H, hd), jnp.float32, 0.5)
    k = _rand(rng, (B, S, KV, hd), jnp.float32, 0.5)
    v = _rand(rng, (B, S, KV, hd), jnp.float32, 0.5)
    for window in (None, 48):
        got = attn.block_attention(q, k, v, causal=True, window=window,
                                   chunk=32)
        want = attn.naive_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
