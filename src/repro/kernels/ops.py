"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: the Pallas path compiles only for TPU backends.  On this
CPU container the wrappers run the kernels in ``interpret=True`` mode when
``REPRO_PALLAS=interpret`` is set (used by the kernel test-suite), and fall
back to the jnp oracle otherwise — so model code can call these
unconditionally and the dry-run (CPU lowering) never tries to lower Mosaic.

Padding/layout glue lives here so the kernels keep hardware-aligned shapes:
rows to the row-tile multiple, features to the 128-lane multiple, GQA
reshapes for attention.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import logistic_vjp as lv_k
from repro.kernels import ref
from repro.kernels import soft_threshold as st_k


def _mode() -> str:
    """'pallas' (TPU), 'interpret' (forced), or 'ref' (CPU default).

    Unrecognized ``REPRO_PALLAS`` values RAISE instead of silently falling
    through to the backend default — a typo ('interperet') would otherwise
    quietly run the jnp oracle while claiming kernel coverage."""
    env = os.environ.get("REPRO_PALLAS", "")
    if env in ("interpret", "ref", "pallas"):
        return env
    if env:
        raise ValueError(
            f"REPRO_PALLAS={env!r} is not a recognized mode; use 'ref', "
            f"'interpret', or 'pallas' (or unset for the backend default)")
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# fused margin-loss value+grad (logistic / smoothed hinge)
# ---------------------------------------------------------------------------


def margin_layout(n_rows: int, n_features: int):
    """(padded rows, padded features, row tile) in which the margin
    kernels stream an (n_rows, n_features) shard.

    Features pad to the 128-lane multiple.  Small shards tile to the f32
    sublane multiple (8) rather than the full default row tile — a W=1024
    fleet of 8-row lanes must not pad every lane to 256 rows — and wide
    shards take the row tile that fits VMEM (``block_rows_for``).  A
    shard already in this layout maps to itself."""
    dp = _round_up(n_features, 128)
    br = min(lv_k.block_rows_for(dp), _round_up(n_rows, 8))
    return _round_up(n_rows, br), dp, br


def _pad_f32(a, shape):
    """Zero-pad a 2-D array to ``shape`` as f32; one already of that
    shape is not copied."""
    a = a.astype(jnp.float32)
    if a.shape == shape:
        return a
    return jnp.zeros(shape, jnp.float32).at[:a.shape[0], :a.shape[1]].set(a)


@functools.partial(jax.jit, static_argnames=("loss", "gamma", "mode"))
def _margin_impl(A, b, x, mask, *, loss, gamma, mode):
    D = x.shape[0]
    Np, Dp, br = margin_layout(*A.shape)
    # a shard staged in the kernel layout (see margin_layout) streams as
    # is: padding it here would copy A on every FISTA iteration
    a_p = _pad_f32(A, (Np, Dp))
    b_p = _pad_f32(b[:, None], (Np, 1))
    m_p = _pad_f32(mask[:, None], (Np, 1))
    x_p = _pad_f32(x[None, :], (1, Dp))
    if mode == "ref":
        if loss == "logistic":
            f, grad = ref.logistic_vjp_ref(a_p, b_p, m_p, x_p)
        else:
            f, grad = ref.svm_vjp_ref(a_p, b_p, m_p, x_p, gamma)
    else:
        fn = (lv_k.logistic_vjp_pallas if loss == "logistic"
              else functools.partial(lv_k.svm_vjp_pallas, gamma=gamma))
        f, grad = fn(a_p, b_p, m_p, x_p, block_rows=br,
                     interpret=(mode == "interpret"))
    return f[0, 0], grad[0, :D]


def _margin_dispatch(A, b, x, mask, *, loss, gamma):
    """Shared entry: accepts a leading worker axis (A (W,N,D), b/mask
    (W,N), x (W,D)) and per-lane row masks; ``jax.vmap`` lifts the batch
    onto the Pallas grid, so all W lanes run in ONE kernel launch."""
    mode = _mode()
    one = functools.partial(_margin_impl, loss=loss, gamma=gamma, mode=mode)
    if A.ndim == 3:
        if mask is None:
            mask = jnp.ones(A.shape[:2], jnp.float32)
        return jax.vmap(one)(A, b, x, mask)
    if mask is None:
        mask = jnp.ones((A.shape[0],), jnp.float32)
    return one(A, b, x, mask)


def fused_logistic_vjp(A, b, x, *, mask=None):
    """Single-pass loss+grad of sum_n mask_n * log1p(exp(-b_n <a_n, x>)).

    A (N, D) f32, b (N,) ±1, x (D,); ``mask`` an optional {0,1} row mask
    (padded rows contribute exactly zero).  A leading worker axis batches:
    A (W, N, D), b/mask (W, N), x (W, D) -> (loss (W,), grad (W, D)).
    A may come staged in ``margin_layout(N, D)``: rows already padded
    (with mask 0) and zero columns past ``len(x)``."""
    return _margin_dispatch(A, b, x, mask, loss="logistic", gamma=0.0)


def fused_svm_vjp(A, b, x, *, gamma: float, mask=None):
    """Smoothed-hinge twin of ``fused_logistic_vjp`` (problems/svm.py's
    loss; ``gamma`` the smoothing width).  Same shapes/batching/masking."""
    return _margin_dispatch(A, b, x, mask, loss="hinge", gamma=float(gamma))


def logistic_value_and_grad(A, b):
    """Drop-in replacement for data.logreg.logistic_value_and_grad that
    routes through the fused kernel."""
    def vg(x):
        return fused_logistic_vjp(A, b, x)
    return vg


# ---------------------------------------------------------------------------
# fused softmax value+grad (ref-backed)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_classes", "mode"))
def _softmax_impl(A, y, x, mask, *, n_classes, mode):
    # mode rides along only so the jit cache stays keyed consistently with
    # the other wrappers; every mode runs the jnp oracle (see below)
    del mode
    D = A.shape[1]
    X = x.reshape(D, n_classes)
    f, grad = ref.softmax_vjp_ref(A, y, mask[:, None], X)
    return f[0, 0], grad.reshape(-1)


def fused_softmax_vjp(A, y, x, *, n_classes: int, mask=None):
    """Fused multinomial value+grad with the same wrapper contract as the
    margin kernels: A (N, D), y (N,) int, x the FLATTENED (D*C,) variable,
    optional row mask; leading worker axis batches.

    No Pallas body yet — padding the class dim to the 128-lane multiple
    changes logsumexp (every padded class contributes exp(0)) and would
    need a class mask woven through the reduction, while C is small and
    XLA already fuses the (N,D)@(D,C) pair well.  All three modes run the
    jnp oracle (``ref.softmax_vjp_ref``); the differential harness still
    exercises this path so a future Pallas port lands against pinned
    numbers."""
    mode = _mode()
    one = functools.partial(_softmax_impl, n_classes=n_classes, mode=mode)
    if A.ndim == 3:
        if mask is None:
            mask = jnp.ones(A.shape[:2], jnp.float32)
        return jax.vmap(one)(A, y, x, mask)
    if mask is None:
        mask = jnp.ones((A.shape[0],), jnp.float32)
    return one(A, y, x, mask)


# ---------------------------------------------------------------------------
# fused soft-threshold z-update
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _softthr_impl(omega, z_old, thr, *, mode):
    D = omega.shape[0]
    Dp = _round_up(D, 128)
    o_p = jnp.zeros((1, Dp), jnp.float32).at[0, :D].set(omega)
    z_p = jnp.zeros((1, Dp), jnp.float32).at[0, :D].set(z_old)
    t = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    if mode == "ref":
        z_new, ssq, nnz = ref.soft_threshold_ref(o_p, z_p, t)
    else:
        z_new, ssq, nnz = st_k.soft_threshold_pallas(
            o_p, z_p, t, interpret=(mode == "interpret"))
    return z_new[0, :D], ssq[0, 0], nnz[0, 0]


def fused_z_update(omega_bar, z_old, thr):
    """z_new = S(omega_bar; thr); also returns ||z_new - z_old||^2 and nnz.

    omega_bar, z_old (D,); thr scalar.  One HBM pass on TPU."""
    return _softthr_impl(omega_bar, z_old, thr, mode=_mode())


# ---------------------------------------------------------------------------
# flash attention (train/prefill)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_kv",
                                    "mode"))
def _flash_impl(q, k, v, *, causal, window, block_q, block_kv, mode):
    B, S, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    # (B,S,H,hd) -> (B,KV,G,S,hd) -> (B*KV, G*S, hd)
    qr = (q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * KV, G * S, hd))
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    o = fa_k.flash_attention_pallas(
        qr, kr, vr, seq_q=S, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv,
        interpret=(mode == "interpret"))
    return (o.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)
            .reshape(B, S, H, hd))


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 512, block_kv: int = 512):
    """q (B,S,H,hd), k/v (B,Skv,KV,hd) -> (B,S,H,hd)."""
    return _flash_impl(q, k, v, causal=causal, window=window,
                       block_q=block_q, block_kv=block_kv, mode=_mode())


# ---------------------------------------------------------------------------
# decode attention (one token vs KV cache)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_s", "mode"))
def _decode_impl(q, k_cache, v_cache, positions, *, block_s, mode):
    B, _, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    if mode == "ref":
        return ref.decode_attention_ref(q, k_cache, v_cache, positions)
    qr = q.reshape(B, KV, G, hd)
    kr = k_cache.transpose(0, 2, 1, 3)                # (B,KV,Smax,hd)
    vr = v_cache.transpose(0, 2, 1, 3)
    o = dec_k.decode_attention_pallas(
        qr, kr, vr, positions.astype(jnp.int32), block_s=block_s,
        interpret=(mode == "interpret"))
    return o.reshape(B, 1, H, hd)


def decode_attention(q, k_cache, v_cache, positions, *, block_s: int = 512):
    """q (B,1,H,hd), caches (B,Smax,KV,hd), positions (B,) -> (B,1,H,hd)."""
    return _decode_impl(q, k_cache, v_cache, positions, block_s=block_s,
                        mode=_mode())
