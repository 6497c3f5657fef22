"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import prox as prox_mod
from repro.models import attention as attn_mod


def logistic_value_and_grad(A, b):
    """Closed-form value+grad of  f(x) = sum_n log(1 + exp(-b_n <a_n, x>))
    over dense rows A (N, D), b (N,): vg(x) -> (f, grad)."""
    def vg(x):
        margins = -b * (A @ x)                            # (N,)
        f = jnp.sum(jnp.logaddexp(0.0, margins))          # stable log1p(exp)
        grad = A.T @ (-b * jax.nn.sigmoid(margins))
        return f, grad
    return vg


def logistic_vjp_ref(a, b, mask, x):
    """a (N,D), b (N,1), mask (N,1), x (1,D) -> (loss (1,1), grad (1,D))."""
    m = -b * (a @ x.T)                                # (N,1)
    loss = jnp.sum(mask * jnp.logaddexp(0.0, m))
    c = mask * (-b) * jax.nn.sigmoid(m)               # (N,1)
    grad = c.T @ a                                    # (1,D)
    return loss.reshape(1, 1), grad


def svm_vjp_ref(a, b, mask, x, gamma):
    """Smoothed-hinge twin of ``logistic_vjp_ref`` (problems/svm.py's loss).
    a (N,D), b (N,1), mask (N,1), x (1,D) -> (loss (1,1), grad (1,D))."""
    m = b * (a @ x.T)                                 # (N,1)
    val = jnp.where(m >= 1.0, 0.0,
                    jnp.where(m <= 1.0 - gamma,
                              1.0 - m - gamma / 2,
                              (1.0 - m) ** 2 / (2 * gamma)))
    dldm = jnp.where(m >= 1.0, 0.0,
                     jnp.where(m <= 1.0 - gamma, -1.0, -(1.0 - m) / gamma))
    c = mask * dldm * b                               # (N,1)
    loss = jnp.sum(mask * val)
    return loss.reshape(1, 1), c.T @ a


def softmax_vjp_ref(a, y, mask, X):
    """Fused multinomial value+grad (problems/softmax.py's loss).
    a (N,D), y (N,) int labels, mask (N,1), X (D,C) -> (loss (1,1),
    grad (D,C)).  Masked rows contribute exactly zero to both."""
    C = X.shape[1]
    logits = a @ X                                    # (N, C)
    lse = jax.scipy.special.logsumexp(logits, axis=1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    loss = jnp.sum(mask[:, 0] * (lse - picked))
    resid = mask * (jax.nn.softmax(logits, axis=1)
                    - jax.nn.one_hot(y, C, dtype=X.dtype))  # (N, C)
    return loss.reshape(1, 1), a.T @ resid


def soft_threshold_ref(omega, z_old, thr):
    """omega, z_old (1,D), thr (1,1) -> (z_new, ssq (1,1), nnz (1,1))."""
    z_new = prox_mod.soft_threshold(omega, thr[0, 0])
    diff = z_new - z_old
    ssq = jnp.sum(diff * diff).reshape(1, 1)
    nnz = jnp.sum((z_new != 0.0).astype(jnp.float32)).reshape(1, 1)
    return z_new, ssq, nnz


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """q (B,S,H,hd), k/v (B,Skv,KV,hd) -> (B,S,H,hd).  Naive oracle."""
    return attn_mod.naive_attention(q, k, v, causal=causal, window=window)


def decode_attention_ref(q, k_cache, v_cache, positions):
    """q (B,1,H,hd), caches (B,Smax,KV,hd), positions (B,) -> (B,1,H,hd)."""
    return attn_mod.decode_attention(q, k_cache, v_cache, positions)
