"""Fused margin-loss value+gradient Pallas TPU kernels (logistic + hinge).

The ADMM worker's inner-loop hot spot is the FISTA gradient evaluation
  f(x)    = sum_n l(b_n <a_n, x>)
  grad(x) = A^T (l'(b Ax) * b)
which naively is two full passes over A (one for Ax, one for A^T c).  These
kernels fuse both into ONE pass: for each row tile of A held in VMEM they
compute the margins (MXU matvec), the loss partial and the coefficient
vector (VPU elementwise/transcendentals), and immediately apply the
transposed-tile matvec for the gradient contribution — so A is streamed
from HBM exactly once per FISTA iteration.  Loss and gradient accumulate
in VMEM across the (sequential) row-tile grid.

Two margin losses share the one kernel body (static ``loss`` switch):

  * ``logistic`` — l(m) = log(1 + exp(-m)), the paper's workload;
  * ``hinge``    — the quadratically-smoothed (Huberized) hinge of
    problems/svm.py, l_gamma(m) piecewise in (1 - m).

TPU adaptation (DESIGN.md §7): the paper's CSR-sparse rows (p=0.001) become
dense VMEM tiles — gather/scatter on the sparse structure would idle the MXU;
the row tile shrinks with the feature dim so a tile fits VMEM
(``block_rows_for``).

Padding contract (handled by ops.fused_*_vjp): rows are padded with
mask=0 (excluded from loss and grad), the feature dim with zero columns.
A leading worker axis batches via ``jax.vmap`` — Pallas lifts the batch
dimension onto the grid, so all W lanes run in one kernel launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Row-tile height for narrow shards; ``block_rows_for`` shrinks it for wide
# ones.
DEFAULT_BLOCK_ROWS = 256
# Bytes one f32 A tile may take.  The pipeline double-buffers it, and the
# chip compiler's default scoped-VMEM limit is 16 MiB: a 256 x 10112 tile
# (10.4 MB, ~20 MB double-buffered) is refused at the paper's width, a
# 128 x 10112 one compiles.  The rest of the limit holds the (TN, 1) b and
# mask tiles, x and the gradient accumulator.
A_TILE_BYTES = 6 * 2**20


def block_rows_for(d_padded: int) -> int:
    """Row tile for a lane-padded feature width: ``DEFAULT_BLOCK_ROWS``
    halved until the f32 A tile fits ``A_TILE_BYTES``, but never below
    the f32 sublane multiple (8).  256 rows up to D=6144, 128 up to
    D=12288."""
    br = DEFAULT_BLOCK_ROWS
    while br > 8 and br * d_padded * 4 > A_TILE_BYTES:
        br //= 2
    return br


# Both matvecs multiply in full f32 on the MXU: the gather path the kernel
# is checked against multiplies in f32, and the kernel is bound by HBM
# traffic (2 flops per 4-byte element of A), so the extra passes are free.
_PRECISION = jax.lax.Precision.HIGHEST


def _margin_kernel(loss: str, gamma: float,
                   a_ref, b_ref, mask_ref, x_ref, loss_ref, grad_ref):
    i = pl.program_id(0)

    a = a_ref[...]                                   # (TN, D)
    b = b_ref[...]                                   # (TN, 1)
    mask = mask_ref[...]                             # (TN, 1)
    x = x_ref[...]                                   # (1, D)

    # signed activation <a_n, x>   (MXU: (TN,D) @ (D,1))
    ax = jax.lax.dot_general(a, x.T, (((1,), (0,)), ((), ())),
                             precision=_PRECISION,
                             preferred_element_type=jnp.float32)  # (TN,1)
    if loss == "logistic":
        # l(m) = log1p(exp(-m)) at m = b*ax; stable via logaddexp
        neg_m = -b * ax
        val = jnp.logaddexp(0.0, neg_m)
        dldax = (-b) * jax.nn.sigmoid(neg_m)         # d l / d ax
    elif loss == "hinge":
        # smoothed hinge (Rennie & Srebro '05), gamma the smoothing width
        m = b * ax
        val = jnp.where(m >= 1.0, 0.0,
                        jnp.where(m <= 1.0 - gamma,
                                  1.0 - m - gamma / 2,
                                  (1.0 - m) ** 2 / (2 * gamma)))
        dldm = jnp.where(m >= 1.0, 0.0,
                         jnp.where(m <= 1.0 - gamma,
                                   -1.0, -(1.0 - m) / gamma))
        dldax = dldm * b
    else:  # pragma: no cover - static arg, guarded by the wrappers
        raise ValueError(f"unknown margin loss {loss!r}")

    loss_part = jnp.sum(mask * val)
    c = mask * dldax                                 # (TN,1)
    # gradient partial: A^T c  (MXU: (1,TN) @ (TN,D))
    gpart = jax.lax.dot_general(c.T, a, (((1,), (0,)), ((), ())),
                                precision=_PRECISION,
                                preferred_element_type=jnp.float32)  # (1,D)

    @pl.when(i == 0)
    def _init():
        loss_ref[...] = jnp.zeros_like(loss_ref)
        grad_ref[...] = jnp.zeros_like(grad_ref)

    loss_ref[...] += loss_part.reshape(1, 1)
    grad_ref[...] += gpart


def _margin_vjp_pallas(a, b, mask, x, *, loss: str, gamma: float,
                       block_rows: int, interpret: bool):
    N, D = a.shape
    assert N % block_rows == 0 and D % 128 == 0, (N, D)
    grid = (N // block_rows,)
    return pl.pallas_call(
        functools.partial(_margin_kernel, loss, gamma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(a, b, mask, x)


def logistic_vjp_pallas(a, b, mask, x, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = False):
    """a (N, D), b (N, 1), mask (N, 1), x (1, D); N % block_rows == 0,
    D % 128 == 0.  Returns (loss (1,1) f32, grad (1,D) f32)."""
    return _margin_vjp_pallas(a, b, mask, x, loss="logistic", gamma=0.0,
                              block_rows=block_rows, interpret=interpret)


def svm_vjp_pallas(a, b, mask, x, *, gamma: float,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """Smoothed-hinge twin of ``logistic_vjp_pallas`` (problems/svm.py's
    loss); same shape/padding contract, ``gamma`` the smoothing width."""
    return _margin_vjp_pallas(a, b, mask, x, loss="hinge", gamma=gamma,
                              block_rows=block_rows, interpret=interpret)
