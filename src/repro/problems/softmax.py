"""Multinomial softmax regression — the matrix-variable workload.

Global problem over row-partitioned multiclass data (A, y):

    min_X  sum_n [ logsumexp(a_n X) - (a_n X)_{y_n} ]  +  lam1 ||X||_1

with X in R^{d x C}.  On the wire the decision variable is the FLATTENED
matrix — ``n_features = d * C`` — so this workload stresses exactly what
the scalar-label problems cannot: C-times-larger ω-messages through the
fan-in tree, the compression codecs, and the byte-scaled ingest/egress
cost model (``SchedulerConfig.wire_d``/``compress`` earn their keep here
without any benchmark-side scaling fiction).

The scheduler never learns X is a matrix: ``solve`` reshapes internally
and the elementwise l1 master prox is shape-blind.  Data: C Gaussian
class blobs — per global row, a label y ~ U{0..C-1} and features
a = class_sep * mu_y + noise * N(0, I_d), with the class means mu drawn
from the off-row PRNG stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import prox
from repro.data.logreg import shard_rows
from repro.problems import base


class SoftmaxProblem(base.FistaShardProblem):
    """See module docstring.  h(Z) = lam1 ||Z||_1 (elementwise, flat)."""

    def __init__(self, n_samples: int = 1024, n_features: int = 64, *,
                 n_classes: int = 8, lam1: float = 1e-3,
                 class_sep: float = 1.5, noise: float = 1.0, seed: int = 0,
                 fista=None, fixed_inner=None, dtype="float32"):
        # the scheduler-facing vector is the flattened (d, C) matrix
        super().__init__(n_samples, n_features * n_classes, seed=seed,
                         fista=fista, fixed_inner=fixed_inner, dtype=dtype)
        self.d_in = int(n_features)
        self.n_classes = int(n_classes)
        self.lam1 = float(lam1)
        self.class_sep = float(class_sep)
        self.noise = float(noise)

    def class_means(self) -> jnp.ndarray:
        """(C, d) blob centers from the off-row PRNG stream."""
        return jax.random.normal(self._aux_key(0),
                                 (self.n_classes, self.d_in), jnp.float32)

    def _gen_shard(self, wid: int, n_workers: int):
        lo, hi = shard_rows(self.total_samples, n_workers, wid)
        mu = self.class_means()
        C, sep, sig = self.n_classes, self.class_sep, self.noise

        def row(key):
            ky, ka = jax.random.split(key)
            y = jax.random.randint(ky, (), 0, C)
            a = sep * mu[y] + sig * jax.random.normal(
                ka, (self.d_in,), jnp.float32)
            return a, y

        A, y = jax.vmap(row)(self._row_keys(lo, hi))
        return A.astype(self.dtype), y.astype(jnp.int32)

    def _loss_value_and_grad(self, shard, mask=None):
        # a zero-padded row has logits 0, so it would contribute log(C)
        # to the value and a nonzero resid row — the mask zeroes both
        # (the A=0 row already kills A.T @ resid, masking resid keeps the
        # contract exact by construction)
        A, y = shard
        d, C = self.d_in, self.n_classes

        def vg(x):
            X = x.reshape(d, C)
            logits = A @ X                                   # (N, C)
            lse = jax.scipy.special.logsumexp(logits, axis=1)
            picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
            f = jnp.sum(base.masked(mask, lse - picked))
            rows = None if mask is None else mask[:, None]
            resid = base.masked(rows, jax.nn.softmax(logits, axis=1)
                                - jax.nn.one_hot(y, C, dtype=x.dtype))
            return f, (A.T @ resid).reshape(-1)
        return vg

    # -- fused-kernel path (SchedulerConfig(kernel="pallas")) ---------------
    def _masked_kernel_loss_value_and_grad(self, shard, mask):
        # shards are already dense, so the default kernel_batch_shards
        # (= batch_shards) is the right staging; the fused wrapper is
        # ref-backed in every mode (see ops.fused_softmax_vjp) but keeps
        # this workload on the kernel call contract
        from repro.kernels import ops
        A, y = shard
        C = self.n_classes

        def vg(x):
            return ops.fused_softmax_vjp(A, y, x, n_classes=C, mask=mask)
        return vg

    def prox_h(self, v, t):
        return prox.prox_l1(v, t, self.lam1)

    @property
    def h_l1_lam(self):
        return self.lam1

    def h_value(self, z) -> float:
        return self.lam1 * float(jnp.sum(jnp.abs(z)))


base.register("softmax", SoftmaxProblem)
