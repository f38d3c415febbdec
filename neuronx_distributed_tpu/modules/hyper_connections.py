"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a residual path of ``n`` streams a
token that every sublayer reads, writes and mixes by maps of the token's
own streams.

With the residual ``X`` in ``R^{n x C}`` and one sublayer ``F`` (its own
``Phi``, ``b``, ``alpha``)::

    v      = vec(X);  r = rsqrt(mean(v^2) + rms_eps)
    m      = (r v) Phi,                 Phi in R^{nC x (2n + n^2)}, float32
    H_pre  = sigmoid(a_pre  m[0:n]      + b_pre)                     R^n
    H_post = 2 sigmoid(a_post m[n:2n]   + b_post)                    R^n
    M_0    = exp(clip(a_res mat(m[2n:]) + b_res, clamp))             R^{n x n}
    H_res  = M_iters,  M_t = cols(rows(M_{t-1}))   (Sinkhorn-Knopp)
    u      = sum_i H_pre[i] X[i]                   what F's norm reads
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(norm(u))

``H_res`` is (nearly) doubly stochastic, so the mix neither grows nor
shrinks what the streams carry together. The carry between layers is the
streams side by side, ``[..., n * C]`` in the model's dtype; a stream is
a slice of whole lanes of it, and the maps are float32.

The Sinkhorn iterations are unrolled over the ``n x n`` cells too: a
cell is an array of the tokens' shape, a row's or a column's sum ``n - 1``
adds of cells, so the forty normalisations are elementwise operations of
one fusion. Written as reductions over a ``[..., n, n]`` tensor each sum
is a fusion of its own and each division another: 95 launches a
sublayer in the compiled step (the AOT text for a described v5e), the
loop of launches this form is there to avoid.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def sinkhorn_knopp(cells, iters: int, eps: float):
    """``iters`` rounds of rows then columns over the positive matrix
    ``cells[i][j]`` (each an array of the tokens' shape): ``m_ij / (sum_j
    m_ij + eps)``, then ``m_ij / (sum_i m_ij + eps)``. Unrolled, sums and
    all: ``iters`` is a Python number and no reduction is traced."""
    n = len(cells)
    for _ in range(iters):
        rows = [functools.reduce(jnp.add, row) + eps for row in cells]
        cells = [[cells[i][j] / rows[i] for j in range(n)]
                 for i in range(n)]
        cols = [functools.reduce(jnp.add, [cells[i][j] for i in range(n)])
                + eps for j in range(n)]
        cells = [[cells[i][j] / cols[j] for j in range(n)]
                 for i in range(n)]
    return cells


def _streams(x: jax.Array, streams: int):
    """The streams of ``x [..., n * C]``, float32: slices of whole lanes."""
    return [p.astype(jnp.float32) for p in jnp.split(x, streams, axis=-1)]


def _weighted(weights, parts) -> jax.Array:
    """``sum_i weights[i] parts[i]``, a weight a token."""
    return functools.reduce(jnp.add, [w[..., None] * p
                                      for w, p in zip(weights, parts)])


def widen(x: jax.Array, streams: int) -> jax.Array:
    """``[..., C] -> [..., n * C]``: every stream starts as the token's
    embedding (arXiv:2409.19606)."""
    return jnp.tile(x, (1,) * (x.ndim - 1) + (streams,))


def read_out(x: jax.Array, streams: int) -> jax.Array:
    """``[..., n * C] -> [..., C]``: the streams' sum, added in float32,
    is what the final norm reads."""
    return functools.reduce(jnp.add, _streams(x, streams)).astype(x.dtype)


class HyperConnection(nn.Module):
    """One sublayer's three maps and what they do to the streams.
    Parameters, all float32: ``phi [n * C, 2n + n^2]`` (columns: pre,
    post, then ``H_res`` row by row), ``alpha [3]`` (pre, post, res) and
    ``bias [2n + n^2]``."""

    streams: int
    hidden: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    rms_eps: float = 1e-6

    def setup(self):
        n = self.streams
        self.phi = self.param(
            "phi", nn.with_partitioning(nn.initializers.normal(0.02),
                                        (None, None)),
            (n * self.hidden, 2 * n + n * n), jnp.float32)
        self.alpha = self.param("alpha", nn.initializers.ones_init(), (3,),
                                jnp.float32)
        self.bias = self.param("bias", nn.initializers.zeros_init(),
                               (2 * n + n * n,), jnp.float32)

    def maps(self, x: jax.Array):
        """``(H_pre, H_post, H_res)`` of the streams ``x [..., n * C]``:
        ``H_pre[i]``, ``H_post[i]`` and ``H_res[i][j]`` are float32
        arrays of the tokens' shape ``[...]``."""
        n = self.streams
        v = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1) + self.rms_eps)
        # (r v) Phi as r (v Phi): the scaled copy of v is never formed
        m = jnp.dot(v, self.phi, precision=jax.lax.Precision.HIGHEST)
        alpha = jnp.concatenate([jnp.broadcast_to(self.alpha[k], (width,))
                                 for k, width in enumerate((n, n, n * n))])
        z = (m * r[..., None]) * alpha + self.bias           # [..., 2n + n^2]
        z = [z[..., k] for k in range(2 * n + n * n)]
        pre = [jax.nn.sigmoid(z[i]) for i in range(n)]
        post = [2.0 * jax.nn.sigmoid(z[n + i]) for i in range(n)]
        res = [[jnp.exp(jnp.clip(z[2 * n + i * n + j], *self.clamp))
                for j in range(n)] for i in range(n)]
        return pre, post, sinkhorn_knopp(res, self.sinkhorn_iters, self.eps)

    def read(self, x: jax.Array, pre) -> jax.Array:
        """``u = sum_i H_pre[i] X[i]`` (``pre`` as :meth:`maps` gives
        it), ``[..., C]`` in ``x``'s dtype."""
        u = _weighted(pre, _streams(x, self.streams))
        return u.astype(x.dtype)

    def write(self, x: jax.Array, y: jax.Array, post, res) -> jax.Array:
        """``X'[i] = sum_j H_res[i][j] X[j] + H_post[i] y`` (``post``,
        ``res`` as :meth:`maps` gives them), the streams side by side in
        ``x``'s dtype."""
        parts = _streams(x, self.streams)
        y = y.astype(jnp.float32)
        out = [_weighted(res[i], parts) + post[i][..., None] * y
               for i in range(self.streams)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype)
