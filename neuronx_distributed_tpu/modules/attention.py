"""Attention building blocks: rotary embeddings and multi-head attention.

Rotary utilities mirror the reference's ``modules/attention/utils.py``
(``precompute_freqs_cis:42``, llama3 frequency scaling ``apply_scaling:20``).
The attention core defaults to a pure-XLA softmax attention (which XLA fuses
well on TPU); the Pallas flash-attention kernel in :mod:`..ops.flash_attention`
is used automatically for longer sequences (reference:
``kernels/flash_attn.py:162``).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..parallel import layers as pl
from ..parallel import mesh as ps


def attention_dropout_seed(module: nn.Module, rate: float):
    """``(dropout_p, dropout_seed)`` gate shared by every model family:
    dropout is active iff ``rate > 0`` AND the module was given a
    ``"dropout"`` rng (no deterministic-flag threading). The uint32 seed
    feeds the counter-based mask hash
    (:func:`..ops.flash_attention.dropout_keep_mask`) — one draw per
    attention module, folded per layer by the scan rng split."""
    if rate > 0.0 and module.has_rng("dropout"):
        return rate, jax.random.bits(module.make_rng("dropout"), (),
                                     jnp.uint32)
    return 0.0, None


def apply_rope_scaling(freqs: jax.Array,
                       scale_factor: float = 8.0,
                       low_freq_factor: float = 1.0,
                       high_freq_factor: float = 4.0,
                       original_max_position: int = 8192) -> jax.Array:
    """Llama-3 style rope frequency scaling (reference
    ``modules/attention/utils.py:20``)."""
    low_freq_wavelen = original_max_position / low_freq_factor
    high_freq_wavelen = original_max_position / high_freq_factor
    wavelen = 2 * math.pi / freqs
    scaled = jnp.where(wavelen > low_freq_wavelen, freqs / scale_factor, freqs)
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    mid = (1 - smooth) * freqs / scale_factor + smooth * freqs
    is_mid = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
    return jnp.where(is_mid, mid, scaled)


def precompute_rope(head_dim: int, max_len: int, theta: float = 10000.0,
                    use_scaled: bool = False,
                    dtype: Any = jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables ``[max_len, head_dim//2]`` (reference
    ``precompute_freqs_cis:42``)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    if use_scaled:
        inv_freq = apply_rope_scaling(inv_freq)
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's inverse frequencies (arXiv:2309.00071, the checkpoints'
    ``rope_type: yarn``) over ``dim`` rotated values, ``[dim // 2]``
    float32: with ``f_i = theta^(-2i / dim)`` the pairs that turn more
    than ``beta_fast`` times in ``original_max_position`` positions keep
    ``f_i``, those that turn fewer than ``beta_slow`` times take ``f_i /
    factor``, and a linear ramp over the pair's index joins the two:
    ``lo = floor(dim ln(original / (beta_fast 2 pi)) / (2 ln theta))``,
    ``hi = ceil(dim ln(original / (beta_slow 2 pi)) / (2 ln theta))``,
    ``r_i = clip((i - lo) / (hi - lo), 0, 1)``, ``inv_freq_i = (f_i /
    factor) r_i + f_i (1 - r_i)``. The ``attention_factor`` that goes
    with it (``0.1 ln(factor) + 1`` by default) multiplies cos and sin,
    and is the caller's."""
    import numpy as np

    def turns_at(rotations):
        return (dim * math.log(original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_at(beta_fast)), 0)
    hi = min(math.ceil(turns_at(beta_slow)), dim - 1)
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return jnp.asarray(f / factor * ramp + f * (1 - ramp), jnp.float32)


def rope_rows(positions: jax.Array, head_dim: int, theta: float,
              inv_freq: Optional[jax.Array] = None):
    """cos and sin ``[T, head_dim // 2]`` at ``positions [T]``: the rows of
    :func:`precompute_rope`'s tables, without a table
    of ``max_position_embeddings`` (524,288) rows. ``inv_freq``
    (``[head_dim // 2]``) replaces the plain ``theta^(-2i / head_dim)``
    (:func:`yarn_inv_freq`)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(
            0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 positions: Optional[jax.Array] = None) -> jax.Array:
    """Apply rotary embedding. ``x: [B, S, N, D]``; cos/sin ``[L, D/2]``;
    ``positions: [B, S]`` (defaults to arange)."""
    b, s, n, d = x.shape
    if positions is None:
        cos_p = cos[:s][None, :, None, :]
        sin_p = sin[:s][None, :, None, :]
    else:
        cos_p = cos[positions][:, :, None, :]
        sin_p = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p,
                           x2 * cos_p + x1 * sin_p], axis=-1)
    return out.astype(x.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, K, D] -> [B, S, K*n_rep, D] (GQA head expansion)."""
    if n_rep == 1:
        return x
    b, s, k, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, s, k, n_rep, d)).reshape(b, s, k * n_rep, d)


def sdpa_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True,
                   segment_positions: Optional[jax.Array] = None,
                   scale: Optional[float] = None,
                   dropout_p: float = 0.0,
                   dropout_seed: Optional[jax.Array] = None) -> jax.Array:
    """Plain softmax attention, fp32 accumulation. ``q: [B, S, N, D]``,
    ``k/v: [B, S, N, D]`` (already GQA-expanded). Attention dropout uses the
    same counter-based (seed, head, q, k) hash as the flash kernels
    (``ops.flash_attention.dropout_keep_mask``), so sdpa and flash produce
    bit-identical masks for the same seed."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        kpos = jnp.arange(sk)
        if segment_positions is None:
            mask = (jnp.arange(sq)[:, None] >= kpos[None, :])[None, None]
        else:
            # [B, S] query positions -> [B, 1, Q, K]
            mask = (segment_positions[:, :, None] >= kpos[None, None, :]
                    )[:, None]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0:
        from ..ops.flash_attention import dropout_keep_mask, flat_bh

        bh = flat_bh(b, n)
        keep = dropout_keep_mask(
            jnp.asarray(dropout_seed, jnp.uint32), bh,
            jnp.arange(sq)[None, None, :, None],
            jnp.arange(sk)[None, None, None, :], sk, dropout_p)
        probs = jnp.where(keep, probs * (1.0 / (1.0 - dropout_p)), 0.0)
    out = jnp.einsum("bnqk,bknd->bqnd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
