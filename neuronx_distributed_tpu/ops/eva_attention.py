"""EVA chunked linearized attention (Zheng et al., ICLR 2023,
arXiv:2302.04542) in the deterministic form of EvaByte's released code:
exact softmax attention inside the query's own window, one summary
(control variate) per chunk of every earlier window, one shared
normaliser.

Per head, with ``s = head_dim ** -0.5``, window ``W``, chunk ``C`` and
learned per-head vectors ``phi`` and ``mu``:

* chunk ``j`` holds positions ``[jC, (j+1)C)``; ``a_m = softmax over the
  chunk of (s * k_m . phi)``; ``ktil_j = sum_m a_m k_m + mu``;
  ``vtil_j = sum_m a_m v_m`` (keys after rotary);
* query ``i`` in window ``w = i // W`` attends the exact rows
  ``E = {m : wW <= m <= i}`` and the summaries ``S = {j : (j+1)C <= wW}``
  under one softmax.

Three entry points: :func:`chunk_summaries` (the pooling),
:func:`eva_attention_full` (no cache: windows by reshape, summaries by
one pooled einsum; training and the CPU tests) and
:func:`write_window_summaries` (the paged step: the summaries of the
windows a step completes, written as rows of the one pool). The paged
attention over both kinds of row is
:func:`.paged_attention.paged_attention` with ``window=``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunk_summaries(k, v, phi, mu, chunk: int, scale: float):
    """``k``, ``v`` ``[..., P, KV, D]`` with ``P`` a multiple of ``chunk``
    -> ``(ktil, vtil)`` ``[..., P // chunk, KV, D]`` float32. The pooling
    softmax runs in float32 (``mixedp_attn``)."""
    *lead, p, kv, d = k.shape
    kc = k.astype(jnp.float32).reshape(*lead, p // chunk, chunk, kv, d)
    vc = v.astype(jnp.float32).reshape(*lead, p // chunk, chunk, kv, d)
    logits = jnp.einsum("...cnd,nd->...cn", kc,
                        phi.astype(jnp.float32)) * scale
    a = jax.nn.softmax(logits, axis=-2)[..., None]
    return (jnp.sum(a * kc, axis=-3) + mu.astype(jnp.float32),
            jnp.sum(a * vc, axis=-3))


def eva_attention_full(q, k, v, phi, mu, window: int, chunk: int,
                       scale: float):
    """``q``, ``k``, ``v`` ``[B, S, N, D]`` (after rotary, positions
    ``0..S-1``, as many K/V heads as query heads) -> ``[B, S, N, D]``
    float32. The sequence is padded at its end to whole windows (to whole
    chunks where it is shorter than one); a pad is later than every real
    query and lies in no window a real query sees summarised."""
    b, s, n, d = q.shape
    span = window if s > window else -(-s // chunk) * chunk
    pad = -s % span
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    nw = (s + pad) // span
    qf, kf, vf = (x.astype(jnp.float32).reshape(b, nw, span, n, d)
                  for x in (q, k, v))
    ktil, vtil = chunk_summaries(k, v, phi, mu, chunk, scale)  # [B, J, N, D]
    exact = jnp.einsum("bwqnd,bwknd->bwnqk", qf, kf) * scale
    causal = jnp.arange(span)[:, None] >= jnp.arange(span)[None, :]
    exact = jnp.where(causal, exact, -jnp.inf)
    pooled = jnp.einsum("bwqnd,bjnd->bwnqj", qf, ktil) * scale
    earlier = (jnp.arange(ktil.shape[1])[None, :] // (span // chunk)
               < jnp.arange(nw)[:, None])                      # [nw, J]
    pooled = jnp.where(earlier[None, :, None, None, :], pooled, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([exact, pooled], axis=-1),
                           axis=-1)
    out = (jnp.einsum("bwnqk,bwknd->bwqnd", probs[..., :span], vf)
           + jnp.einsum("bwnqj,bjnd->bwqnd", probs[..., span:], vtil))
    return out.reshape(b, nw * span, n, d)[:, :s]


def write_window_summaries(k_pool, v_pool, layer, roll, phi, mu, chunk: int,
                           scale: float):
    """The paged step's summarisation at layer ``layer`` of the stacks
    ``[L, num_blocks, block_size, KV, D]``: ``roll`` is
    :func:`..inference.paging.window_roll`'s ``(any, src [S, bpw], dst
    [S])``. For each slot whose window this step completes, its ``bpw``
    ring blocks (already holding this step's rows) are gathered at
    ``(layer, block)``, pooled into ``block_size`` summary row pairs and
    written as the block ``(layer, dst)`` of the same stacks. A step
    that completes no window skips the reads and the pooling (the
    ``cond``, which hands back the summaries and never a pool), and its
    writes are all dropped: ``dst == num_blocks`` is out of bounds of
    the block dimension, whatever the layer."""
    any_done, src, dst = roll
    bs, kv, d = k_pool.shape[2:]

    def pooled(kp, vp):
        def one_slot(blocks):
            kt, vt = chunk_summaries(
                kp[layer, blocks].reshape(-1, kv, d),
                vp[layer, blocks].reshape(-1, kv, d), phi, mu, chunk, scale)
            return kt.astype(kp.dtype), vt.astype(vp.dtype)

        return jax.lax.map(one_slot, src)

    def nothing(kp, vp):
        z = jnp.zeros((src.shape[0], bs, kv, d), kp.dtype)
        return z, z.astype(vp.dtype)

    ktil, vtil = jax.lax.cond(any_done, pooled, nothing, k_pool, v_pool)
    return (k_pool.at[layer, dst].set(ktil, mode="drop"),
            v_pool.at[layer, dst].set(vtil, mode="drop"))
