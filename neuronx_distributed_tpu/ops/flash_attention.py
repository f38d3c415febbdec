"""Memory-efficient (flash) attention.

Analogue of the reference's NKI flash attention wrapper
(``kernels/flash_attn.py:162`` → ``nki.kernels.attention.flash_fwd/bwd``).

Two implementations behind one signature (``flash_attention``). The XLA
one is blockwise online-softmax attention expressed with ``lax.scan`` over
KV blocks — O(S) memory instead of O(S²), fp32 accumulation, its backward a
second scan from the saved log-sum-exp. The hand-tiled Pallas (Mosaic)
kernels — one forward, two backward — take the call on the TPU when the
shapes tile; the scan formulation is their golden reference and fallback
(the reference keeps torch fallbacks for its NKI kernels the same way).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.device import on_tpu
from .pallas_utils import compiler_params as _compiler_params


# ---------------------------------------------------------------------------
# Attention dropout: counter-based keep masks. The reference threads a seed
# into its NKI kernels the same way (``kernels/flash_attn.py:30,54`` passes
# seed + dropout_p into flash_fwd/flash_attn_bwd). Here the mask for element
# (head, q, k) is a pure integer hash of (seed, head, q, k) — a murmur3-style
# finalizer in plain uint32 ops — so the SAME mask regenerates anywhere it is
# needed: the Pallas forward kernel, both Pallas backward kernels, the XLA
# fallback scan, and ``sdpa_reference``. No PRNG state to carry, no [S, S]
# mask to materialise, and (unlike ``pltpu.prng_random_bits``) it works in
# interpret mode on CPU, so CI exercises the exact TPU mask path.
# ---------------------------------------------------------------------------

def dropout_keep_mask(seed, head_idx, q_pos, k_pos, sk: int, p: float):
    """Boolean keep-mask from integer coordinate arrays (broadcastable).

    ``seed``: uint32 scalar. ``head_idx``: flat batch*head index. The
    per-element counter is ``q*sk + k`` (unique while sq*sk < 2**32, i.e.
    sequences to 64K) xored with a per-(seed, head) hash, then mixed with
    the murmur3 finalizer. Keep probability is ``1 - p``.
    """
    hseed = (seed.astype(jnp.uint32)
             + head_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    hseed = (hseed ^ (hseed >> 16)) * jnp.uint32(0x21F0AAAD)
    x = (q_pos.astype(jnp.uint32) * jnp.uint32(sk)
         + k_pos.astype(jnp.uint32)) ^ hseed
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x >= jnp.uint32(round(p * 0xFFFFFFFF))


def flat_bh(b: int, n: int) -> jax.Array:
    """``[B, N, 1, 1]`` flat batch*head coordinate for dropout masks.

    Every mask site (sdpa_reference, the XLA flash scan, ring attention)
    must use this exact batch-major layout — cross-implementation mask
    parity (and ring's bit-consistency with the dense model) depends on
    all of them agreeing.
    """
    return (jnp.arange(b)[:, None] * n
            + jnp.arange(n)[None, :])[..., None, None]


def _block_attention(q, k_blk, v_blk, q_pos, k_pos_start, block_k, causal,
                     scale):
    """Scores and partial PV for one KV block. q: [B,N,Sq,D],
    k_blk/v_blk: [B,N,Bk,D]."""
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        k_pos = k_pos_start + jnp.arange(block_k)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return s


def _flash_xla_impl(q, k, v, causal, block_k, scale, dropout_p,
                    dropout_seed):
    """Blockwise-scan forward; returns ``(out [B,S,N,D], lse [B,N,S])``."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    block_k = min(block_k, sk)
    if sk % block_k != 0:
        # fall back to one block covering everything (static shapes only)
        block_k = sk
    nblocks = sk // block_k

    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,N,Sq,D]
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    q_pos = jnp.arange(sq)
    if dropout_p > 0.0:
        bh = flat_bh(b, n)
        seed = jnp.asarray(dropout_seed, jnp.uint32)

    kb = kt.reshape(b, n, nblocks, block_k, d)
    vb = vt.reshape(b, n, nblocks, block_k, d)

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, idx = blk
        s = _block_attention(qt, k_blk, v_blk, q_pos, idx * block_k, block_k,
                             causal, scale)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m_new = -inf) against NaNs
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        correction = jnp.where(jnp.isfinite(m_prev),
                               jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_prev * correction + jnp.sum(p, axis=-1)
        if dropout_p > 0.0:
            keep = dropout_keep_mask(
                seed, bh, q_pos[None, None, :, None],
                (idx * block_k + jnp.arange(block_k))[None, None, None, :],
                sk, dropout_p)
            p_acc = jnp.where(keep, p, 0.0)
        else:
            p_acc = p
        acc = acc * correction[..., None] + jnp.einsum(
            "bnqk,bnkd->bnqd", p_acc, v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, n, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, n, sq), jnp.float32)
    acc0 = jnp.zeros((b, n, sq, d), jnp.float32)
    blks = (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0),
            jnp.arange(nblocks))
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0), blks)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if dropout_p > 0.0:
        out = out * (1.0 / (1.0 - dropout_p))
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_xla(q, k, v, seed, causal, block_k, scale, dropout_p):
    out, _ = _flash_xla_impl(q, k, v, causal, block_k, scale, dropout_p,
                             seed[0])
    return out


def _flash_xla_vjp_fwd(q, k, v, seed, causal, block_k, scale, dropout_p):
    out, lse = _flash_xla_impl(q, k, v, causal, block_k, scale, dropout_p,
                               seed[0])
    # same named residuals as the Pallas path, so what a rematerialised
    # layer keeps (utils/remat.py) is NOT a silent no-op when shapes demote
    # the dispatch to the XLA fallback (review finding r5): the saved
    # out+lse feed _flash_bwd_from_lse directly.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, seed, out, lse)


def _flash_xla_vjp_bwd(causal, block_k, scale, dropout_p, res, g):
    import numpy as np

    q, k, v, seed, out, lse = res
    dq, dk, dv = _flash_bwd_from_lse(q, k, v, out, lse, g, causal, block_k,
                                     scale, dropout_p, seed[0])
    return dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0)


_flash_xla.defvjp(_flash_xla_vjp_fwd, _flash_xla_vjp_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_k", "scale",
                                    "dropout_p"))
def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_k: int = 512,
                        scale: Optional[float] = None,
                        dropout_p: float = 0.0,
                        dropout_seed: Optional[jax.Array] = None) -> jax.Array:
    """Blockwise attention. ``q/k/v: [B, S, N, D]`` (kv already GQA-expanded);
    returns ``[B, S, N, D]``. ``dropout_p``: attention-probability dropout
    (the softmax normaliser sums UNdropped probabilities; dropped entries are
    zeroed and survivors rescaled by 1/(1-p), standard semantics; the
    counter-based mask regenerates identically in the backward)."""
    d = q.shape[-1]
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = (jnp.asarray(dropout_seed, jnp.uint32).reshape((1,))
            if dropout_p > 0.0 else jnp.zeros((1,), jnp.uint32))
    # clamp HERE (not just in the impl) so the custom_vjp backward sees the
    # same static block_k the forward actually used — _flash_bwd_from_lse
    # reshapes k/v by it (review finding r5: sk % block_k != 0 would crash
    # the backward with a size-mismatched reshape)
    sk = k.shape[1]
    block_k = min(block_k, sk)
    if sk % block_k != 0:
        block_k = sk
    return _flash_xla(q, k, v, seed, causal, block_k, scale_, dropout_p)


# ---------------------------------------------------------------------------
# Pallas (Mosaic) TPU kernels — the hand-tiled fast path: the forward here,
# the two backward kernels (dq; dk and dv) further down, all three from the
# same (seed, head, q, k) dropout mask. The forward's grid is
# (batch*heads, q_blocks, k_blocks) with the KV dim innermost (sequential on
# TPU): K/V stream through VMEM one (block_k, d) tile at a time while
# m/l/acc accumulate in VMEM scratch — constant VMEM regardless of sequence
# length. It returns the output and each row's log-sum-exp; the backward
# kernels recompute p = exp(s - lse) from those, and the XLA scan above
# stays as the fallback and the golden reference of both directions.
#
# Layout of the statistics. A score tile is [block_q, block_k] with a query
# row along a sublane, and everything the online softmax does with a row's
# maximum m and sum l is a row-wise operand against that tile or against
# the [block_q, d] accumulator. So m and l live as [block_q, 128] scratch,
# a row's value repeated along its lanes: the lane reductions (max, sum)
# leave their result there, the running maximum, the correction and the
# sum are whole-vreg elementwise operations, and the operand against the
# tile is the same vregs side by side (``_along_lanes``). Kept as
# one-dimensional [block_q] (values along the lanes, as the lse output
# wants them) every tile moved 512 values from sublanes to lanes to store
# them and back to use them: 1,168 of the tile's 2,758 bundles were those
# permutations' and their stores', for 0.68 us of MXU work. The one move
# left is ``_rows_to_lanes`` at ``_finalize``, once a query block.
# ---------------------------------------------------------------------------

def _tile_keep_mask(seed_ref, head, qi, kb, block_q, block_k, sk, dropout_p):
    """Regenerate the (block_q, block_k) keep mask for one tile — identical
    in the forward and both backward kernels (coords are global)."""
    shape = (block_q, block_k)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    return dropout_keep_mask(seed_ref[0], head, q_pos, k_pos, sk, dropout_p)


# A masked score. Finite, so ``exp(s - m)`` is zero on a masked entry by
# itself and the running maximum is a number from the first tile on: the
# forward pays no ``isfinite`` select over the score tile. A row whose
# maximum never rose above it met no live key (``_finalize``).
_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128


def _along_lanes(stat, width: int):
    """A row statistic ``[rows, 128]`` (a row's value in every lane) as an
    operand against ``[rows, width]``: the same vregs side by side where
    ``width`` is whole lanes, no move across sublanes or lanes."""
    if width == _LANES:
        return stat
    if width % _LANES == 0:
        return jnp.concatenate([stat] * (width // _LANES), axis=1)
    # interpret mode's loose blocks
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _mxu_pair(a, b):
    """Two operands of one product in the narrower's type, bf16 at the
    least. At default precision the MXU runs a float32 product in one
    pass over operands it rounds to bf16 (bit for bit what ``astype``
    gives: chip probe, PR 58), so beside a bf16 operand the other loses
    nothing by being handed over in bf16, at half the vregs."""
    t = jnp.promote_types(jnp.bfloat16, min(a.dtype, b.dtype,
                                            key=lambda t: t.itemsize))
    return a.astype(t), b.astype(t)


def _rows_to_lanes(stat):
    """``[rows, 128]`` row statistic -> ``[1, rows]``: row ``r`` of a
    chunk of 128 rows keeps lane ``r`` alone and the chunk's rows fold
    into one, so the move is a select and maxima over whole vregs, not a
    permutation a row."""
    rows = stat.shape[0]
    if rows % _LANES:
        return stat[:, 0][None, :]  # interpret mode's loose blocks
    shape = (_LANES, _LANES)
    own = (lax.broadcasted_iota(jnp.int32, shape, 0)
           == lax.broadcasted_iota(jnp.int32, shape, 1))
    return jnp.concatenate(
        [jnp.max(jnp.where(own, stat[c:c + _LANES], -jnp.inf), axis=0,
                 keepdims=True) for c in range(0, rows, _LANES)], axis=1)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref, m_ref,
                      l_ref, acc_ref, *, block_q: int, block_k: int,
                      num_kb: int, causal: bool, scale: float,
                      dropout_p: float, sk: int):
    from jax.experimental import pallas as pl

    head = pl.program_id(0)  # hoisted: program_id has no lowering inside
    qi = pl.program_id(1)    # pl.when bodies in interpret mode
    kb = pl.program_id(2)
    d = acc_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(on_diagonal: bool):
        # the product takes its operands as they come (bf16 from the
        # models) and the scale follows it
        s = jax.lax.dot_general(*_mxu_pair(q_ref[0], k_ref[0]),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if on_diagonal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _MASK_VALUE)
        m_prev = m_ref[:]                                   # [BQ, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _along_lanes(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            # normaliser l accumulates UNdropped p; only the PV accumulation
            # sees the mask (survivor rescale happens once, in _finalize)
            keep = _tile_keep_mask(seed_ref, head, qi, kb,
                                   block_q, block_k, sk, dropout_p)
            p = jnp.where(keep, p, 0.0)
        acc_ref[:] = acc_ref[:] * _along_lanes(corr, d) + jax.lax.dot_general(
            *_mxu_pair(p, v_ref[0]), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # the first and the last key of the tile against the block's
        # first and last query: wholly above the diagonal a tile is
        # skipped, wholly under it nothing is masked, and only a tile the
        # diagonal crosses pays the iotas, the compare and the select
        crosses = kb * block_k + block_k - 1 > qi * block_q
        live = kb * block_k <= qi * block_q + block_q - 1
        pl.when(jnp.logical_not(crosses))(lambda: _tile(False))
        pl.when(jnp.logical_and(crosses, live))(lambda: _tile(True))
    else:
        _tile(False)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        inv_keep = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0
        m = m_ref[:]
        l = jnp.maximum(l_ref[:], 1e-30)
        # a row that met no live key (its sums are of masked entries):
        # zero output and lse = -inf
        met = m > _MASK_VALUE
        o_ref[0] = (acc_ref[:] * _along_lanes(
            jnp.where(met, inv_keep / l, 0.0), d)).astype(o_ref.dtype)
        lse = jnp.where(met, m + jnp.log(l), -jnp.inf)
        # log-sum-exp per query row (softmax stats for the flash backward),
        # the one place the statistics leave the rows for the lanes.
        # lse block is (1, 1, block_q): 3D so the sublane dim (=1) equals the
        # array dim — Mosaic's (8, 128) tiling rule for 2D blocks would
        # reject a (1, block_q) block on a (b*n, sq) array.
        lse_ref[0] = _rows_to_lanes(lse)


def _causal_kv_index(causal, block_q, block_k):
    """BlockSpec index_map for KV tiles in a (i, q_block, k_block) grid.

    Causal truncation: skipped (above-diagonal) iterations clamp the KV
    block index to the q-block's diagonal — Mosaic elides the DMA when
    consecutive iterations map to the same block, so masked blocks cost
    neither compute (``pl.when``) nor HBM traffic."""
    if not causal:
        return lambda i, j, kb: (i, kb, 0)
    return lambda i, j, kb: (
        i, jnp.minimum(kb, (j * block_q + block_q - 1) // block_k), 0)


def _flash_pallas_fwd(q, k, v, seed, causal, block_q, block_k, scale,
                      interpret=False, dropout_p=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, n, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * n, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * n, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * n, sk, d)
    num_kb = sk // block_k
    grid = (b * n, sq // block_q, num_kb)
    kv_index = _causal_kv_index(causal, block_q, block_k)

    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_q=block_q,
                          block_k=block_k, num_kb=num_kb, causal=causal,
                          scale=scale, dropout_p=dropout_p, sk=sk),
        out_shape=[jax.ShapeDtypeStruct((b * n, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b * n, 1, sq), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
                   pl.BlockSpec((1, 1, block_q),
                                lambda i, j, kb: (i, 0, j))],
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="flash_attention_fwd",
    )(qt, kt, vt, seed)
    return (jnp.swapaxes(out.reshape(b, n, sq, d), 1, 2),
            lse.reshape(b, n, sq))


def _flash_bwd_from_lse(q, k, v, out, lse, g, causal, block_k, scale,
                        dropout_p=0.0, dropout_seed=None):
    """Standard flash backward from saved softmax stats: one blockwise pass
    recomputing p = exp(s - lse) per KV block (no second forward's
    max/sum accumulation). All in fp32; O(S) memory. With ``dropout_p`` the
    forward's counter-based keep mask regenerates per block (same math as
    ``_flash_bwd_dkv_kernel``)."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    nb = sk // block_k
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)      # [B,N,Sq,D]
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    ot = jnp.swapaxes(out, 1, 2).astype(jnp.float32)
    gt = jnp.swapaxes(g, 1, 2).astype(jnp.float32)
    delta = jnp.sum(gt * ot, axis=-1)                   # [B,N,Sq]
    q_pos = jnp.arange(sq)
    if dropout_p > 0.0:
        bh_idx = flat_bh(b, n)
        seed_u32 = jnp.asarray(dropout_seed, jnp.uint32)
        inv_keep = 1.0 / (1.0 - dropout_p)

    kb_ = kt.reshape(b, n, nb, block_k, d)
    vb_ = vt.reshape(b, n, nb, block_k, d)

    def body(dq, blk):
        k_blk, v_blk, idx = blk
        s = jnp.einsum("bnqd,bnkd->bnqk", qt, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = idx * block_k + jnp.arange(block_k)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            s = jnp.where(mask, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse[..., None]), 0.0)  # [B,N,Sq,BK]
        if dropout_p > 0.0:
            keep = dropout_keep_mask(
                seed_u32, bh_idx, q_pos[None, None, :, None],
                (idx * block_k + jnp.arange(block_k))[None, None, None, :],
                sk, dropout_p)
            p_v = jnp.where(keep, p * inv_keep, 0.0)
        else:
            p_v = p
        dv = jnp.einsum("bnqk,bnqd->bnkd", p_v, gt,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bnqd,bnkd->bnqk", gt, v_blk,
                        preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bnqk,bnkd->bnqd", ds, k_blk,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bnqk,bnqd->bnkd", ds, qt,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qt)
    dq, (dks, dvs) = lax.scan(
        body, dq0, (jnp.moveaxis(kb_, 2, 0), jnp.moveaxis(vb_, 2, 0),
                    jnp.arange(nb)))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, n, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, n, sk, d)
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


# ---------------------------------------------------------------------------
# Pallas flash backward (reference binds NKI flash_attn_bwd the same way,
# kernels/flash_attn.py:18). Two kernels, the standard split:
#   dq:    grid (b*n, q_blocks, k_blocks) — dq accumulates over KV blocks;
#   dk/dv: grid (b*n, k_blocks, q_blocks) — dk/dv accumulate over Q blocks.
# Both recompute p = exp(s - lse) from the saved log-sum-exp; delta =
# sum(g * out) per row is precomputed in XLA (cheap elementwise reduce).
# The XLA scan formulation above (_flash_bwd_from_lse) stays as the golden
# fallback.
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         seed_ref, dq_ref, dq_acc, *, block_q: int,
                         block_k: int, num_kb: int, causal: bool,
                         scale: float, dropout_p: float, sk: int):
    from jax.experimental import pallas as pl

    head = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when((not causal) or (kb * block_k <= qi * block_q + block_q - 1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # dP flows only through kept entries (the same regenerated mask
            # as the forward); the delta identity delta = rowsum(g*out) =
            # sum_j P_j dP_j still holds under dropout, so ds is unchanged
            keep = _tile_keep_mask(seed_ref, head, qi, kb,
                                   block_q, block_k, sk, dropout_p)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(k_ref, v_ref, q_ref, g_ref, lse_ref, delta_ref,
                          seed_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          block_q: int, block_k: int, num_qb: int,
                          causal: bool, scale: float, dropout_p: float,
                          sk: int):
    from jax.experimental import pallas as pl

    head = pl.program_id(0)
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when((not causal) or (qi * block_q + block_q - 1 >= kb * block_k))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse[:, None]), 0.0)
        if dropout_p > 0.0:
            keep = _tile_keep_mask(seed_ref, head, qi, kb,
                                   block_q, block_k, sk, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            # dV sees the dropped+rescaled probabilities (out = D(P) @ V)
            p_v = jnp.where(keep, p * inv, 0.0)
        else:
            p_v = p
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p_v, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_pallas_bwd(q, k, v, out, lse, g, seed, causal, block_q, block_k,
                      scale, interpret=False, dropout_p=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, n, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * n, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * n, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * n, sk, d)
    gt = jnp.swapaxes(g, 1, 2).reshape(b * n, sq, d)
    ot = jnp.swapaxes(out, 1, 2).reshape(b * n, sq, d)
    # stats carried 3D (b*n, 1, sq) so their (1, 1, block_q) blocks satisfy
    # Mosaic's sublane tiling rule (see _flash_pallas_fwd)
    lse_t = lse.reshape(b * n, 1, sq)
    delta = jnp.sum(gt.astype(jnp.float32) * ot.astype(jnp.float32), -1,
                    keepdims=True).reshape(b * n, 1, sq)
    num_qb, num_kb = sq // block_q, sk // block_k

    kv_index = _causal_kv_index(causal, block_q, block_k)
    if causal:
        # first q block at/below the diagonal for this KV block
        def q_index(i, kb, j):
            return (i, jnp.maximum(j, (kb * block_k) // block_q), 0)

        def qrow_index(i, kb, j):
            return (i, 0, jnp.maximum(j, (kb * block_k) // block_q))
    else:
        def q_index(i, kb, j):
            return (i, j, 0)

        def qrow_index(i, kb, j):
            return (i, 0, j)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, num_kb=num_kb, causal=causal,
                          scale=scale, dropout_p=dropout_p, sk=sk),
        out_shape=jax.ShapeDtypeStruct((b * n, sq, d), q.dtype),
        grid=(b * n, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, gt, lse_t, delta, seed)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, num_qb=num_qb, causal=causal,
                          scale=scale, dropout_p=dropout_p, sk=sk),
        out_shape=[jax.ShapeDtypeStruct((b * n, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * n, sk, d), v.dtype)],
        grid=(b * n, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_q), qrow_index),
            pl.BlockSpec((1, 1, block_q), qrow_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="flash_attention_bwd_dkv",
    )(kt, vt, qt, gt, lse_t, delta, seed)

    return (jnp.swapaxes(dq.reshape(b, n, sq, d), 1, 2),
            jnp.swapaxes(dk.reshape(b, n, sk, d), 1, 2),
            jnp.swapaxes(dv.reshape(b, n, sk, d), 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_pallas(q, k, v, seed, causal, block_q, block_k, scale, interpret,
                  dropout_p):
    out, _ = _flash_pallas_fwd(q, k, v, seed, causal, block_q, block_k,
                               scale, interpret, dropout_p)
    return out


def _flash_pallas_vjp_fwd(q, k, v, seed, causal, block_q, block_k, scale,
                          interpret, dropout_p):
    out, lse = _flash_pallas_fwd(q, k, v, seed, causal, block_q, block_k,
                                 scale, interpret, dropout_p)
    # Residual names for rematerialisation policies: under
    # ``jax.checkpoint(policy=save_only_these_names('flash_out',
    # 'flash_lse'))`` (``remat_policy='save_attention'``, the models'
    # default: utils/remat.py) the backward pass reuses the saved output +
    # softmax stats instead of re-running the forward kernel — the flash
    # backward only ever needed (q, k, v, out, lse), and q/k/v fall out of
    # the projection recompute. This trades O(B·S·N·D) saved bytes
    # for skipping the full attention forward in the backward pass.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, seed, out, lse)


def _flash_pallas_vjp_bwd(causal, block_q, block_k, scale, interpret,
                          dropout_p, res, g):
    import numpy as np

    q, k, v, seed, out, lse = res
    dq, dk, dv = _flash_pallas_bwd(q, k, v, out, lse, g, seed, causal,
                                   block_q, block_k, scale, interpret,
                                   dropout_p)
    # seed is integer-typed: its cotangent is the unit float0 type
    dseed = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, dseed


_flash_pallas.defvjp(_flash_pallas_vjp_fwd, _flash_pallas_vjp_bwd)


def _flash_pallas_on_mesh(q, k, v, seed, causal, block_q, block_k, scale,
                          dropout_p):
    """The compiled kernel under whatever partitions the caller.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so on the jit/GSPMD path over a multi-device mesh the
    kernel runs per shard: batch over ``dp``, heads over ``tp`` — how the
    TP layers leave ``[b, s, n, d]`` activations. Inside a ``shard_map``
    (axes already bound) and on one device it is called as is. A mesh axis
    that neither dimension can absorb is refused rather than replicated:
    with ``check_vma=False`` a replicated operand's cotangent would be
    summed over it."""
    from jax.sharding import PartitionSpec as P

    from ..parallel import mesh as ps

    args = (causal, block_q, block_k, scale, False, dropout_p)
    if not ps.model_parallel_is_initialized():
        return _flash_pallas(q, k, v, seed, *args)
    mesh = ps.get_mesh()
    wide = {a: s for a, s in mesh.shape.items() if s > 1}
    if not wide or any(ps.axis_bound(a) for a in wide):
        return _flash_pallas(q, k, v, seed, *args)
    dims = {ps.DP_AXIS: q.shape[0], ps.TP_AXIS: q.shape[2]}
    loose = [a for a, s in wide.items() if dims.get(a, 1) % s != 0]
    if loose:
        raise NotImplementedError(
            f"flash kernel under GSPMD: mesh axes {loose} of "
            f"{dict(mesh.shape)} divide neither the batch ({q.shape[0]}, "
            f"over dp) nor the heads ({q.shape[2]}, over tp); call it "
            "inside shard_map or use the XLA path (force_pallas=False)")
    spec = P(ps.DP_AXIS if ps.DP_AXIS in wide else None, None,
             ps.TP_AXIS if ps.TP_AXIS in wide else None, None)

    def per_shard(q, k, v, seed):
        if dropout_p > 0.0:
            # distinct masks per shard: the in-kernel hash sees local
            # head and batch indices
            for a in wide:
                seed = seed * jnp.uint32(1000003) + lax.axis_index(
                    a).astype(jnp.uint32)
        return _flash_pallas(q, k, v, seed, *args)

    return ps.shard_map(per_shard, mesh, in_specs=(spec, spec, spec, P()),
                        out_specs=spec)(q, k, v, seed)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "scale", "force_pallas",
                                             "dropout_p"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512,
                    scale: Optional[float] = None,
                    force_pallas: Optional[bool] = None,
                    dropout_p: float = 0.0,
                    dropout_seed: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention entry point: Pallas kernel on TPU when the shapes
    tile cleanly, scan/XLA formulation otherwise (the reference dispatches
    NKI-vs-torch the same way, ``kernels/flash_attn.py``).

    ``dropout_p`` + ``dropout_seed`` (uint32 scalar, required when p > 0):
    in-kernel attention dropout via counter-based masks — the same
    (seed, head, q, k) hash regenerates the mask in the forward kernel,
    both backward kernels, and the XLA fallback, so the two dispatch paths
    are bit-identical per seed (reference seed plumbing:
    ``kernels/flash_attn.py:30,54``)."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    # head_dim not 128-aligned (BERT/GPT-NeoX d=64): the Pallas path
    # zero-pads D up to the lane width — zero columns add nothing to QK^T
    # and the padded V columns only produce output columns we slice off,
    # so the kernel result is exact. Costs up to 2x kernel FLOPs/VMEM at
    # d=64, still ahead of demoting the whole model to the XLA scan
    # (VERDICT r4 missing #6; the reference's NKI flash serves its d=64
    # zoo with the same kernel, kernels/flash_attn.py:162). The tileable
    # decision below uses the PADDED width; the XLA fallback receives the
    # original arrays.
    d_kernel = -(-d // 128) * 128
    # clamp block sizes to the sequence before any divisibility decision,
    # then shrink (in 128-steps) to a size that divides the sequence — so a
    # seq divisible by 256 but not 512 still takes the Pallas path with
    # 256-blocks instead of silently demoting to the XLA fallback
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    while bq > 128 and bq % 128 == 0 and sq % bq != 0:
        bq -= 128
    while bk > 128 and bk % 128 == 0 and sk % bk != 0:
        bk -= 128
    # Mosaic tiling: d and (because the lse output's lane dim is block_q)
    # the block sizes must be 128-aligned for the compiled TPU path; the
    # force path accepts 8-aligned blocks (interpret mode / expert use)
    tileable_loose = (sq % bq == 0 and sk % bk == 0
                      and bq % 8 == 0 and bk % 8 == 0)
    tileable_strict = (tileable_loose and bq % 128 == 0 and bk % 128 == 0)
    if force_pallas:
        if not tileable_loose:
            raise ValueError(
                f"force_pallas: shapes (sq={sq}, sk={sk}) don't tile "
                f"with block_q={bq}, block_k={bk} (blocks must be "
                "8-aligned and divide the sequence)")
        use_pallas = True
    elif force_pallas is None:
        use_pallas = on_tpu() and tileable_strict
    else:
        use_pallas = False
    if dropout_p > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed (a uint32 "
                             "scalar; derive it from a PRNG key per step)")
        seed = jnp.asarray(dropout_seed, jnp.uint32).reshape((1,))
    else:
        seed = jnp.zeros((1,), jnp.uint32)
    if use_pallas:
        interpret = not on_tpu()
        if not interpret and not tileable_strict:
            raise ValueError(
                f"force_pallas on TPU requires 128-aligned blocks "
                f"(got block_q={bq}, block_k={bk}); loose 8-aligned blocks "
                "are only valid in CPU interpret mode")
        # interpret mode lowers to plain XLA ops, which GSPMD partitions
        # itself; only the compiled kernel needs the mesh spelled out
        def run(q, k, v):
            if interpret:
                return _flash_pallas(q, k, v, seed, causal, bq, bk, scale_,
                                     True, dropout_p)
            return _flash_pallas_on_mesh(q, k, v, seed, causal, bq, bk,
                                         scale_, dropout_p)

        if d != d_kernel:
            padw = ((0, 0), (0, 0), (0, 0), (0, d_kernel - d))
            return run(jnp.pad(q, padw), jnp.pad(k, padw),
                       jnp.pad(v, padw))[..., :d]
        return run(q, k, v)
    return flash_attention_xla(q, k, v, causal=causal,
                               block_k=bk, scale=scale_,
                               dropout_p=dropout_p,
                               dropout_seed=seed[0] if dropout_p > 0.0
                               else None)
