"""Ring attention for context parallelism.

Analogue of the reference's NKI ring attention wrapper
(``kernels/ring_attention_kernel.py:118`` → ``nki_ring_attn_func``): each cp
rank holds one sequence slice of Q/K/V; KV blocks rotate around the cp ring
while each rank accumulates flash-style online-softmax partials for its local
queries. The reference drives the ring with precomputed device ``src_tgt_pairs``
(``parallel_state.py:737-742``); here the ring is ``lax.ppermute`` over the
``cp`` mesh axis — the ring edges ARE the mesh axis ordering, which
``initialize_model_parallel`` lays out along the ICI torus.

Causal masking across ring steps: the kv block currently held at step ``i``
originated at rank ``(r - i) mod cp``; queries attend with position masks
computed from the *global* positions of both blocks, so causality holds
exactly across the ring (SURVEY §7.3 flags this as the hard part the
reference hides inside its NKI kernel).

Differentiable through JAX autodiff (the scan+ppermute transpose is the
reverse ring — same structure the pipeline engine relies on).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel import comm
from ..parallel import mesh as ps
from ..utils.device import on_tpu


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis: str = ps.CP_AXIS,
                   causal: bool = True,
                   scale: Optional[float] = None,
                   dropout_p: float = 0.0,
                   dropout_seed: Optional[jax.Array] = None,
                   wire=None,
                   wire_dtype: Optional[str] = None,
                   wire_block_size: int = 256) -> jax.Array:
    """Ring attention over the cp axis.

    ``q/k/v: [B, S_local, N, D]`` — this rank's sequence slice, kv already
    GQA-expanded. Must be called with ``axis`` bound (inside shard_map);
    falls back to plain attention when cp is absent/1.

    ``dropout_p``: attention dropout with the shared counter-based hash
    over GLOBAL (q, k) sequence coordinates — every cp rank regenerates
    exactly the mask the non-CP model draws for its slice, so adding cp
    sharding is bit-consistent with the same model at cp=1. (Head indices
    in the hash are tp-LOCAL, so the masks match at equal TP degree;
    changing tp changes the draw, as in the reference's per-rank seed
    plumbing, ``kernels/ring_attention_kernel.py``.)

    ``wire`` / ``wire_dtype``: quantize the KV ring hops through the
    shared wire codec (EQuARX-style blockwise int8/fp8,
    :mod:`..parallel.wire_codec`): each ppermute ships the quantized
    payload plus its fp32 block scales and the receiver dequantizes
    before accumulating. ``wire`` takes a :class:`CompressionConfig`
    directly; ``wire_dtype`` (``"int8"``/``"fp8"``) builds one with
    ``wire_block_size``-element blocks. ``None``/``"fp32"`` keeps the
    hops at full precision and is BITWISE identical to the pre-wire ring
    (the fallback knob serving exposes as ``cp_wire_dtype="fp32"``).
    Each hop requantizes the visiting chunk, so a chunk that travels
    ``j`` hops has been through ``j`` round-trips — inference-only
    (rounding has zero gradient; the training path never passes ``wire``).

    Returns ``[B, S_local, N, D]``.
    """
    from ..parallel.wire_codec import CompressionConfig

    if wire is None and wire_dtype is not None and wire_dtype != "fp32":
        wire = CompressionConfig(dtype=wire_dtype,
                                 block_size=wire_block_size)
    if wire is not None and not wire.quantized:
        wire = None
    cp = comm._axis_size(axis)
    if cp is None or cp == 1:
        from ..modules.attention import sdpa_reference

        return sdpa_reference(q, k, v, causal=causal, scale=scale,
                              dropout_p=dropout_p,
                              dropout_seed=dropout_seed)

    b, s_local, n, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    r = lax.axis_index(axis)
    qpos = r * s_local + jnp.arange(s_local)  # global query positions

    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,N,Sq,D]
    ring_perm = [(i, (i + 1) % cp) for i in range(cp)]
    if dropout_p > 0.0:
        from .flash_attention import dropout_keep_mask, flat_bh

        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed")
        seed_u32 = jnp.asarray(dropout_seed, jnp.uint32)
        s_global = cp * s_local
        bh = flat_bh(b, n)

    def accumulate(carry, k_cur, v_cur, i):
        m_prev, l_prev, acc = carry
        src = (r - i) % cp  # rank where this kv block originated
        kt = jnp.swapaxes(k_cur, 1, 2).astype(jnp.float32)
        vt = jnp.swapaxes(v_cur, 1, 2).astype(jnp.float32)
        s = jnp.einsum("bnqd,bnkd->bnqk", qt, kt,
                       preferred_element_type=jnp.float32) * scale
        kpos = src * s_local + jnp.arange(s_local)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        if dropout_p > 0.0:
            keep = dropout_keep_mask(
                seed_u32, bh, qpos[None, None, :, None],
                kpos[None, None, None, :], s_global, dropout_p)
            p_acc = jnp.where(keep, p, 0.0)
        else:
            p_acc = p
        acc = acc * corr[..., None] + jnp.einsum(
            "bnqk,bnkd->bnqd", p_acc, vt,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    def step(carry, i):
        m_prev, l_prev, acc, k_cur, v_cur = carry
        m_new, l_new, acc = accumulate((m_prev, l_prev, acc), k_cur, v_cur, i)
        if wire is None:
            k_next = comm.ppermute(k_cur, axis, ring_perm)
            v_next = comm.ppermute(v_cur, axis, ring_perm)
        else:
            # quantized hop: the int8/fp8 payload and its fp32 block
            # scales ride the same ring permute; dequantize on arrival
            from ..parallel.wire_codec import decode_payload, encode_payload

            kq, ks = encode_payload(k_cur, wire)
            vq, vs = encode_payload(v_cur, wire)
            kq = comm.ppermute(kq, axis, ring_perm)
            ks = comm.ppermute(ks, axis, ring_perm)
            vq = comm.ppermute(vq, axis, ring_perm)
            vs = comm.ppermute(vs, axis, ring_perm)
            k_next = decode_payload(kq, ks, wire).astype(k_cur.dtype)
            v_next = decode_payload(vq, vs, wire).astype(v_cur.dtype)
        return (m_new, l_new, acc, k_next, v_next), None

    m0 = jnp.full((b, n, s_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, n, s_local), jnp.float32)
    acc0 = jnp.zeros((b, n, s_local, d), jnp.float32)
    # cp-1 rotating steps, then a final permute-free accumulate (uniform
    # across ranks; saves two collectives per call)
    (m, l, acc, k_last, v_last), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(cp - 1))
    m, l, acc = accumulate((m, l, acc), k_last, v_last, cp - 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if dropout_p > 0.0:
        out = out * (1.0 / (1.0 - dropout_p))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas-fused ring attention: each ring step runs the hand-tiled flash
# kernel (ops/flash_attention) on the visiting KV chunk; chunk partials
# merge across steps with the stable log-sum-exp combine. The backward is a
# second ring pass reusing the Pallas flash-backward kernels, with the
# dk/dv accumulators travelling around the ring alongside their KV chunk
# (one full circle returns them home). Reference ships this fusion as one
# NKI kernel (kernels/ring_attention_kernel.py:118); the XLA formulation
# above stays as the golden reference.
#
# Cross-rank causality is all-or-nothing per chunk: the diagonal chunk
# (src == r) uses the causal kernel, chunks from earlier ranks the dense
# kernel, later ranks contribute nothing — selected with lax.cond on the
# rank-dependent predicate (no collectives inside, so divergence across cp
# ranks is safe), which skips the masked chunks' compute entirely.
# ---------------------------------------------------------------------------

def _chunk_fwd(q, k_c, v_c, rel, seed, block_q, block_k, scale, interpret,
               dropout_p):
    """(out, lse) of q against one visiting chunk. rel = sign of
    (r - src): 0 -> diagonal (causal), >0 -> fully attended, <0 -> skip.
    ``seed``: (1,) uint32, already folded per (rank, src) pair so every
    chunk draws an independent mask and the backward regenerates it."""
    from .flash_attention import _flash_pallas_fwd

    def diag(q, k_c, v_c):
        return _flash_pallas_fwd(q, k_c, v_c, seed, True, block_q, block_k,
                                 scale, interpret, dropout_p=dropout_p)

    def full(q, k_c, v_c):
        return _flash_pallas_fwd(q, k_c, v_c, seed, False, block_q, block_k,
                                 scale, interpret, dropout_p=dropout_p)

    def skip(q, k_c, v_c):
        b, s, n, d = q.shape
        return (jnp.zeros_like(q),
                jnp.full((b, n, s), -jnp.inf, jnp.float32))

    return lax.cond(rel == 0, diag,
                    lambda q, k_c, v_c: lax.cond(rel > 0, full, skip,
                                                 q, k_c, v_c),
                    q, k_c, v_c)


def _chunk_bwd(q, k_c, v_c, out, lse, g, rel, seed, block_q, block_k, scale,
               interpret, dropout_p):
    from .flash_attention import _flash_pallas_bwd

    def diag(args):
        return _flash_pallas_bwd(*args, seed, True, block_q, block_k, scale,
                                 interpret, dropout_p=dropout_p)

    def full(args):
        return _flash_pallas_bwd(*args, seed, False, block_q, block_k,
                                 scale, interpret, dropout_p=dropout_p)

    def skip(args):
        q, k_c, v_c, _, _, _ = args
        return jnp.zeros_like(q), jnp.zeros_like(k_c), jnp.zeros_like(v_c)

    args = (q, k_c, v_c, out, lse, g)
    return lax.cond(rel == 0, diag,
                    lambda a: lax.cond(rel > 0, full, skip, a), args)


def _pair_seed(seed, r, src, cp):
    """Fold the (query-rank, chunk-home-rank) pair into the base seed so
    each of the cp^2 chunk visits draws an independent mask; fwd and bwd
    recompute the identical fold from (r, src), so masks regenerate."""
    pair = (r.astype(jnp.uint32) * jnp.uint32(cp) + src.astype(jnp.uint32))
    return seed + pair * jnp.uint32(0x9E3779B1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_pallas(q, k, v, seed, axis, block_q, block_k, scale, interpret,
                 dropout_p):
    out, _ = _ring_pallas_fwd_pass(q, k, v, seed, axis, block_q, block_k,
                                   scale, interpret, dropout_p)
    return out


def _ring_pallas_fwd_pass(q, k, v, seed, axis, block_q, block_k, scale,
                          interpret, dropout_p):
    cp = comm._axis_size(axis)
    b, s_local, n, d = q.shape
    r = lax.axis_index(axis)
    ring_perm = [(i, (i + 1) % cp) for i in range(cp)]

    def step(carry, i):
        o_run, lse_run, k_cur, v_cur = carry
        src = (r - i) % cp
        rel = r - src  # 0 diag; >0 earlier rank (attend); <0 later (skip)
        o_i, lse_i = _chunk_fwd(q, k_cur, v_cur, rel,
                                _pair_seed(seed, r, src, cp), block_q,
                                block_k, scale, interpret, dropout_p)
        o_i = jnp.swapaxes(o_i, 1, 2).astype(jnp.float32)  # [B,N,S,D]
        m = jnp.maximum(lse_run, lse_i)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        a = jnp.where(jnp.isfinite(lse_run), jnp.exp(lse_run - m_safe), 0.0)
        bb = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - m_safe), 0.0)
        denom = jnp.maximum(a + bb, 1e-30)
        o_run = (o_run * (a / denom)[..., None]
                 + o_i * (bb / denom)[..., None])
        lse_run = m_safe + jnp.log(denom)
        lse_run = jnp.where(a + bb > 0, lse_run, -jnp.inf)
        k_next = comm.ppermute(k_cur, axis, ring_perm)
        v_next = comm.ppermute(v_cur, axis, ring_perm)
        return (o_run, lse_run, k_next, v_next), None

    o0 = jnp.zeros((b, n, s_local, d), jnp.float32)
    lse0 = jnp.full((b, n, s_local), -jnp.inf, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(cp))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype), lse


def _ring_pallas_vjp_fwd(q, k, v, seed, axis, block_q, block_k, scale,
                         interpret, dropout_p):
    out, lse = _ring_pallas_fwd_pass(q, k, v, seed, axis, block_q, block_k,
                                     scale, interpret, dropout_p)
    return out, (q, k, v, seed, out, lse)


def _ring_pallas_vjp_bwd(axis, block_q, block_k, scale, interpret, dropout_p,
                         res, g):
    import numpy as np

    q, k, v, seed, out, lse = res
    cp = comm._axis_size(axis)
    r = lax.axis_index(axis)
    ring_perm = [(i, (i + 1) % cp) for i in range(cp)]

    def step(carry, i):
        dq_acc, k_cur, v_cur, dk_buf, dv_buf = carry
        src = (r - i) % cp
        rel = r - src
        dq_i, dk_i, dv_i = _chunk_bwd(q, k_cur, v_cur, out, lse, g, rel,
                                      _pair_seed(seed, r, src, cp),
                                      block_q, block_k, scale, interpret,
                                      dropout_p)
        dq_acc = dq_acc + dq_i.astype(jnp.float32)
        dk_buf = dk_buf + dk_i.astype(jnp.float32)
        dv_buf = dv_buf + dv_i.astype(jnp.float32)
        # the accumulators travel with their chunk; after the full circle
        # they are back at the chunk's home rank
        k_cur = comm.ppermute(k_cur, axis, ring_perm)
        v_cur = comm.ppermute(v_cur, axis, ring_perm)
        dk_buf = comm.ppermute(dk_buf, axis, ring_perm)
        dv_buf = comm.ppermute(dv_buf, axis, ring_perm)
        return (dq_acc, k_cur, v_cur, dk_buf, dv_buf), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dkv0 = jnp.zeros(k.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dkv0, jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(cp))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            np.zeros(seed.shape, jax.dtypes.float0))


_ring_pallas.defvjp(_ring_pallas_vjp_fwd, _ring_pallas_vjp_bwd)


def ring_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                          axis: str = ps.CP_AXIS,
                          block_q: int = 128, block_k: int = 128,
                          scale: Optional[float] = None,
                          interpret: Optional[bool] = None,
                          dropout_p: float = 0.0,
                          dropout_seed: Optional[jax.Array] = None,
                          ) -> jax.Array:
    """Ring attention with the Pallas flash kernels fused into each ring
    step. Same contract as :func:`ring_attention` except: causal only (the
    cross-chunk skip logic assumes causal), and dropout masks are the
    in-kernel per-chunk draw — deterministic and fwd/bwd-consistent (the
    (rank, chunk-home) pair is folded into the seed) but a DIFFERENT draw
    from :func:`ring_attention`'s global-coordinate masks, which are the
    ones bit-consistent with the cp=1 model. Falls back to
    :func:`ring_attention` (forwarding the dropout arguments) when cp is
    absent or shapes don't tile."""
    cp = comm._axis_size(axis)
    b, s_local, n, d = q.shape
    bq, bk = min(block_q, s_local), min(block_k, s_local)
    if interpret is None:
        interpret = not on_tpu()
    # compiled TPU Mosaic requires 128-aligned blocks (flash_attention's
    # tileable_strict); interpret mode accepts 8-aligned for tests
    align = 8 if interpret else 128
    tiles = (s_local % bq == 0 and s_local % bk == 0 and d % 128 == 0
             and bq % align == 0 and bk % align == 0)
    if cp is None or cp == 1 or not tiles:
        return ring_attention(q, k, v, axis=axis, causal=True, scale=scale,
                              dropout_p=dropout_p,
                              dropout_seed=dropout_seed)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = (jnp.asarray(dropout_seed, jnp.uint32).reshape((1,))
            if dropout_p > 0.0 else jnp.zeros((1,), jnp.uint32))
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    return _ring_pallas(q, k, v, seed, axis, bq, bk, scale_, interpret,
                        dropout_p)


# -- nxdlint jaxpr-audit entry point ---------------------------------------

from ..analysis.audit_registry import BuiltEntry, register_entry_point


@register_entry_point(
    "ring-attention",
    description="cp ring attention: cp-1 rotating ppermute hops under "
                "shard_map on the cp axis",
    tags=("train", "serve"),
    in_shardings=((None, "cp", None, None),) * 3,
    max_replicated_bytes=1 << 20,
)
def _audit_ring_attention() -> BuiltEntry:
    """Builder for ``analysis --jaxpr``/``--mesh-protocol``: the XLA ring
    on a 4-way cp mesh. The verifier checks every rotation perm covers
    the axis exactly once and q/k/v stay cp-sharded after propagation."""
    from jax.sharding import PartitionSpec as P

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(context_parallel_size=4)
    fn = jax.jit(ps.shard_map(
        lambda q, k, v: ring_attention(q, k, v),
        mesh, in_specs=(P(None, "cp", None, None),) * 3,
        out_specs=P(None, "cp", None, None)))
    q = jnp.zeros((2, 32, 4, 8), jnp.float32)
    return BuiltEntry(fn=fn, args=(q, q, q), mesh=mesh)


@register_entry_point(
    "ring-attention-int8",
    description="cp ring attention with int8 quantized KV hops: each "
                "ppermute ships the wire-codec payload + fp32 block "
                "scales (CP prefill serving tier)",
    tags=("serve",),
    wire_dtype="int8",
    # the fp32 *scales* legitimately ride the ring beside the int8
    # payload: at the audit shapes they are 64 elements per hop, below
    # this floor; the KV payloads themselves (4096 elements) would trip
    # the wire-precision rule if they ever shipped unquantized
    wire_min_elems=128,
    in_shardings=((None, "cp", None, None),) * 3,
    max_replicated_bytes=1 << 20,
)
def _audit_ring_attention_int8() -> BuiltEntry:
    """Builder for ``analysis --jaxpr``/``--mesh-protocol``: the serving
    ring with quantized hops on a 4-way cp mesh. The wire-precision rule
    verifies no wide-float KV payload rides a ring primitive — only the
    int8 values and their (small) scale tensors may appear."""
    from jax.sharding import PartitionSpec as P

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(context_parallel_size=4)
    fn = jax.jit(ps.shard_map(
        lambda q, k, v: ring_attention(q, k, v, wire_dtype="int8"),
        mesh, in_specs=(P(None, "cp", None, None),) * 3,
        out_specs=P(None, "cp", None, None)))
    q = jnp.zeros((2, 32, 4, 8), jnp.float32)
    return BuiltEntry(fn=fn, args=(q, q, q), mesh=mesh)
