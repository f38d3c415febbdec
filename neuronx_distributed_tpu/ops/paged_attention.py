"""Paged decode attention over a shared block pool.

Decode-time attention where K/V live in the paged pool of
:mod:`..inference.paging` (``[L, num_blocks, block_size, KV, D]``, every
layer's in one stack) and each query token reads the blocks of one layer
named by its slot's block table — the attention half of the vLLM design,
on the fixed-shape serving step. Both implementations read the stack at
``(layer, block)``: a ``pool[layer]`` in front of them would be a copy
of a layer's pool, every layer of every step.

Two implementations behind one signature, following
:mod:`.flash_attention` / :mod:`.flash_decoding`:

* ``_paged_attention_xla`` — pure-``jnp`` gather-based reference. It
  mirrors the contiguous cache path's numerics exactly (same fp32
  einsums, same ``-1e30`` position-sentinel masking), so paged decode is
  bit-for-bit comparable with :func:`..models.llama.llama_forward_with_cache`
  on the contiguous cache; runs everywhere and is the tier-1/CPU path.
* ``_paged_attention_pallas`` — a Mosaic TPU kernel: grid ``(tokens,
  max_blocks_per_seq)``, the walk over each row's block table
  (:func:`_paged_walk`) and the layer scalar-prefetched into SMEM so a
  grid step DMAs at most one pool block into VMEM (online-softmax
  m/l/acc in VMEM scratch). The walk follows the row's context: a
  column that is unmapped (-1) or lies wholly behind the row's own
  position (:func:`column_live`) is skipped, not masked — its grid step
  runs no arithmetic, and past the row's last causal column the block
  index repeats that column's, so the same-block DMA is elided too.

Auto-dispatch picks the kernel on TPU when the shapes tile; CPU runs the
kernel in interpret mode when forced (CI coverage of the mask path).

Both paths are strictly *read-only* over the pool: they gather blocks by
table entry and never scatter back. That is what makes copy-on-write
prefix sharing (:class:`..inference.paging.PrefixCache`) safe — two
tokens' tables may name the same block ids and each still attends to
identical K/V; writers are diverted to private clones by the engine
before the step runs (verified by the shared-table invariance test in
``tests/test_prefix_sharing.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION, dequantize_kv
from ..modules.attention import repeat_kv
from ..utils.device import on_tpu
from ..utils.logger import get_logger
from .pallas_utils import compiler_params as _compiler_params

logger = get_logger(__name__)


def _paged_attention_xla(q, k_pool, v_pool, pool_pos, tables, q_pos, layer,
                         k_scale, v_scale, scale, combine_axis=None,
                         window=None):
    t, n, d = q.shape
    _, nb, bs, kv, _ = k_pool.shape
    n_rep = n // kv
    safe = jnp.clip(tables, 0, nb - 1)
    kg = k_pool[layer, safe]                   # [T, maxb, bs, KV, D]
    vg = v_pool[layer, safe]
    pg = pool_pos[safe]                        # [T, maxb, bs]
    # entries gathered through an unmapped (-1) table slot are another
    # sequence's data — force their stored position to the pad sentinel
    pg = jnp.where(tables[:, :, None] >= 0, pg, PAD_POSITION)
    if k_scale is not None:
        kg = dequantize_kv(kg, k_scale[layer, safe], q.dtype)
        vg = dequantize_kv(vg, v_scale[layer, safe], q.dtype)
    length = tables.shape[1] * bs
    k_full = repeat_kv(kg.reshape(t, length, kv, d).astype(q.dtype), n_rep)
    v_full = repeat_kv(vg.reshape(t, length, kv, d).astype(q.dtype), n_rep)
    pg = pg.reshape(t, length)
    scores = jnp.einsum("bqnd,bknd->bnqk", q[:, None].astype(jnp.float32),
                        k_full.astype(jnp.float32)) * scale
    if window is None:
        mask = q_pos[:, None, None, None] >= pg[:, None, None, :]
    else:
        # two kinds of row under two masks: an exact row counts if it is
        # of the query's own window and not later than the query (stale
        # ring rows fall under the window's start); a summary column
        # counts whole, if its window lies before the query's
        size, ring = window
        cols = jnp.arange(tables.shape[1], dtype=jnp.int32)
        kinds = window_column_kinds(tables, cols, q_pos[:, None], bs, size,
                                    ring)                     # [T, maxb]
        lo = (q_pos // size) * size
        exact = ((pg <= q_pos[:, None]) & (pg >= lo[:, None])
                 ).reshape(t, -1, bs) & (kinds == 1)[:, :, None]
        mask = (exact | (kinds == 2)[:, :, None]).reshape(
            t, 1, 1, length)
    scores = jnp.where(mask, scores, -1e30)
    if combine_axis is None:
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bnqk,bknd->bqnd", probs,
                         v_full.astype(jnp.float32))
        return out[:, 0].astype(q.dtype)
    # flash-decoding combine over CP-sharded resident blocks: each rank
    # attends its local gather; one pmax + two psums merge the partials
    # (reference combine_kv_on_device, trace/spmd.py:74). The global max
    # makes fully-masked shards (a token with no resident blocks on this
    # rank) contribute exp(-1e30 - m) == 0 rather than a local uniform.
    m = jax.lax.pmax(jnp.max(scores, axis=-1), combine_axis)   # [T,N,1]
    p = jnp.exp(scores - m[..., None])
    l = jax.lax.psum(jnp.sum(p, axis=-1), combine_axis)        # [T,N,1]
    o = jax.lax.psum(
        jnp.einsum("bnqk,bknd->bqnd", p, v_full.astype(jnp.float32)),
        combine_axis)                                          # [T,1,N,D]
    out = o / jnp.maximum(l[..., 0], 1e-30)[:, None, :, None]
    return out[:, 0].astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def column_live(entry, column, q_pos, block_size: int):
    """Whether table column ``column`` (holding block id ``entry``) can
    contribute to a row at ``q_pos``: it is mapped, and its first position
    ``column * block_size`` is not beyond the row's own (a position ``p``
    lives in column ``p // block_size``,
    :func:`..inference.paging.flat_write_indices`, and a row attends only
    to positions ``<= q_pos``). Every other column adds exactly nothing to
    the online softmax. Broadcasts over jnp arrays (the kernel's walk)
    and NumPy ones (the engine's ``nxd_paged_columns_total``, the tests).
    """
    return (entry >= 0) & (column * block_size <= q_pos)


def window_column_kinds(entry, column, q_pos, block_size: int, window: int,
                        ring: int):
    """What a row at ``q_pos`` finds in table column ``column`` (holding
    block id ``entry``) of a window-summary cache
    (:class:`..inference.paging.WindowSummaryCache`): 1, exact rows it
    attends (a ring column whose block holds positions of the row's own
    window that are not beyond the row); 2, the summaries of an earlier
    window (column ``ring + w'`` with ``w' < q_pos // window``); 0,
    nothing (unmapped, a stale ring column, a later window's summaries,
    a pad row). Broadcasts over jnp arrays (the kernel's walk) and NumPy
    ones (the engine's ``nxd_eva_columns_total``, the tests)."""
    w = q_pos // window
    bpw = window // block_size
    # the ring column of block b is b % ring; of the row's own window the
    # blocks w * bpw .. q_pos // block_size are resident
    first = w * bpw
    held = q_pos // block_size - first + 1          # 1 .. bpw
    exact = (column < ring) & ((column - first) % ring < held)
    summary = (column >= ring) & (column - ring < w)
    real = (entry >= 0) & (q_pos < PAD_POSITION)
    return (real & exact) * 1 + (real & summary) * 2


def _window_walk(tables, q_pos, block_size: int, window: int, ring: int):
    """:func:`_paged_walk` for a window-summary cache: a live column's
    block id, or for a skipped column the complement of the block the
    previous live column named (the row's first live one before any; 0
    where the row has none), so that a skipped step's DMA is elided."""
    maxb = tables.shape[1]
    cols = jnp.arange(maxb, dtype=jnp.int32)[None, :]
    live = window_column_kinds(tables, cols, q_pos[:, None], block_size,
                               window, ring) > 0
    last = jax.lax.cummax(jnp.where(live, cols, -1), axis=1)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)[:, None]
    name = jnp.where(last >= 0, last, first)
    fetch = jnp.maximum(jnp.take_along_axis(tables, name, axis=1), 0)
    return jnp.where(live, fetch, ~fetch)


def _paged_walk(tables, q_pos, block_size: int):
    """``[T, max_blocks_per_seq]`` int32, one entry a grid step of the
    kernel: a live column's block id (>= 0: fetch it and compute), or for
    a skipped column the complement (``~b`` < 0) of the block the step
    names and never reads — the row's last causal column's, 0 where that
    is unmapped — so consecutive skipped steps repeat the index and their
    DMA is elided. Worked out here, ahead of the kernel, and not per grid
    step from the table in SMEM: on the v5e that scalar work was 0.11 us
    of every step, 4% of a live one and 44% of a skipped one."""
    maxb = tables.shape[1]
    cols = jnp.arange(maxb, dtype=jnp.int32)
    last = jnp.clip(q_pos // block_size, 0, maxb - 1)[:, None]
    fetch = jnp.maximum(jnp.take_along_axis(
        tables, jnp.minimum(cols, last), axis=1), 0)
    return jnp.where(column_live(tables, cols, q_pos[:, None], block_size),
                     fetch, ~fetch)


def _paged_kernel(walk_ref, qpos_ref, layer_ref, *refs,
                  num_blocks_per_seq: int, n_rep: int, scale: float,
                  quantized: bool, ring: Optional[int] = None):
    """One (token, table column) grid step: online softmax of the token's
    heads over one pool block, if the column is live for the token
    (``walk_ref[t, j] >= 0``, :func:`_paged_walk`); a skipped column
    leaves the running max, sum and accumulator as they are, which is
    what its all-masked block did. ``layer_ref`` is the index maps' (which
    layer of the stacks a block is fetched from); the body never reads it.

    Everything stays in the pool block's own layout — slots on the major
    dim, KV heads on sublanes, head_dim on lanes — so Mosaic sees only
    elementwise products, lane/major reductions and lane broadcasts:
    scores are ``[BS, KV, 1]`` (one per (slot, head) row), the running
    max/sum ``[KV, 1]`` and the accumulator ``[KV, D]`` per query-head
    replica ``r`` (query head ``h * n_rep + r`` reads KV head ``h``).

    With ``ring`` (a window-summary cache, the ``eva_attention`` kernel)
    a further prefetched scalar a row is the first position of its window:
    the rows of columns under ``ring`` are exact and count from there to
    the row's own position, those of the columns from ``ring`` on are
    summaries and count whole; one softmax runs over both."""
    from jax.experimental import pallas as pl

    if ring is not None:
        qlo_ref, *refs = refs
    q_ref, k_ref, v_ref, pos_ref, *rest = refs
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bs = k_ref.shape[1]

    @pl.when(walk_ref[t, j] >= 0)
    def _accumulate():
        k = k_ref[0].astype(jnp.float32)               # [BS, KV, D]
        v = v_ref[0].astype(jnp.float32)
        # per-slot validity arrives slot-on-lanes ([1, 1, BS]); a one-hot
        # select + lane max moves it to slot-on-major ([BS, 1, 1])
        ok = qpos_ref[t] >= pos_ref[...]
        if ring is not None:
            ok = ok & (pos_ref[...] >= qlo_ref[t])
        ok = ok.astype(jnp.float32)
        if ring is not None:
            ok = jnp.where(j >= ring, 1.0, ok)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (bs, 1, bs), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (bs, 1, bs), 2))
        valid = jnp.max(jnp.where(eye, ok, 0.0), axis=-1,
                        keepdims=True) > 0.5
        if quantized:
            # scales arrive slot-on-lanes too ([1, KV, BS]); the same trick
            # puts each on its slot's major index ([BS, KV, 1]). They scale
            # the score and the probability, not the [BS, KV, D] operands.
            def per_row(s_ref):
                return jnp.sum(jnp.where(eye, s_ref[...], 0.0), axis=-1,
                               keepdims=True)

            k_scale = per_row(ks_ref)
            v_scale = per_row(vs_ref)
        for r in range(n_rep):
            q = q_ref[0, r].astype(jnp.float32) * scale    # [KV, D]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)   # [BS, KV, 1]
            if quantized:
                s = s * k_scale
            s = jnp.where(valid, s, -jnp.inf)
            m_prev = m_ref[r]                              # [KV, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(valid, jnp.exp(s - m_safe[None]), 0.0)
            corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                             0.0)
            m_ref[r] = m_new
            l_ref[r] = l_ref[r] * corr + jnp.sum(p, axis=0)
            if quantized:
                p = p * v_scale
            acc_ref[r] = acc_ref[r] * corr + jnp.sum(p * v, axis=0)

    @pl.when(j == num_blocks_per_seq - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, pool_pos, tables, q_pos,
                            layer, k_scale, v_scale, scale, interpret=False,
                            window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, n, d = q.shape
    _, nb, bs, kv, _ = k_pool.shape
    maxb = tables.shape[1]
    n_rep = n // kv
    quantized = k_scale is not None

    q_pos = q_pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if window is None:
        walk = _paged_walk(tables.astype(jnp.int32), q_pos, bs)
        prefetch, ring, name = (walk, q_pos, layer), None, "paged_attention"
    else:
        size, ring = window
        walk = _window_walk(tables.astype(jnp.int32), q_pos, bs, size, ring)
        prefetch = (walk, q_pos, layer, (q_pos // size) * size)
        name = "eva_attention"

    def block(ti, j, walk_s, *_):
        w = walk_s[ti, j]
        return jnp.where(w < 0, ~w, w)

    # a block of the stacks: the layer's dim is squeezed out of the block,
    # so the body sees one layer's [1, bs, ...] block as it always has
    def stack5(ti, j, walk_s, qpos_s, layer_s, *_):
        return (layer_s[0], block(ti, j, walk_s), 0, 0, 0)

    def stack4(ti, j, walk_s, qpos_s, layer_s, *_):
        return (layer_s[0], block(ti, j, walk_s), 0, 0)

    def blk3(*idx):
        return (block(*idx), 0, 0)

    def tok(ti, j, *_):
        return (ti, 0, 0, 0)

    # Mosaic wants the last two block dims (8, 128)-aligned or whole: the
    # positions (shared by the layers) ride as [nb, 1, bs] rows, the scales
    # as [kv, bs] planes, and q/out as [T, n_rep, KV, D] so a replica is a
    # leading index
    in_specs = [
        pl.BlockSpec((1, n_rep, kv, d), tok),
        pl.BlockSpec((None, 1, bs, kv, d), stack5),
        pl.BlockSpec((None, 1, bs, kv, d), stack5),
        pl.BlockSpec((1, 1, bs), blk3),
    ]
    operands = [q.reshape(t, kv, n_rep, d).swapaxes(1, 2), k_pool, v_pool,
                pool_pos.reshape(nb, 1, bs)]
    if quantized:
        # slots on lanes is how the chip stores an array whose last dim is
        # a few heads wide, and how the step's scatter writes it: the swap
        # is then no copy, where a [bs, kv] plane made the whole stack
        # change layout in front of the kernel and back behind it
        in_specs += [pl.BlockSpec((None, 1, kv, bs), stack4),
                     pl.BlockSpec((None, 1, kv, bs), stack4)]
        operands += [k_scale.swapaxes(2, 3), v_scale.swapaxes(2, 3)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(t, maxb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_rep, kv, d), tok),
        scratch_shapes=[pltpu.VMEM((n_rep, kv, 1), jnp.float32),
                        pltpu.VMEM((n_rep, kv, 1), jnp.float32),
                        pltpu.VMEM((n_rep, kv, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, num_blocks_per_seq=maxb,
                          n_rep=n_rep, scale=scale, quantized=quantized,
                          ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n_rep, kv, d), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name=name,
    )(*prefetch, *operands)
    return out.swapaxes(1, 2).reshape(t, n, d)


@functools.lru_cache(maxsize=None)
def paged_attention_impl(head_dim: int, block_size: int,
                         force_pallas: Optional[bool] = None) -> str:
    """Which implementation :func:`paged_attention` runs for a pool of this
    ``head_dim`` and ``block_size`` on the default backend: ``"pallas"``
    (the compiled TPU kernel), ``"pallas-interpret"`` (the kernel, forced,
    off TPU) or ``"xla"`` (the gather reference).

    The compiled kernel wants ``head_dim`` on whole lanes and whole
    128-slot blocks (the shapes ``tests/test_chip_compile.py`` covers). A
    TPU that falls to the reference for its shapes says so once, here."""
    if force_pallas is False:
        return "xla"
    if not on_tpu():
        return "pallas-interpret" if force_pallas else "xla"
    if head_dim % 128 == 0 and block_size % 128 == 0:
        return "pallas"
    if force_pallas:
        raise ValueError(
            f"force_pallas: paged shapes (head_dim={head_dim}, "
            f"block_size={block_size}) don't tile for the TPU kernel; "
            "non-tiling shapes are only valid in CPU interpret mode")
    logger.warning(
        "paged_attention: head_dim=%d block_size=%d does not tile for the "
        "TPU kernel (both must be multiples of 128); this pool is served "
        "by the XLA gather reference", head_dim, block_size)
    return "xla"


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    pool_pos: jax.Array, tables: jax.Array,
                    q_pos: jax.Array, layer,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    force_pallas: Optional[bool] = None,
                    combine_axis: Optional[str] = None,
                    window: Optional[tuple] = None) -> jax.Array:
    """Paged decode attention.

    ``q [T, N, D]`` one query row per packed token; ``k_pool``/``v_pool``
    the stacks ``[L, num_blocks, block_size, KV, D]`` (int8 when
    ``k_scale``/``v_scale`` ``[L, num_blocks, block_size, KV]`` are
    given) and ``layer`` the (traced) index of the layer to attend, read
    in place at ``(layer, block)``; ``pool_pos [num_blocks, block_size]``
    stored token positions (PAD_POSITION = empty; shared by the layers);
    ``tables [T, max_blocks_per_seq]`` per-token block table (-1 =
    unmapped); ``q_pos [T]`` query positions. Returns ``[T, N, D]``.

    ``force_pallas``: ``True`` forces the TPU kernel (interpret mode off
    TPU), ``False`` forces the XLA reference, ``None`` auto-selects.

    ``combine_axis``: name of a bound mesh axis over which the block pool
    is sharded (context-parallel serving). Each rank gathers only its
    resident blocks (``tables`` carry rank-local ids, -1 elsewhere) and
    the partials merge with the flash-decoding log-sum-exp combine —
    one pmax and two psums regardless of session length. Must be called
    inside ``shard_map`` with the axis bound; implies the XLA path (the
    Pallas kernel computes no cross-rank combine).

    ``window``: ``(window_size, ring_columns)`` of a window-summary cache
    (:class:`..inference.paging.WindowSummaryCache`): the table's first
    ``ring_columns`` columns hold exact rows, attended within the query's
    own window, the later ones hold an earlier window's chunk summaries
    each, attended whole, under the one softmax. The kernel is then named
    ``eva_attention`` in a device trace. Not with ``combine_axis``.
    """
    t, n, d = q.shape
    _, nb, bs, kv, _ = k_pool.shape
    if n % kv != 0:
        raise ValueError(f"q heads {n} not a multiple of kv heads {kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    scale_ = (1.0 / math.sqrt(d)) if scale is None else scale

    if window is not None and (combine_axis is not None
                               or k_scale is not None):
        raise ValueError("a window-summary cache serves neither a cp-"
                         "sharded nor an int8 pool")
    if combine_axis is not None:
        # the CP merge lives in XLA-land (collectives between the local
        # gather and the normalisation); the kernel path has no axis
        return _paged_attention_xla(q, k_pool, v_pool, pool_pos, tables,
                                    q_pos, layer, k_scale, v_scale, scale_,
                                    combine_axis=combine_axis)
    impl = paged_attention_impl(d, bs, force_pallas)
    if impl == "xla":
        return _paged_attention_xla(q, k_pool, v_pool, pool_pos, tables,
                                    q_pos, layer, k_scale, v_scale, scale_,
                                    window=window)
    return _paged_attention_pallas(q, k_pool, v_pool, pool_pos, tables,
                                   q_pos, layer, k_scale, v_scale, scale_,
                                   interpret=impl == "pallas-interpret",
                                   window=window)
