"""Block-sparse attention with a learned-free top-k selection (MiniCPM4's
InfLLM-v2): a query scores the mean-pooled keys of overlapping kernels,
the scores of a K/V group's query heads are summed, a ``block``-position
block takes the largest score of the kernels that overlap it, and the
``topk`` highest blocks (the first blocks and the local window always
among them) are attended with exact softmax. Below ``dense_len`` every
causal block is attended.

* :func:`select_blocks` is the selection, shared by every path.
* :func:`sparse_attention_full` attends a whole sequence, no cache.
* :func:`sparse_paged_attention` attends the paged pool of
  :class:`..inference.paging.SparseStatePagedCache`: the selection is
  the walk. The Pallas kernel (``sparse_paged_attention`` in a device
  trace) runs a grid of ``(rows, groups, walk width)`` where the width is
  ``max(dense_len / block_size, topk)`` whatever the context; a grid step
  fetches one selected pool block of one K/V head and multiplies it with
  the group's query heads on the MXU, masking the part of the pool block
  that was not selected. The XLA path gathers the row's whole table
  (CPU tests).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION
from .paged_attention import paged_attention_impl
from .pallas_utils import compiler_params as _compiler_params

#: what :func:`selection_counts` counts, in order
COUNT_KINDS = ("selected", "forced", "dense", "skipped", "attended",
               "skipped_positions")


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selection's geometry, in positions (``topk`` and
    ``init_blocks`` in blocks)."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    def __post_init__(self) -> None:
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                f"sparse selection: kernel {self.kernel} must be twice the "
                f"stride {self.stride} and block {self.block} whole strides")

    def walk_width(self, block_size: int, columns: int) -> int:
        """Pool blocks a row can attend: every causal one below
        ``dense_len``, at most ``topk`` selected ones beyond."""
        return min(columns, max(-(-self.dense_len // block_size), self.topk))


def compress_keys(k: jax.Array, spec: SparseSpec) -> jax.Array:
    """``k [S, ...]`` -> ``[S // stride, ...]`` float32: entry ``i`` is the
    mean of ``k[stride * i : stride * i + kernel]``; the last entry, whose
    kernel is not whole, is of no use and masked by its caller."""
    s = k.shape[0]
    pad = -s % spec.stride
    halves = jnp.pad(k.astype(jnp.float32),
                     ((0, pad),) + ((0, 0),) * (k.ndim - 1)).reshape(
        (-1, spec.stride) + k.shape[1:]).sum(1)
    nxt = jnp.concatenate([halves[1:], jnp.zeros_like(halves[:1])])
    return (halves + nxt) / spec.kernel


def select_blocks(q: jax.Array, ck: jax.Array, q_pos: jax.Array,
                  spec: SparseSpec, scale: float):
    """``q [T, G, R, D]`` (``R`` query heads a K/V group), ``ck [T, N, G,
    D]`` (or ``[N, G, D]``, shared by the rows) the compressed keys in
    position order (entry ``i`` covers ``stride * i .. + kernel``),
    ``q_pos [T]``. Returns ``(sel, forced) [T, G, N * stride / block]``
    bool: the blocks each (row, group) attends, and those of them that
    the first blocks and the local window forced. A pad row selects
    nothing; a row below ``dense_len`` every causal block."""
    t = q.shape[0]
    n = ck.shape[-3]
    per = spec.block // spec.stride
    nb = n // per
    g, r, d = q.shape[1:]
    # the groups' heads against the keys of all groups at once, each head
    # zero outside its own group's D values: the keys are contracted as
    # they lie ([.., N, G * D]), where "tgrd,tngd" moved them to [T, G, N, D]
    wide = (q.astype(jnp.float32)[:, :, :, None, :]
            * jnp.eye(g, dtype=jnp.float32)[None, :, None, :, None]
            ).reshape(t, g * r, g * d)
    keys = ck.astype(jnp.float32).reshape(ck.shape[:-2] + (g * d,))
    eq = "tqk,tnk->tqn" if ck.ndim == 4 else "tqk,nk->tqn"
    s = jnp.einsum(eq, wide, keys, precision=jax.lax.Precision.HIGHEST
                   ).reshape(t, g, r, n) * scale
    ends = jnp.arange(n) * spec.stride + spec.kernel - 1
    whole = ends[None, :] <= q_pos[:, None]                     # [T, N]
    s = jnp.where(whole[:, None, None, :], s, -jnp.inf)
    p = jnp.where(whole[:, None, None, :],
                  jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    r = jnp.where(whole[:, None, :], jnp.sum(p, 2), -1.0)       # [T, G, N]
    # kernels stride*i .. stride*i + 2*stride - 1 that overlap block b:
    # those that start inside it and the one that starts a stride before
    r4 = r.reshape(t, -1, nb, per)
    before = jnp.concatenate(
        [jnp.full_like(r4[:, :, :1, -1], -1.0), r4[:, :, :-1, -1]], 2)
    score = jnp.maximum(r4.max(-1), before)                     # [T, G, NB]
    blocks = jnp.arange(nb)
    own = (q_pos // spec.block)[:, None]
    causal = (blocks[None, :] <= own) & (q_pos < PAD_POSITION)[:, None]
    forced = causal & ((blocks[None, :] < spec.init_blocks) | (
        blocks[None, :] >= jnp.maximum(q_pos - spec.window + 1, 0)[:, None]
        // spec.block))
    score = jnp.where(forced[:, None], jnp.inf,
                      jnp.where(causal[:, None], score, -jnp.inf))
    vals, idx = jax.lax.top_k(score, min(spec.topk, nb))
    picked = jnp.any((idx[..., None] == blocks) & (vals[..., None]
                                                   > -jnp.inf), -2)
    dense = (q_pos < spec.dense_len)[:, None, None]
    sel = jnp.where(dense, causal[:, None], picked)
    return sel, forced[:, None] & ~dense & sel


def _attended(sel, q_pos, spec: SparseSpec):
    """Positions a (row, group) attends: whole selected blocks, and the
    row's own up to the row."""
    first = jnp.arange(sel.shape[-1]) * spec.block
    return jnp.sum(sel * jnp.clip(q_pos[:, None, None] - first + 1, 0,
                                  spec.block), -1)


def selection_counts(sel, forced, q_pos, spec: SparseSpec, block_size: int,
                     width: int) -> jax.Array:
    """``[6]`` int32, :data:`COUNT_KINDS`: the walk's grid steps of the
    rows by what is in them (a selected pool block, one the window or
    the first blocks forced, one of a row below ``dense_len``, nothing),
    and the causal positions attended and skipped."""
    t, g, nb = sel.shape
    per = block_size // spec.block
    cols = sel.reshape(t, g, -1, per).any(-1)
    dense = (q_pos < spec.dense_len)[:, None, None]
    by_force = forced.reshape(t, g, -1, per).any(-1) & cols
    live = jnp.sum(cols)
    n_dense = jnp.sum(cols & dense)
    n_forced = jnp.sum(by_force & ~dense)
    attended = jnp.sum(_attended(sel, q_pos, spec))
    causal = g * jnp.sum(jnp.where(q_pos < PAD_POSITION, q_pos + 1, 0))
    return jnp.stack([live - n_dense - n_forced, n_forced, n_dense,
                      t * g * width - live, attended,
                      causal - attended]).astype(jnp.int32)


def sparse_attention_full(q: jax.Array, k: jax.Array, v: jax.Array,
                          spec: SparseSpec, scale: float) -> jax.Array:
    """``q [B, S, N, D]``, ``k, v [B, S, KV, D]`` at positions ``0..S-1``
    -> ``[B, S, N, D]`` float32. Dense scores under the selection's mask:
    for tests and small training, not for long sequences."""
    b, s, n, d = q.shape
    kv = k.shape[2]
    pad = -s % spec.block
    pos = jnp.arange(s)

    def one(q1, k1, v1):
        qg = q1.reshape(s, kv, n // kv, d)
        ck = compress_keys(jnp.pad(k1, ((0, pad), (0, 0), (0, 0))), spec)
        sel, _ = select_blocks(qg, ck, pos, spec, scale)     # [S, KV, NB]
        allowed = jnp.repeat(sel, spec.block, -1)[..., :s] & (
            pos[None, :] <= pos[:, None])[:, None]
        scores = jnp.einsum("tgrd,sgd->tgrs", qg.astype(jnp.float32),
                            k1.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST) * scale
        probs = jax.nn.softmax(
            jnp.where(allowed[:, :, None], scores, -1e30), -1)
        return jnp.einsum("tgrs,sgd->tgrd", probs, v1.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST
                          ).reshape(s, n, d)

    return jax.vmap(one)(q, k, v)


# ---------------------------------------------------------------------------
# The paged pool: K/V ``[L, num_blocks, KV, block_size, D]`` (heads before
# slots: a minor pair (KV, D) of two heads would be padded to a tile of 16
# rows), compressed keys ``[L, num_blocks * block_size / stride, KV * D]``
# by the same block ids.
# ---------------------------------------------------------------------------

def write_sparse_rows(pool: jax.Array, rows: jax.Array, flat_idx: jax.Array,
                      layer) -> jax.Array:
    """Scatter ``rows [T, KV, D]`` into layer ``layer`` of ``pool [L,
    num_blocks, KV, block_size, D]`` at the flat indices of
    :func:`..inference.paging.flat_write_indices` (``== capacity`` is
    dropped). One scatter of ``T * KV`` rows into the stack addressed as
    ``[L, num_blocks * KV * block_size, D]`` by ``(layer, row)``, as
    :func:`..inference.paging.write_pool_rows` writes: in place where the
    stack is the layer scan's carry (indexed at ``(layer, block, :,
    slot)`` the scatter made XLA copy the stack into another layout and
    back)."""
    n_layers, nb, kv, bs, d = pool.shape
    heads = jnp.arange(kv, dtype=flat_idx.dtype)[None, :]
    row = ((flat_idx // bs)[:, None] * kv + heads) * bs + (
        flat_idx % bs)[:, None]
    row = jnp.where((flat_idx < nb * bs)[:, None], row, nb * kv * bs)
    flat = pool.reshape(n_layers, nb * kv * bs, d)
    flat = flat.at[layer, row.reshape(-1)].set(
        rows.reshape(-1, d).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def write_compressed_keys(ck: jax.Array, k_pool: jax.Array, layer,
                          tables: jax.Array, q_pos: jax.Array,
                          spec: SparseSpec) -> jax.Array:
    """For every row whose position completes a kernel (``q_pos == stride
    * i + kernel - 1``), the mean of the kernel's keys, read from the
    pool (this step's rows already written; a kernel may straddle two
    blocks), into entry ``i`` of the row's sequence: slot ``i % (block_size
    / stride)`` of the block that holds position ``stride * i``."""
    _, nb, kv, bs, d = k_pool.shape
    per = bs // spec.stride
    maxb = tables.shape[1]
    done = (q_pos < PAD_POSITION) & (q_pos >= spec.kernel - 1) & (
        (q_pos + 1) % spec.stride == 0)
    span = jnp.maximum(q_pos[:, None] - spec.kernel + 1, 0) + jnp.arange(
        spec.kernel)[None, :]                                   # [T, kernel]
    blk = jnp.take_along_axis(tables, jnp.minimum(span // bs, maxb - 1), 1)
    row = ((jnp.clip(blk, 0, nb - 1)[:, :, None] * kv
            + jnp.arange(kv)[None, None, :]) * bs + (span % bs)[:, :, None])
    keys = k_pool.reshape(-1, nb * kv * bs, d)[layer, row]  # [T, kernel, KV, D]
    mean = keys.astype(jnp.float32).mean(1).reshape(-1, kv * d)
    i = (q_pos - spec.kernel + 1) // spec.stride
    home = jnp.take_along_axis(
        tables, jnp.clip(i // per, 0, maxb - 1)[:, None], 1)[:, 0]
    dst = jnp.where(done & (home >= 0), home * per + i % per, nb * per)
    return ck.at[layer, dst].set(mean.astype(ck.dtype), mode="drop")


def gather_compressed_keys(ck: jax.Array, layer, tables: jax.Array,
                           spec: SparseSpec, block_size: int, kv: int):
    """``[T, max_blocks_per_seq * block_size / stride, KV, D]``: each
    row's sequence's compressed keys in position order (an unmapped
    column's are another sequence's, and lie beyond the row's position)."""
    per = block_size // spec.stride
    idx = (jnp.maximum(tables, 0)[:, :, None] * per
           + jnp.arange(per)[None, None, :]).reshape(tables.shape[0], -1)
    got = ck[layer, idx]                                   # [T, N, KV * D]
    return got.reshape(got.shape[:2] + (kv, -1))


def _sparse_paged_xla(q, k_pool, v_pool, layer, tables, q_pos, sel, spec,
                      scale):
    t, g, r, d = q.shape
    _, nb, kv, bs, _ = k_pool.shape
    safe = jnp.clip(tables, 0, nb - 1)
    kg = k_pool[layer, safe].swapaxes(2, 3).reshape(t, -1, kv, d)
    vg = v_pool[layer, safe].swapaxes(2, 3).reshape(t, -1, kv, d)
    pos = jnp.arange(kg.shape[1])
    allowed = (jnp.repeat(sel, spec.block, -1)
               & (pos[None, :] <= q_pos[:, None])[:, None]
               & jnp.repeat(tables >= 0, bs, -1)[:, None])     # [T, G, P]
    scores = jnp.einsum("tgrd,tpgd->tgrp", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(jnp.where(allowed[:, :, None], scores, -1e30), -1)
    return jnp.einsum("tgrp,tpgd->tgrd", probs, vg.astype(jnp.float32))


def sparse_walk(sel: jax.Array, tables: jax.Array, spec: SparseSpec,
                block_size: int, width: int):
    """The kernel's walk, ``(blocks, marks) [T, G * width]`` int32. Grid
    step ``j`` of (row, group) fetches pool block ``blocks[.., j] >= 0``
    and attends the parts ``marks & parts`` of it (a pool block is
    ``block_size / block`` selection blocks; ``marks >> parts`` is the
    block's table column, which says what positions it holds); past the
    (row, group)'s selected pool blocks ``blocks`` is the complement of
    the last one's id: nothing is computed, and the repeated index elides
    the DMA."""
    t, g, _ = sel.shape
    per = block_size // spec.block
    maxb = tables.shape[1]
    parts = jnp.sum(sel.reshape(t, g, maxb, per).astype(jnp.int32)
                    << jnp.arange(per, dtype=jnp.int32), -1)    # [T, G, maxb]
    live = (parts > 0) & (tables >= 0)[:, None]
    cols = jnp.arange(maxb, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(live, cols, maxb + cols), -1)[..., :width]
    count = jnp.sum(live, -1, keepdims=True)
    blk = jnp.take_along_axis(
        jnp.broadcast_to(jnp.maximum(tables, 0)[:, None], live.shape),
        order, -1)
    last = jnp.take_along_axis(blk, jnp.maximum(count - 1, 0), -1)
    step = jnp.arange(width, dtype=jnp.int32)
    blocks = jnp.where(step < count, blk, ~last)
    marks = (order << per) | jnp.take_along_axis(parts, order, -1)
    return (blocks.reshape(t, g * width).astype(jnp.int32),
            marks.reshape(t, g * width).astype(jnp.int32))


def _sparse_kernel(blocks_ref, marks_ref, qpos_ref, layer_ref, q_ref, k_ref,
                   v_ref, o_ref, m_ref, l_ref, acc_ref, *, width: int,
                   per: int, part: int, scale: float):
    """One (row, group, walk step): the group's ``R`` query heads against
    one pool block of the group's K/V head, ``[R, D] x [D, BS]`` on the
    MXU, online softmax in float32 scratch."""
    from jax.experimental import pallas as pl

    t, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(blocks_ref[t, g * width + j] >= 0)
    def _accumulate():
        mark = marks_ref[t, g * width + j]
        bs = k_ref.shape[0]
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # [R, BS]
        slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = ((mark >> per) * bs + slot <= qpos_ref[t]) & (
            ((mark >> (slot // part)) & 1) == 1)
        s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_ref[...]                                       # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == width - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _sparse_paged_pallas(q, k_pool, v_pool, layer, tables, q_pos, sel, spec,
                         scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, g, r, d = q.shape
    bs = k_pool.shape[3]
    width = spec.walk_width(bs, tables.shape[1])
    blocks, marks = sparse_walk(sel, tables.astype(jnp.int32), spec, bs,
                                width)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row(ti, gi, j, *_):
        return (ti, gi, 0, 0)

    def block(ti, gi, j, blocks_s, marks_s, qpos_s, layer_s):
        b = blocks_s[ti, gi * width + j]
        return (layer_s[0], jnp.where(b < 0, ~b, b), gi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t, g, width),
        in_specs=[pl.BlockSpec((None, None, r, d), row),
                  pl.BlockSpec((None, None, None, bs, d), block),
                  pl.BlockSpec((None, None, None, bs, d), block)],
        out_specs=pl.BlockSpec((None, None, r, d), row),
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_sparse_kernel, width=width,
                          per=bs // spec.block, part=spec.block,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, g, r, d), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="sparse_paged_attention",
    )(blocks, marks, q_pos.astype(jnp.int32), layer, q, k_pool, v_pool)


def sparse_paged_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, ck: jax.Array, layer,
                           tables: jax.Array, q_pos: jax.Array,
                           spec: SparseSpec, scale: Optional[float] = None,
                           force_pallas: Optional[bool] = None):
    """Select and attend. ``q [T, N, D]`` one query row a packed token;
    ``k_pool, v_pool [L, num_blocks, KV, block_size, D]`` and ``ck [L,
    num_blocks * block_size / stride, KV * D]`` the stacks, read at
    ``layer``; ``tables [T, max_blocks_per_seq]`` per-token block tables
    (position ``p`` in column ``p // block_size``); ``q_pos [T]``.
    Returns ``(out [T, N, D], counts [6] int32)``
    (:func:`selection_counts`). ``force_pallas`` as
    :func:`.paged_attention.paged_attention`."""
    t, n, d = q.shape
    _, _, kv, bs, _ = k_pool.shape
    if bs % spec.block:
        raise ValueError(f"pool blocks of {bs} positions are not whole "
                         f"selection blocks of {spec.block}")
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    qg = q.reshape(t, kv, n // kv, d)
    sel, forced = select_blocks(
        qg, gather_compressed_keys(ck, layer, tables, spec, bs, kv), q_pos,
        spec, scale)
    counts = selection_counts(sel, forced, q_pos, spec, bs,
                              spec.walk_width(bs, tables.shape[1]))
    impl = paged_attention_impl(d, bs, force_pallas)
    if impl == "xla":
        out = _sparse_paged_xla(qg, k_pool, v_pool, layer, tables, q_pos,
                                sel, spec, scale)
    else:
        out = _sparse_paged_pallas(qg, k_pool, v_pool, layer, tables, q_pos,
                                   sel, spec, scale,
                                   interpret=impl == "pallas-interpret")
    return out.reshape(t, n, d).astype(q.dtype), counts
