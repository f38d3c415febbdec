"""Block-sparse attention with a learned-free top-k selection (MiniCPM4's
InfLLM-v2): a query scores the mean-pooled keys of overlapping kernels,
the scores of a K/V group's query heads are summed, a ``block``-position
block takes the largest score of the kernels that overlap it, and the
``topk`` highest blocks (the first blocks and the local window always
among them) are attended with exact softmax. Below ``dense_len`` every
causal block is attended.

* The selection is its scores (:func:`key_scores`) and what follows them
  (:func:`blocks_of_scores`: the mask of whole kernels, the softmax, the
  block maxima, the forced blocks, the exact top-k), one tail for every
  path. Where the scores come from is the path's: :func:`select_blocks`
  multiplies the queries with a sequence's whole compressed keys (the
  no-cache forward, and the XLA path of the paged pool over
  :func:`gather_compressed_keys`, each row's whole table: the CPU tests
  and the kernel's reference); on the chip the ``compressed_key_scores``
  kernel (:func:`_score_kernel`) reads the keys where they lie in the
  pool's stack, by block id: a tile of packed rows, both K/V groups'
  heads stacked, against a unit of :data:`SCORE_LANES` compressed keys
  (16 table columns of 8) at a time; the distinct pool blocks of a
  column are its *layers*, a layer's keys are copied once (one
  ``[per, G x D]`` piece a column, into one of two VMEM buffers while
  the layer before is multiplied) and serve every row of the tile whose
  table names them, so a prefill chunk's rows read their slot's keys
  once and a decode row's keys are multiplied for its part of the tile
  alone. Which pairs a tile scores follows the tables and positions
  alone: :func:`score_walk`, built once a step for all sparse layers. A
  row below ``dense_len``, a pad row and a column beyond the row's
  position name no pair; their scores are 0 and never read.
* :func:`sparse_attention_full` attends a whole sequence, no cache.
* :func:`sparse_paged_attention` attends the paged pool of
  :class:`..inference.paging.SparseStatePagedCache`: the selection is
  the walk. The Pallas kernel (``sparse_paged_attention`` in a device
  trace) has :mod:`.paged_attention`'s unit with a body of its own: a
  *tile* of consecutive packed rows of one K/V group, the group's query
  heads stacked head by head (``[heads x rows, D]``: one tile of 128 rows
  of 16 heads at the published widths, :func:`tile_height`), against a
  *pair* (table column, pool block) that at least one row of the tile
  selected, was forced to, or attends densely. The grid is ``(tiles,
  groups)``; inside, a loop over the (tile, group)'s pairs copies each
  pair's K and V block of the group's head (one contiguous ``[block_size,
  D]`` of the pool's heads-before-slots layout) once from the stacks in
  HBM into one of two VMEM buffers while the pair before it is computed,
  and multiplies on the MXU: scores from the stored operands into
  float32, ``p x v`` with ``p`` float32
  (:func:`.paged_attention._p_times_v`), the running max, sum and
  accumulator float32 scratch. So a prefill chunk's rows fetch a block
  their slot holds once and not once a row, and the selection is a mask:
  a row attends, of a pair, the causal positions of the selection blocks
  it picked in that pool block (its ``parts`` bits); a row that does not
  name the pair is all ``-inf``; a pair that lies inside one part of the
  tile (:func:`narrow_height` rows: a decode row's, whose neighbours are
  other slots') runs over that part of every head alone.

  The walk (:func:`sparse_tile_walk`) depends on the layer's queries, so
  it is built on the device once a layer, from the selection and the
  tables, without a sort: a pair belongs to the first row of its tile
  that attends it (:func:`first_namers`, an all-pairs comparison of the
  tile's rows), and each row's pairs (at most
  :meth:`SparseSpec.walk_width`) are put in order by a one-hot of their
  rank. The XLA path gathers the row's whole table (CPU tests).

Which path runs follows :func:`.paged_attention.paged_attention_impl`,
for the scores and the attention alike.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION
from .paged_attention import _p_times_v, paged_attention_impl
from .pallas_utils import compiler_params as _compiler_params

#: ``cache.counts``, in order: what :func:`selection_counts` counts, then
#: the live (row, group, column) a tile fetches a pool block for
#: (:func:`first_namers`) and those an earlier row's fetch serves, then
#: the same two of the score kernel's compressed keys (:func:`score_walk`)
COUNT_KINDS = ("selected", "forced", "dense", "skipped", "attended",
               "skipped_positions", "fetched", "shared", "keys_fetched",
               "keys_shared")

#: VMEM a tile's float32 accumulator may take (:func:`tile_height`)
ACC_BYTES = 1 << 20
#: compressed keys of a unit of the score kernel: one product's width and
#: the lanes of the block of scores it writes (:func:`score_walk`)
SCORE_LANES = 128


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


def key_scores(q: jax.Array, ck: jax.Array, scale: float) -> jax.Array:
    """``q [T, G, R, D]`` (``R`` query heads a K/V group) against ``ck [T,
    N, G, D]`` (or ``[N, G, D]``, shared by the rows), the compressed keys
    in position order: the scaled scores ``[T, G, R, N]`` float32."""
    t = q.shape[0]
    n = ck.shape[-3]
    g, r, d = q.shape[1:]
    # the groups' heads against the keys of all groups at once, each head
    # zero outside its own group's D values: the keys are contracted as
    # they lie ([.., N, G * D]), where "tgrd,tngd" moved them to [T, G, N, D]
    wide = (q.astype(jnp.float32)[:, :, :, None, :]
            * jnp.eye(g, dtype=jnp.float32)[None, :, None, :, None]
            ).reshape(t, g * r, g * d)
    keys = ck.astype(jnp.float32).reshape(ck.shape[:-2] + (g * d,))
    eq = "tqk,tnk->tqn" if ck.ndim == 4 else "tqk,nk->tqn"
    return jnp.einsum(eq, wide, keys, precision=jax.lax.Precision.HIGHEST
                      ).reshape(t, g, r, n) * scale


def blocks_of_scores(s: jax.Array, q_pos: jax.Array, spec: SparseSpec):
    """The selection from its scores ``s [T, G, R, N]`` (entry ``i`` of
    ``N`` is the kernel of positions ``stride * i .. + kernel``; what lies
    at a kernel that is not whole for the row, or at a row below
    ``dense_len``, is never read, and must be no NaN) and ``q_pos [T]``.
    Returns ``(sel, forced) [T, G, N * stride / block]`` bool: the blocks
    each (row, group) attends, and those of them that the first blocks
    and the local window forced. A pad row selects nothing; a row below
    ``dense_len`` every causal block."""
    t, _, _, n = s.shape
    per = spec.block // spec.stride
    nb = n // per
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


def select_blocks(q: jax.Array, ck: jax.Array, q_pos: jax.Array,
                  spec: SparseSpec, scale: float):
    """:func:`blocks_of_scores` of :func:`key_scores`: the selection of
    ``q [T, G, R, D]`` over whole compressed keys ``ck`` in position
    order (the no-cache forward, the XLA path over
    :func:`gather_compressed_keys`)."""
    return blocks_of_scores(key_scores(q, ck, scale), q_pos, spec)


def _attended(sel, q_pos, spec: SparseSpec):
    """Positions a (row, group) attends: whole selected blocks, and the
    row's own up to the row."""
    first = jnp.arange(sel.shape[-1]) * spec.block
    return jnp.sum(sel * jnp.clip(q_pos[:, None, None] - first + 1, 0,
                                  spec.block), -1)


def selection_counts(sel, forced, q_pos, spec: SparseSpec, block_size: int,
                     width: int) -> jax.Array:
    """``[6]`` int32, the first six of :data:`COUNT_KINDS`: the ``width``
    table columns a (row, group) can attend (:meth:`SparseSpec.walk_width`)
    by what is in them (a selected pool block, one the window or the
    first blocks forced, one of a row below ``dense_len``, nothing), and
    the causal positions attended and skipped."""
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


def narrow_height(dtype) -> int:
    """Rows of the part of a tile that a pair named inside it alone runs
    over (a decode row's pairs: its neighbours are other slots'): one
    tile of sublanes of the queries' dtype, 8 rows of float32 and 16 of
    bf16, so that the part of every head is whole vregs."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def tile_height(tokens: int, heads: int, head_dim: int, dtype) -> int:
    """Packed rows of a tile of the kernel, from the shapes alone: as
    many as there are, in whole parts (:func:`narrow_height`), while the
    tile's float32 accumulator, ``rows x heads x head_dim``, stays within
    :data:`ACC_BYTES` of VMEM. A tile that spans a prefill chunk lists a
    pool block once for all the chunk's rows and turns the selection into
    a mask; measured on the v5e at 8, 32 and 128 rows of 16 heads of 128
    (``PERF.md``, Findings, PR 35), the tallest won."""
    part = narrow_height(dtype)
    fit = max(part, ACC_BYTES // (heads * head_dim * 4) // part * part)
    return min(fit, -(-tokens // part) * part)


def _tile_queries(q: jax.Array, rows: int) -> jax.Array:
    """``q [T, G, R, D]`` -> ``[tiles, G, R, rows, D]``: a tile's queries
    a group at a time, head by head, as the kernels stack them; the last
    tile filled up with rows of zeros."""
    t, g, heads, d = q.shape
    return jnp.pad(q, ((0, -t % rows), (0, 0), (0, 0), (0, 0))).reshape(
        -1, rows, g, heads, d).transpose(0, 2, 3, 1, 4)


class SparseWalk(NamedTuple):
    """What the kernel is handed of one layer's selection
    (:func:`sparse_tile_walk`). The packed rows, padded to whole tiles,
    are ``Tp``; ``W`` is :meth:`SparseSpec.walk_width`. A pair (table
    column, pool block) of a (tile, group) is listed by the first row of
    the tile that attends it, so the lists are a row's: ``count [Tp * G]``
    the pairs (row, group) lists, ``blocks`` and ``marks [Tp * G * W]``
    each pair's pool block and ``column * 2 + narrow`` (``narrow``: every
    row that attends the pair lies in the lister's part of
    :func:`narrow_height` rows); ``after [Tp * G]`` the next row of the
    tile that lists any (the tile's height if none); ``total`` and
    ``start [tiles * G]`` a (tile, group)'s pairs and the first row that
    lists any. ``keys [tiles, G, rows, max_blocks_per_seq]`` is what a
    row attends of each column: ``block << per | bits`` with bit ``i``
    the ``i``-th of the pool block's ``per`` selection blocks, 0 for
    nothing."""

    total: jax.Array
    start: jax.Array
    count: jax.Array
    after: jax.Array
    blocks: jax.Array
    marks: jax.Array
    keys: jax.Array


def column_parts(sel: jax.Array, tables: jax.Array, per: int) -> jax.Array:
    """``[T, G, max_blocks_per_seq]`` int32: the selection blocks (bits,
    ``per`` a pool block) that (row, group) attends of each mapped table
    column; 0 where it attends none of it."""
    t, g, _ = sel.shape
    parts = jnp.sum(sel.reshape(t, g, -1, per).astype(jnp.int32)
                    << jnp.arange(per, dtype=jnp.int32), -1)
    return jnp.where((tables >= 0)[:, None], parts, 0)


def _tile_namers(parts, tables, rows: int):
    """``parts [T, G, maxb]`` and ``tables [T, maxb]`` in tiles of ``rows``
    rows (padded with rows that attend nothing), ``[tiles, rows, ...]``
    each, and ``same [tiles, rows, rows, G, maxb]``: whether row ``r2``
    (third axis) of the tile attends, in the column, the pool block that
    row ``r`` has there."""
    t, g, maxb = parts.shape
    pad = -t % rows
    parts = jnp.pad(parts, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, rows, g, maxb)
    tables = jnp.pad(tables, ((0, pad), (0, 0))).reshape(-1, rows, maxb)
    same = ((tables[:, :, None] == tables[:, None])[:, :, :, None]
            & (parts > 0)[:, None])
    return parts, tables, same


def _none_among(same, among):
    """Of ``same`` (:func:`_tile_namers`), whether no row ``r2`` with
    ``among [rows, rows]`` (by ``r``, ``r2``) attends row ``r``'s block."""
    return ~jnp.any(same & among[None, :, :, None, None], axis=2)


def _first(parts, same):
    """Of a tile's ``parts`` and ``same`` (:func:`_tile_namers`), the live
    (row, group, column) whose pool block no earlier row attends."""
    r = jnp.arange(parts.shape[1], dtype=jnp.int32)
    return (parts > 0) & _none_among(same, r[None, :] < r[:, None])


def first_namers(parts, tables, rows: int):
    """``[tiles, rows, G, maxb]`` bool: the live (row, group, column)
    whose pool block no earlier row of the tile attends in that column:
    the tile fetches the block for it (and for the later rows that name
    it). Rows of one slot share a table, so a prefill chunk's rows are
    served by their first; membership is by the table entry, any order of
    rows is correct."""
    parts, _, same = _tile_namers(parts, tables, rows)
    return _first(parts, same)


def sparse_tile_walk(parts: jax.Array, tables: jax.Array, rows: int,
                     part_rows: int, width: int, per: int) -> SparseWalk:
    """The kernel's walk of one layer, built on the device from the
    layer's selection (``parts``, :func:`column_parts`) and the tables,
    without a sort: a tile's pairs are its first namers
    (:func:`first_namers`), a row's are put in order of column by their
    rank among the row's (at most ``width``: what a row can attend)."""
    parts, tables, same = _tile_namers(parts, tables, rows)
    r = jnp.arange(rows, dtype=jnp.int32)
    first = _first(parts, same)
    narrow = _none_among(
        same, r[None, :] >= (r[:, None] // part_rows + 1) * part_rows)
    rank = jnp.cumsum(first, axis=-1, dtype=jnp.int32) - first
    count = jnp.sum(first, axis=-1, dtype=jnp.int32)      # [tiles, rows, G]
    cols = jnp.arange(tables.shape[-1], dtype=jnp.int32)
    slot = first[..., None, :] & (
        rank[..., None, :] == jnp.arange(width, dtype=jnp.int32)[:, None])
    blocks = jnp.sum(jnp.where(slot, tables[:, :, None, None, :], 0), -1)
    marks = jnp.sum(jnp.where(slot, (cols * 2 + narrow)[..., None, :], 0),
                    -1)
    lists = jnp.where(count > 0, r[:, None], rows)        # [tiles, rows, G]
    later = (r[None, :] > r[:, None])[None, :, :, None]
    after = jnp.min(jnp.where(later, lists[:, None], rows), axis=2)
    keys = jnp.where(parts > 0, (tables[:, :, None] << per) | parts, 0)
    return SparseWalk(
        total=jnp.sum(count, axis=1).reshape(-1),
        start=jnp.min(lists, axis=1).reshape(-1).astype(jnp.int32),
        count=count.reshape(-1), after=after.reshape(-1).astype(jnp.int32),
        blocks=blocks.reshape(-1).astype(jnp.int32),
        marks=marks.reshape(-1).astype(jnp.int32),
        keys=keys.swapaxes(1, 2).astype(jnp.int32))


def _sparse_kernel(total_ref, start_ref, count_ref, after_ref, blocks_ref,
                   marks_ref, layer_ref, keys_ref, qpos_ref, q_ref, k_hbm,
                   v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
                   *, part_rows: int, width: int, per: int, part: int,
                   scale: float):
    """One tile of packed rows and one K/V group against the pool blocks
    the tile's rows attend: a loop over the (tile, group)'s pairs, row by
    listing row (:class:`SparseWalk`), each pair's K and V block of the
    group's head copied once from the stacks in HBM into one of two VMEM
    buffers while the pair before it is computed.

    The group's query heads are stacked head by head (``q_ref [heads,
    rows, D]``), so a row's mask serves every head as it is: scores
    ``[heads * rows, D] x [D, block_size]`` on the MXU from the stored
    operands into float32, masked by row (the positions of the pair's
    column that are causal and lie in a selection block the row attends
    of this very pool block; a row that does not name the pair is all
    ``-inf``), the online softmax in float32 scratch, ``p x v`` with
    ``p`` float32 (:func:`.paged_attention._p_times_v`). A pair marked
    narrow runs over its lister's ``part_rows`` rows of every head
    alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, g = pl.program_id(0), pl.program_id(1)
    groups = pl.num_programs(1)
    heads, rows, d = q_ref.shape
    bs = k_buf.shape[1]
    total = total_ref[tile * groups + g]
    layer = layer_ref[0]
    operand = (jnp.bfloat16 if q_ref.dtype == jnp.bfloat16
               and k_buf.dtype == jnp.bfloat16 else jnp.float32)

    def lists(r):
        return (tile * rows + r) * groups + g

    def copies(r, k, slot):
        """The copies of pair ``k`` of row ``r``'s list into ``slot``."""
        b = blocks_ref[lists(r) * width + k]
        return [pltpu.make_async_copy(src.at[layer, b, g], buf.at[slot],
                                      sems.at[slot, i])
                for i, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    first = start_ref[tile * groups + g]

    @pl.when(total > 0)
    def _first():
        for c in copies(first, 0, 0):
            c.start()

    def pair(i, at):
        r, k = at
        slot = i % 2
        last = k + 1 >= count_ref[lists(r)]
        nxt = (jnp.where(last, after_ref[lists(r)], r),
               jnp.where(last, 0, k + 1))

        @pl.when(i + 1 < total)
        def _next():
            for c in copies(*nxt, 1 - slot):
                c.start()

        for c in copies(r, k, slot):
            c.wait()
        block = blocks_ref[lists(r) * width + k]
        mark = marks_ref[lists(r) * width + k]
        col = mark >> 1
        keys = k_buf[slot].astype(operand)                  # [bs, D]
        values = v_buf[slot].astype(operand)

        def attend(rs, n):
            """The pair against rows ``rs`` (``n`` of them) of the tile."""
            key = keys_ref[rs, :]                           # [n, maxb]
            column = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
            mine = jnp.max(jnp.where(column == col, key, 0), axis=1,
                           keepdims=True)                   # [n, 1]
            bits = jnp.where((mine >> per) == block, mine, 0)
            pos = jax.lax.broadcasted_iota(jnp.int32, (n, bs), 1)
            ok = (((bits >> (pos // part)) & 1) == 1) & (
                col * bs + pos <= qpos_ref[rs, :])          # [n, bs]
            s = jax.lax.dot_general(
                q_ref[:, rs, :].reshape(heads * n, d).astype(operand), keys,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok[None], s.reshape(heads, n, bs), -jnp.inf)
            m_prev = m_ref[:, rs, :]                        # [heads, n, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            # a masked score is -inf and m_safe is finite: p is 0 there,
            # and a row that has seen nothing yet corrects by 0
            p = jnp.exp(s - m_safe)
            corr = jnp.exp(m_prev - m_safe)
            m_ref[:, rs, :] = m_new
            l_ref[:, rs, :] = l_ref[:, rs, :] * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[:, rs, :] = acc_ref[:, rs, :] * corr + _p_times_v(
                p.reshape(heads * n, bs), values).reshape(heads, n, d)

        if part_rows >= rows:
            attend(slice(None), rows)
        else:
            @pl.when(mark & 1 == 1)
            def _narrow():
                attend(pl.ds(pl.multiple_of(r // part_rows * part_rows,
                                            part_rows), part_rows),
                       part_rows)

            @pl.when(mark & 1 == 0)
            def _whole():
                attend(slice(None), rows)
        return nxt

    jax.lax.fori_loop(0, total, pair, (first, jnp.int32(0)))
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def _sparse_paged_pallas(q, k_pool, v_pool, layer, tables, q_pos, parts,
                         spec, scale, interpret=False):
    """``q [T, G, R, D]``, ``parts`` :func:`column_parts`. Returns the
    output ``[T, G, R, D]`` and the pairs the tiles fetched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..obs.device_scopes import device_scope

    t, g, heads, d = q.shape
    bs = k_pool.shape[3]
    maxb = tables.shape[1]
    per = bs // spec.block
    width = spec.walk_width(bs, maxb)
    rows = tile_height(t, heads, d, q.dtype)
    part_rows = narrow_height(q.dtype)
    with device_scope("attn.walk"):
        walk = sparse_tile_walk(parts, tables.astype(jnp.int32), rows,
                                part_rows, width, per)
    tiles = walk.keys.shape[0]
    positions = jnp.pad(q_pos.astype(jnp.int32), (0, tiles * rows - t),
                        constant_values=PAD_POSITION).reshape(tiles, rows, 1)

    def head_block():
        return pl.BlockSpec((None, None, heads, rows, d),
                            lambda i, j, *_: (i, j, 0, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, part_rows=part_rows, width=width,
                          per=per, part=spec.block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(tiles, g),
            in_specs=[pl.BlockSpec((None, None, rows, maxb),
                                   lambda i, j, *_: (i, j, 0, 0)),
                      pl.BlockSpec((None, rows, 1),
                                   lambda i, j, *_: (i, 0, 0)),
                      head_block(), hbm, hbm],
            out_specs=head_block(),
            scratch_shapes=[
                pltpu.VMEM((2, bs, d), k_pool.dtype),
                pltpu.VMEM((2, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles, g, heads, rows, d), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="sparse_paged_attention",
    )(walk.total, walk.start, walk.count, walk.after, walk.blocks,
      walk.marks, jnp.asarray(layer, jnp.int32).reshape(1), walk.keys,
      positions, _tile_queries(q, rows), k_pool, v_pool)
    return out.transpose(0, 3, 1, 2, 4).reshape(
        tiles * rows, g, heads, d)[:t], jnp.sum(walk.total)


# ---------------------------------------------------------------------------
# The selection's scores from the pool: a tile's rows against the
# compressed keys of the (table column, pool block) pairs they score.
# ---------------------------------------------------------------------------

class ScoreWalk(NamedTuple):
    """What the score kernel is handed of a step's routing
    (:func:`score_walk`), the same for every sparse layer. ``visits [2]``
    int32: the (tile, column, block) whose keys a layer's kernel copies,
    and the further (row, column) each copy serves. The rest is ``None``
    where the XLA path serves. A tile's columns lie in *units* of
    :data:`SCORE_LANES` compressed keys; of a unit, *layer* ``j`` is the
    ``j``-th distinct pool block of each of its columns (those that rows
    of several parts of :func:`narrow_height` rows name before those of
    one part, each in the order of the rows that name them first):
    ``depth [tiles * units]`` the layers of a unit, ``blocks [tiles *
    units * rows * columns a unit]`` a layer's pool block a column (-1:
    none), ``narrow [tiles * units * rows]`` the one part of the tile
    that names a layer's blocks (-1: rows of several), ``lanes [tiles,
    rows, units x SCORE_LANES]`` the layer that holds a row's keys of
    each compressed key's column (-1: the row scores nothing there)."""

    visits: jax.Array
    depth: Optional[jax.Array] = None
    blocks: Optional[jax.Array] = None
    narrow: Optional[jax.Array] = None
    lanes: Optional[jax.Array] = None


def scored_columns(tables: jax.Array, q_pos: jax.Array, spec: SparseSpec,
                   block_size: int) -> jax.Array:
    """``[T, max_blocks_per_seq]`` bool: the table columns whose
    compressed keys a row's selection reads: mapped, of a row at or past
    ``dense_len`` that is no padding, and holding a kernel that is whole
    at the row's position (the column's first is)."""
    first_end = (jnp.arange(tables.shape[1]) * block_size + spec.kernel - 1)
    return ((tables >= 0) & (first_end[None, :] <= q_pos[:, None])
            & ((q_pos >= spec.dense_len) & (q_pos < PAD_POSITION))[:, None])


def _score_units(columns: int, per: int):
    """Table columns of a unit of the score kernel (:data:`SCORE_LANES`
    compressed keys, ``per`` a column; every column where there are
    fewer) and the units of a table."""
    ucols = min(max(1, SCORE_LANES // per), columns)
    return ucols, -(-columns // ucols)


def score_walk(tables: jax.Array, q_pos: jax.Array, spec: SparseSpec,
               block_size: int, heads: int, head_dim: int, dtype,
               force_pallas: Optional[bool] = None) -> ScoreWalk:
    """The score kernel's walk of one packed step, from ``tables [T,
    max_blocks_per_seq]`` and ``q_pos [T]`` alone (never a layer's
    queries): built once a step and handed to every sparse layer. A pair
    (column, block) belongs to the first row of its tile that scores it
    (:func:`first_namers`: rows of one slot, and slots that share a
    prefix block, are served by one copy); a row below ``dense_len``, a
    pad row, an unmapped column and a column beyond the row's position
    name none. Without a sort or a gather: a column's pairs are ranked by
    running counts of their first namers and laid out by a one-hot of
    the rank."""
    t, maxb = tables.shape
    rows = tile_height(t, heads, head_dim, dtype)
    part_rows = narrow_height(dtype)
    tables = tables.astype(jnp.int32)
    live = scored_columns(tables, q_pos, spec, block_size)
    parts, tiled, same = _tile_namers(live[:, None].astype(jnp.int32),
                                      tables, rows)
    first = _first(parts, same)[:, :, 0]                # [tiles, rows, maxb]
    fetched = jnp.sum(first)
    visits = jnp.stack([fetched, jnp.sum(live) - fetched]).astype(jnp.int32)
    if paged_attention_impl(head_dim, block_size, force_pallas) == "xla":
        return ScoreWalk(visits=visits)
    per = block_size // spec.stride
    ucols, units = _score_units(maxb, per)
    same, live = same[:, :, :, 0], parts[:, :, 0] > 0
    # a column's pairs that rows of several parts name come first, then
    # those of one part (a decode row's), each in the order of their first
    # namers: the layers past a chunk's are narrow
    part = jnp.arange(rows, dtype=jnp.int32) // part_rows
    wide = jnp.any(same & (part[:, None] != part[None, :])[None, :, :, None],
                   axis=2)
    ahead, behind = first & wide, first & ~wide
    rank = jnp.where(
        wide, jnp.cumsum(ahead, axis=1, dtype=jnp.int32) - ahead,
        jnp.sum(ahead, axis=1, keepdims=True, dtype=jnp.int32)
        + jnp.cumsum(behind, axis=1, dtype=jnp.int32) - behind)
    # the layer of a row's block: the rank of the block's first namer
    layer = jnp.where(live, jnp.sum(jnp.where(
        same & first[:, None], rank[:, None], 0), axis=2), -1)
    # layer j of a column: its pair of rank j, by a one-hot of the rank;
    # with the block, the part of the tile that names it (``rows``, which
    # is no part, for a pair that several do)
    at = first[:, None] & (rank[:, None] == jnp.arange(
        rows, dtype=jnp.int32)[:, None, None])      # [tiles, j, rows, maxb]
    blocks = jnp.sum(jnp.where(at, tiled[:, None] + 1, 0), axis=2) - 1
    namer = jnp.sum(jnp.where(
        at, jnp.where(wide, rows, part[:, None])[:, None] + 1, 0),
        axis=2) - 1

    def by_unit(x, fill):
        """``[tiles, n, maxb]`` -> ``[tiles, units, n, ucols]``."""
        x = jnp.pad(x, ((0, 0), (0, 0), (0, units * ucols - maxb)),
                    constant_values=fill)
        return x.reshape(x.shape[:2] + (units, ucols)).swapaxes(1, 2)

    depth = jnp.max(by_unit(jnp.sum(first, axis=1, dtype=jnp.int32)[:, None],
                            0), axis=(2, 3))
    namer = by_unit(namer, -1)
    lo = jnp.min(jnp.where(namer >= 0, namer, rows), axis=-1)
    hi = jnp.max(namer, axis=-1)
    lanes = jnp.repeat(jnp.pad(
        layer, ((0, 0), (0, 0), (0, units * ucols - maxb)),
        constant_values=-1), per, axis=-1)
    narrow = jnp.where((lo == hi) & (lo < rows), lo, -1)
    return ScoreWalk(
        visits=visits, depth=depth.reshape(-1),
        blocks=by_unit(blocks, -1).reshape(-1).astype(jnp.int32),
        narrow=narrow.reshape(-1).astype(jnp.int32), lanes=lanes)


def _score_kernel(depth_ref, blocks_ref, narrow_ref, layer_ref, lanes_ref,
                  q_ref, ck_hbm, s_ref, key_buf, sems, *, part_rows: int,
                  per: int, scale: float):
    """One tile of packed rows, every K/V group's query heads stacked head
    by head (``q_ref [G, heads, rows, D]``), against one unit of its
    columns (:class:`ScoreWalk`): a loop over the unit's layers, each
    layer's compressed keys (``per`` entries a pool block, the groups'
    side by side as the pool holds them) copied from the stack in HBM
    into one of two VMEM buffers while the layer before it is multiplied,
    ``[heads * rows, D] x [D, keys]`` a group on the MXU from the stored
    operands into float32. A row keeps, of a layer's scores, those of the
    columns where the layer holds its own block; what no layer serves
    stays 0. A layer that one part of the tile names alone is multiplied
    for that part of every head alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, unit = pl.program_id(0), pl.program_id(1)
    groups, heads, rows, d = q_ref.shape
    lanes = key_buf.shape[1]
    ucols = lanes // per
    at = tile * pl.num_programs(1) + unit
    depth = depth_ref[at]
    layer = layer_ref[0]
    operand = (jnp.bfloat16 if q_ref.dtype == jnp.bfloat16
               and key_buf.dtype == jnp.bfloat16 else jnp.float32)

    def copies(j, slot, then):
        # the copies of layer ``j``: a block a column, where it has one
        def column(c, carry):
            b = blocks_ref[(at * rows + j) * ucols + c]

            @pl.when(b >= 0)
            def _block():
                then(pltpu.make_async_copy(
                    ck_hbm.at[layer, pl.ds(pl.multiple_of(b * per, per),
                                           per)],
                    key_buf.at[slot, pl.ds(pl.multiple_of(c * per, per),
                                           per)],
                    sems.at[slot, c]))
            return carry

        jax.lax.fori_loop(0, ucols, column, 0)

    s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(depth > 0)
    def _first():
        copies(0, 0, lambda c: c.start())

    def one(j, carry):
        slot = j % 2

        @pl.when(j + 1 < depth)
        def _next():
            copies(j + 1, 1 - slot, lambda c: c.start())

        copies(j, slot, lambda c: c.wait())
        keys = key_buf[slot].astype(operand)              # [lanes, G * D]

        def score(rs, n):
            """The layer against rows ``rs`` (``n`` of them) of the tile;
            a column the layer has no block for holds another layer's
            keys or none, and no row keeps its scores."""
            mine = (lanes_ref[rs, :] == j)[None]          # [1, n, lanes]
            for g in range(groups):
                s = jax.lax.dot_general(
                    q_ref[g, :, rs, :].reshape(heads * n, d).astype(operand),
                    keys[:, g * d:(g + 1) * d], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s_ref[g, :, rs, :] = jnp.where(
                    mine, s.reshape(heads, n, lanes), s_ref[g, :, rs, :])

        if part_rows >= rows:
            score(slice(None), rows)
        else:
            part = narrow_ref[at * rows + j]

            @pl.when(part >= 0)
            def _narrow():
                score(pl.ds(pl.multiple_of(part * part_rows, part_rows),
                            part_rows), part_rows)

            @pl.when(part < 0)
            def _whole():
                score(slice(None), rows)
        return carry

    jax.lax.fori_loop(0, depth, one, 0)


def _key_scores_pallas(q, ck, layer, walk: ScoreWalk, per: int, columns: int,
                       scale, interpret=False):
    """:func:`key_scores` of ``q [T, G, R, D]`` over the rows' own
    sequences' compressed keys, read where they lie in ``ck [L,
    num_blocks * per, G * D]`` (``per`` a pool block) by the walk's block
    ids: ``[T, G, R, columns * per]`` float32, 0 where a row scores
    nothing (:func:`scored_columns`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, g, heads, d = q.shape
    n = columns * per
    tiles, rows, _ = walk.lanes.shape
    ucols, units = _score_units(columns, per)
    lanes = ucols * per
    out = pl.pallas_call(
        functools.partial(_score_kernel, part_rows=narrow_height(q.dtype),
                          per=per, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles, units),
            in_specs=[pl.BlockSpec((None, rows, lanes),
                                   lambda i, u, *_: (i, 0, u)),
                      pl.BlockSpec((None, g, heads, rows, d),
                                   lambda i, u, *_: (i, 0, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, g, heads, rows, lanes),
                                   lambda i, u, *_: (i, 0, 0, 0, u)),
            scratch_shapes=[pltpu.VMEM((2, lanes, g * d), ck.dtype),
                            pltpu.SemaphoreType.DMA((2, ucols))]),
        out_shape=jax.ShapeDtypeStruct((tiles, g, heads, rows, n),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="compressed_key_scores",
    )(walk.depth, walk.blocks, walk.narrow,
      jnp.asarray(layer, jnp.int32).reshape(1), walk.lanes,
      _tile_queries(q, rows), ck)
    return out.transpose(0, 3, 1, 2, 4).reshape(tiles * rows, g, heads, n)[:t]


def sparse_paged_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, ck: jax.Array, layer,
                           tables: jax.Array, q_pos: jax.Array,
                           spec: SparseSpec, scale: Optional[float] = None,
                           force_pallas: Optional[bool] = None,
                           walk: Optional[ScoreWalk] = None):
    """Select and attend. ``q [T, N, D]`` one query row a packed token;
    ``k_pool, v_pool [L, num_blocks, KV, block_size, D]`` and ``ck [L,
    num_blocks * block_size / stride, KV * D]`` the stacks, read at
    ``layer``; ``tables [T, max_blocks_per_seq]`` per-token block tables
    (position ``p`` in column ``p // block_size``); ``q_pos [T]``;
    ``walk`` the step's :func:`score_walk` (built here where the caller
    has none). Returns ``(out [T, N, D], counts [10] int32)``
    (:data:`COUNT_KINDS`). ``force_pallas`` as
    :func:`.paged_attention.paged_attention`: the kernels on the chip
    (the scores from the pool in place, then the selected blocks), the
    gathers of the rows' whole tables elsewhere."""
    from ..obs.device_scopes import device_scope

    t, n, d = q.shape
    _, _, kv, bs, _ = k_pool.shape
    if bs % spec.block:
        raise ValueError(f"pool blocks of {bs} positions are not whole "
                         f"selection blocks of {spec.block}")
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    qg = q.reshape(t, kv, n // kv, d)
    impl = paged_attention_impl(d, bs, force_pallas)
    if walk is None:
        with device_scope("attn.walk"):
            walk = score_walk(tables, q_pos, spec, bs, n // kv, d, q.dtype,
                              force_pallas)
    with device_scope("attn.select"):
        if impl == "xla":
            s = key_scores(
                qg, gather_compressed_keys(ck, layer, tables, spec, bs, kv),
                scale)
        else:
            s = _key_scores_pallas(qg, ck, layer, walk, bs // spec.stride,
                                   tables.shape[1], scale,
                                   interpret=impl == "pallas-interpret")
        sel, forced = blocks_of_scores(s, q_pos, spec)
    with device_scope("attn.walk"):
        parts = column_parts(sel, tables, bs // spec.block)
    if impl == "xla":
        out = _sparse_paged_xla(qg, k_pool, v_pool, layer, tables, q_pos,
                                sel, spec, scale)
        # no walk here: the tiles' fetches are counted for the counter
        # alone, as the kernel's tiles would make them
        with device_scope("attn.walk"):
            fetched = jnp.sum(first_namers(
                parts, tables, tile_height(t, n // kv, d, q.dtype)))
    else:
        out, fetched = _sparse_paged_pallas(
            qg, k_pool, v_pool, layer, tables, q_pos, parts, spec, scale,
            interpret=impl == "pallas-interpret")
    # of the live (row, group, column), those whose pool block a tile
    # fetches for them, alone or first, and those an earlier row's serves;
    # then the same of the scored (row, column) and their compressed keys
    with device_scope("attn.select"):
        counts = jnp.concatenate([
            selection_counts(sel, forced, q_pos, spec, bs,
                             spec.walk_width(bs, tables.shape[1])),
            jnp.stack([fetched, jnp.sum(parts > 0) - fetched]).astype(
                jnp.int32), walk.visits])
    return out.reshape(t, n, d).astype(q.dtype), counts
