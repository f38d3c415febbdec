"""Latent attention over a learned selection of positions (DeepSeek
sparse attention, ``deepseek_v32``) on the paged pool.

A position caches two rows a layer: the latent row latent attention
reads (:mod:`.mla_attention`) and one *index key* of ``index_head_dim``
values. A query row scores every cached position of its own sequence
with a small indexer (:func:`index_scores`)::

    I[t, s] = sum_h w[t, h] * relu(q_I[t, h] . k_I[s]) * scale,  s <= t

keeps the ``top`` positions of highest score (:func:`select_positions`:
exact, by counting; equal scores take the lower position; a row whose
context is ``top`` positions or fewer keeps them all) and attends those
rows of the latent pool and no other (:func:`attend_selected`), in the
absorbed form the pool stores. The work of the attention therefore
follows ``top`` and not the context; the scores' follows the context, at
``index_heads x index_head_dim`` multiply-adds a position where a head
of latent attention costs ``rank + rope + rank``.

Three steps, each behind one signature:

* :func:`index_scores`. On the chip the ``index_key_scores`` kernel:
  a tile of :func:`tile_rows` packed rows, every row's index heads
  stacked (``[rows x heads, D]``, the MXU's rows), against one pool
  block of index keys a grid step, read where it lies in the stack by
  its block id. The grid is the step's *pairs* (:func:`index_walk`: a
  (tile, table column, pool block) that a row of the tile names at or
  below its own position; rows of one slot, and slots that share a
  prefix block, are one pair, so a chunk's rows read their slot's keys
  once a layer), in the order (tile, column), as long as the step has
  pairs and no longer (a dynamic grid). A pair's product ``[rows x
  heads, D] x [D, block_size]`` goes from the stored operands into
  float32, through the ReLU, times the heads' weights, summed over a
  row's heads; a row keeps the scores of the pairs it names, at the
  positions at or below its own; everything else of the output stays
  ``-inf`` (the output is laid over an array of ``-inf``, and a (tile,
  column) no pair visits is never written). Elsewhere the gather
  reference, every row's whole table (:func:`_index_scores_xla`: CPU
  tests).
* :func:`select_positions`: exact and no sort. The key of a row's
  ``top``-th highest score by counting (:func:`kth_key`: 32 rounds of a
  row reduction over keys that order as the scores do), the members read
  off it (above it, and of those equal to it the lowest positions that
  fill ``top``), their positions by rank (:func:`_ranked_positions`: the
  mask as bytes and counts a chunk of 128 positions, a prefix over the
  chunks, an output place's chunk by comparison, its bit by
  ``population_count``): no ``sort``, no ``top_k``, no gather or scatter
  of single elements, which cost the chip 10-16 ns each. A row's
  positions come ascending.
* :func:`attend_selected`: a row's selected pool rows gathered by flat
  index, ``[T, top, row]``, and one batched product a row with its heads
  as the MXU's rows (``[N, row] x [row, top]``, float32 softmax
  statistics, then ``p x v`` against the rows' first ``rank`` lanes with
  ``p``'s two bf16 parts, as the latent kernel has it). One path for
  rows below and above ``top``: the positions a short row lacks are
  masked.

On a TPU there is no silent fall to the gather reference: shapes the
score kernel cannot tile raise (:func:`index_scores_impl`), and the
walk's scalar-prefetch arrays are held to :data:`SMEM_BYTES`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.kv_cache import PAD_POSITION
from .paged_attention import paged_attention_impl
from .pallas_utils import compiler_params as _compiler_params

LANES = 128
#: stacked rows (packed rows x index heads) of a tile of the score kernel:
#: its float32 product against a block of 256 keys is 2 MiB
TILE_STACKED_ROWS = 2048
INT32_MIN = -(1 << 31)
#: :func:`ordered_keys` of ``-inf``
UNSCORED = INT32_MIN + 0x7fffff
#: what the walk's scalar-prefetch arrays may take of the chip's SMEM
SMEM_BYTES = 768 * 1024
#: the kinds :func:`selection_counts` counts, in its order
COUNT_KINDS = ("selected", "passed_over", "selecting", "whole", "named",
               "unnamed", "shared_with_previous_row", "new")


def positive(s):
    """The indexer's ReLU, for the kernel and the reference alike."""
    return jnp.maximum(s, 0.0)


class IndexWalk(NamedTuple):
    """The score kernel's walk of one packed step (:func:`index_walk`):
    ``pairs [1]`` how many, and of pair ``p`` (in the order tile, column)
    ``tile[p]``, ``col[p]`` (the table column: the pair's positions are
    ``col * block_size`` on), ``block[p]`` (the pool block), ``named[p]``
    (bit ``r``: row ``r`` of the tile names the block in that column) and
    ``opens[p]`` (1: the first pair of its (tile, column))."""

    pairs: jax.Array
    tile: jax.Array
    col: jax.Array
    block: jax.Array
    named: jax.Array
    opens: jax.Array


def tile_rows(step_rows: int, index_heads: int) -> int:
    """Packed rows of a tile of the score kernel: as many as stack to
    :data:`TILE_STACKED_ROWS` with their index heads, whole sublanes, at
    most 32 (a pair's namers are one word) and no more than the step
    holds."""
    rows = max(8, min(32, TILE_STACKED_ROWS // max(index_heads, 1)))
    return min(rows // 8 * 8, -(-step_rows // 8) * 8)


def index_scores_impl(index_head_dim: int, block_size: int,
                      force_pallas: Optional[bool] = None) -> str:
    """``"pallas"``, ``"pallas-interpret"`` or ``"xla"``, as
    :func:`.paged_attention.paged_attention_impl` answers for keys of
    ``index_head_dim`` lanes; on a TPU the answer is the kernel or an
    error (the reference gathers every row's whole table)."""
    impl = paged_attention_impl(index_head_dim, block_size, force_pallas,
                                kernel_only=True)
    if impl == "pallas" and index_head_dim % LANES:
        raise ValueError(f"index keys of {index_head_dim} values are no "
                         "whole lanes: the index_key_scores kernel reads a "
                         "pool block of them as it lies")
    return impl


def max_pairs(step_rows: int, index_heads: int, slots: int,
              max_blocks_per_seq: int) -> int:
    """The most pairs a step can hold: a tile's rows are of at most
    ``slots`` sequences, each of which names a column once."""
    rows = tile_rows(step_rows, index_heads)
    return (-(-step_rows // rows) * min(rows, slots) * max_blocks_per_seq)


def index_walk(tables: jax.Array, q_pos: jax.Array, block_size: int,
               index_heads: int, index_head_dim: int, slots: int,
               force_pallas: Optional[bool] = None) -> Optional[IndexWalk]:
    """The score kernel's walk of one packed step, from ``tables [T,
    max_blocks_per_seq]`` and ``q_pos [T]`` alone: built once a step and
    handed to every layer. ``None`` where the reference serves. A pad
    row, an unmapped column and a column wholly past the row's position
    name nothing."""
    if index_scores_impl(index_head_dim, block_size, force_pallas) == "xla":
        return None
    t, maxb = tables.shape
    rows = tile_rows(t, index_heads)
    tiles = -(-t // rows)
    most = max_pairs(t, index_heads, slots, maxb)
    if 5 * 4 * most > SMEM_BYTES:
        raise ValueError(
            f"index_key_scores: a step of {t} rows over {maxb} table "
            f"columns and {slots} slots lists up to {most} pairs, "
            f"{5 * 4 * most} B of scalar-prefetch arrays beside "
            f"{SMEM_BYTES} B of SMEM: take larger pool blocks")
    pad = tiles * rows - t
    tables = jnp.pad(tables.astype(jnp.int32), ((0, pad), (0, 0)),
                     constant_values=-1).reshape(tiles, rows, maxb)
    q_pos = jnp.pad(q_pos, (0, pad), constant_values=PAD_POSITION
                    ).reshape(tiles, rows, 1)
    first_pos = jnp.arange(maxb, dtype=jnp.int32) * block_size
    live = (tables >= 0) & (first_pos <= q_pos) & (q_pos < PAD_POSITION)
    # [tiles, r, r', maxb]: rows r and r' of a tile name one block
    same = (live[:, :, None] & live[:, None]
            & (tables[:, :, None] == tables[:, None]))
    earlier = jnp.tril(jnp.ones((rows, rows), bool), -1)
    first = live & ~jnp.any(same & earlier[None, :, :, None], axis=2)
    bits = jnp.left_shift(jnp.uint32(1),
                          jnp.arange(rows, dtype=jnp.uint32))
    named = jnp.sum(jnp.where(same, bits[None, None, :, None],
                              jnp.uint32(0)), axis=2, dtype=jnp.uint32)
    # pairs in the order (tile, column, first namer)
    order = first.transpose(0, 2, 1).reshape(-1)
    count = jnp.sum(order, dtype=jnp.int32)
    at = jnp.nonzero(order, size=most, fill_value=0)[0].astype(jnp.int32)
    real = jnp.arange(most, dtype=jnp.int32) < count
    tile, rest = at // (maxb * rows), at % (maxb * rows)
    col, row = rest // rows, rest % rows
    block = jnp.where(real, tables[tile, row, col], 0)
    names = jnp.where(real, named[tile, row, col], jnp.uint32(0))
    group = tile * maxb + col
    opens = real & jnp.concatenate(
        [jnp.ones((1,), bool), group[1:] != group[:-1]])
    return IndexWalk(
        pairs=count.reshape(1), tile=jnp.where(real, tile, 0),
        col=jnp.where(real, col, 0), block=block,
        named=jax.lax.bitcast_convert_type(names, jnp.int32),
        opens=opens.astype(jnp.int32))


def _index_scores_xla(q, w, keys, layer, tables, q_pos, scale):
    nb = keys.shape[1]
    t = q.shape[0]
    safe = jnp.clip(tables, 0, nb - 1)
    own = keys[layer, safe].reshape(t, -1, keys.shape[-1])   # [T, P, D]
    s = jnp.einsum("thd,tkd->thk", q, own,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(positive(s) * w[:, :, None], axis=1) * scale
    bs = keys.shape[2]
    position = jnp.arange(own.shape[1], dtype=jnp.int32)
    ok = ((jnp.repeat(tables >= 0, bs, axis=1))
          & (position[None, :] <= q_pos[:, None])
          & (q_pos < PAD_POSITION)[:, None])
    return jnp.where(ok, s, -jnp.inf)


def _index_kernel(pairs_ref, tile_ref, col_ref, block_ref, named_ref,
                  opens_ref, layer_ref, q_ref, w_ref, qpos_ref, keys_ref,
                  _, o_ref, *, heads: int, scale: float):
    """One pair: the tile's stacked index queries ``[rows x heads, D]``
    against the pool block's keys ``[block_size, D]``."""
    from jax.experimental import pallas as pl

    p = pl.program_id(0)
    rows = qpos_ref.shape[0]
    bs = keys_ref.shape[0]

    @pl.when(opens_ref[p] > 0)
    def _open():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    @pl.when(p < pairs_ref[0])
    def _score():
        s = jax.lax.dot_general(
            q_ref[...], keys_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = positive(s) * w_ref[...]
        s = jnp.sum(s.reshape(rows, heads, bs), axis=1) * scale
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0)
        mine = (jax.lax.shift_right_logical(named_ref[p], row) & 1) > 0
        ok = mine & (col_ref[p] * bs + lane <= qpos_ref[...])
        o_ref[...] = jnp.where(ok, s, o_ref[...])


def _index_scores_pallas(q, w, keys, layer, q_pos, columns: int,
                         walk: IndexWalk, scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, d = q.shape
    bs = keys.shape[2]
    rows = tile_rows(t, heads)
    tiles = -(-t // rows)
    pad = tiles * rows - t
    q_tiles = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        tiles, rows * heads, d)
    w_tiles = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0))).reshape(
        tiles, rows * heads, 1)
    pos_tiles = jnp.pad(q_pos, (0, pad), constant_values=PAD_POSITION
                        ).reshape(tiles, rows, 1)
    nothing = jnp.full((tiles * rows, columns * bs), -jnp.inf, jnp.float32)

    def of_tile(height, width):
        return pl.BlockSpec((None, height, width),
                            lambda p, n, tile, *_: (tile[p], 0, 0))

    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=heads, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(walk.pairs[0],),
            in_specs=[
                of_tile(rows * heads, d), of_tile(rows * heads, 1),
                of_tile(rows, 1),
                pl.BlockSpec((None, None, bs, d),
                             lambda p, n, tile, col, block, named, opens,
                             layer: (layer[0], block[p], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (rows, bs), lambda p, n, tile, col, *_: (tile[p], col[p]))),
        out_shape=jax.ShapeDtypeStruct(nothing.shape, jnp.float32),
        input_output_aliases={11: 0},
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="index_key_scores",
    )(walk.pairs, walk.tile, walk.col, walk.block, walk.named, walk.opens,
      jnp.asarray(layer, jnp.int32).reshape(1), q_tiles, w_tiles, pos_tiles,
      keys, nothing)
    return out[:t]


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array, layer,
                 tables: jax.Array, q_pos: jax.Array, scale: float,
                 slots: int, force_pallas: Optional[bool] = None,
                 walk: Optional[IndexWalk] = None) -> jax.Array:
    """``q [T, Hi, D]`` the rows' index queries (rotated), ``w [T, Hi]``
    their heads' weights (float32), ``keys [L, num_blocks, block_size,
    D]`` the index keys' stack, read at ``layer`` through ``tables [T,
    max_blocks_per_seq]``; ``q_pos [T]``; ``slots`` the table's rows (what
    bounds the walk). Returns ``[T, max_blocks_per_seq * block_size]``
    float32: a row's score of each position of its own sequence at or
    below its own, ``-inf`` elsewhere and for a pad row."""
    bs = keys.shape[2]
    impl = index_scores_impl(q.shape[-1], bs, force_pallas)
    if impl == "xla":
        return _index_scores_xla(q, w, keys, layer, tables, q_pos, scale)
    if walk is None:
        walk = index_walk(tables, q_pos, bs, q.shape[1], q.shape[2], slots,
                          force_pallas)
    return _index_scores_pallas(q, w, keys, layer, q_pos, tables.shape[1],
                                walk, scale,
                                interpret=impl == "pallas-interpret")


class Selection(NamedTuple):
    """What :func:`select_positions` keeps of a step's rows: ``positions
    [T, k]`` int32, ascending along a row, ``chosen [T, k]`` (which of them
    count: a row's first ``min(k, its scored positions)``) and ``member
    [T, P]`` bool, the same set as a mask over the scores' width."""

    positions: jax.Array
    chosen: jax.Array
    member: jax.Array


def ordered_keys(scores):
    """int32 keys that order as the float32 scores compare: ``-0.0`` is
    ``+0.0`` (the comparison ties them, their bits would not), a negative
    score's magnitude bits are turned over, and ``-inf`` (a position the
    row does not name) is :data:`UNSCORED`, below every number's key."""
    scores = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)


def _bisect(rounds: int, lowest, fits):
    """``[T, 1]`` int32: a row's highest value that ``fits``, bit by bit
    from bit ``rounds - 1`` down. ``fits(candidate [T, 1]) -> [T, 1]``
    bool holds up to a row's answer and no further; ``lowest`` is the
    order's lowest value with those bits clear in the order's sense
    (``INT32_MIN`` for 32 rounds over signed values: the ``xor`` turns
    the sign bit to 0, which is the higher half)."""
    def narrow(i, value):
        candidate = value ^ jnp.left_shift(jnp.int32(1), rounds - 1 - i)
        return jnp.where(fits(candidate), candidate, value)

    return jax.lax.fori_loop(0, rounds, narrow, lowest)


def kth_key(keys: jax.Array, k: int) -> jax.Array:
    """``[T, 1]`` int32: a row's ``k``-th highest of ``keys [T, P]`` int32,
    equal keys counted each, by counting and no sort: 32 rounds of "do
    ``k`` keys reach this candidate", from the top bit down, each a row
    reduction over the keys (which the chip's compiler keeps in VMEM
    through the rounds: 34 MB at the cell's ``[128, 66560]``, 12 us a
    round where HBM's rate would make it 42)."""
    return _bisect(
        32, jnp.full((keys.shape[0], 1), INT32_MIN, jnp.int32),
        lambda key: jnp.sum(keys >= key, axis=-1, keepdims=True,
                            dtype=jnp.int32) >= k)


def _tie_cut(tied, need):
    """``[T, 1]`` int32: the position of a row's ``need``-th tied position
    (``tied [T, P]`` bool, ``need [T, 1]`` at least 1 and at most the
    row's ties), by the same bisection over the position."""
    width = tied.shape[-1]
    at = jnp.arange(width, dtype=jnp.int32)[None, :]
    return _bisect(
        width.bit_length(), jnp.zeros((tied.shape[0], 1), jnp.int32),
        lambda cut: jnp.sum(tied & (at < cut), axis=-1, keepdims=True,
                            dtype=jnp.int32) < need)


def _set_bit(word, nth):
    """The place of a uint32 word's ``nth`` set bit (from 0, from the low
    end) in 5 halvings: the bit lies in the low half if that half holds
    more than ``nth``."""
    place = jnp.zeros_like(nth)
    for half in (16, 8, 4, 2, 1):
        low = word & jnp.uint32((1 << half) - 1)
        held = jax.lax.population_count(low).astype(jnp.int32)
        above = nth >= held
        nth = jnp.where(above, nth - held, nth)
        word = jnp.where(above, word >> half, low)
        place = place + jnp.where(above, half, 0)
    return place


def _ranked_positions(member, k: int):
    """``(positions [T, k], chosen [T, k])``: a row's ``j``-th member
    position at place ``j``, by rank and no scatter. The mask in chunks
    of 128 positions, each 16 bytes of bits and a count (one product with
    a constant: exact, the operands are bits and powers of two); place
    ``j`` lies in the chunk whose span of the counts' prefix holds it
    (``[T, chunks, k]`` comparisons, as one-hot rows of a second product
    that fetches the chunk's bytes, its number and the prefix before it);
    then the word by its 4 counts and the bit by halving, elementwise."""
    t, width = member.shape
    chunks = -(-width // LANES)
    lane = np.arange(LANES)
    pack = np.zeros((LANES, 17), np.float32)
    pack[lane, lane // 8], pack[:, 16] = 2.0 ** (lane % 8), 1.0
    packed = jnp.einsum(
        "tcl,ld->tcd",
        jnp.pad(member, ((0, 0), (0, chunks * LANES - width))).reshape(
            t, chunks, LANES).astype(jnp.bfloat16),
        jnp.asarray(pack, jnp.bfloat16), preferred_element_type=jnp.float32)
    held = packed[..., 16].astype(jnp.int32)                 # [T, chunks]
    upto = jnp.cumsum(held, axis=-1)
    before = upto - held
    place = jnp.arange(k, dtype=jnp.int32)
    spans = ((before[:, :, None] <= place)
             & (place < upto[:, :, None]))                   # [T, chunks, k]
    # every value of the fetch is a whole number under 256: exact in bf16
    chunk = jnp.broadcast_to(jnp.arange(chunks, dtype=jnp.int32), (t, chunks))
    fetch = jnp.concatenate(
        [packed[..., :16]] + [x[..., None].astype(jnp.float32) for x in (
            before & 255, chunk >> 8, chunk & 255)], axis=-1)
    got = jnp.einsum("tcd,tck->tdk", fetch.astype(jnp.bfloat16),
                     spans.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    byte = got[:, :16].astype(jnp.uint32)
    words = [byte[:, 4 * i] | (byte[:, 4 * i + 1] << 8)
             | (byte[:, 4 * i + 2] << 16) | (byte[:, 4 * i + 3] << 24)
             for i in range(4)]
    # a chunk holds at most 128: the rank within it from the low byte
    nth = (place[None, :] - got[:, 16]) & 255
    counts = [jax.lax.population_count(w).astype(jnp.int32) for w in words]
    first, second = counts[0], counts[0] + counts[1]
    third = second + counts[2]

    def of_word(*values):
        """``values[i]`` where the rank falls in word ``i``."""
        return jnp.where(nth < first, values[0], jnp.where(
            nth < second, values[1],
            jnp.where(nth < third, values[2], values[3])))

    positions = (((got[:, 17] << 8) + got[:, 18]) * LANES
                 + of_word(0, 32, 64, 96)
                 + _set_bit(of_word(*words),
                            nth - of_word(0, first, second, third)))
    chosen = place[None, :] < upto[:, -1:]
    return jnp.where(chosen, positions, 0), chosen


def select_positions(scores: jax.Array, top: int) -> Selection:
    """A row's ``k = min(top, the scores' width)`` positions of highest
    score (:class:`Selection`), exact and without a sort: the key of the
    ``k``-th highest score by counting (:func:`kth_key`), the members
    read off it (every position above it, and of those equal to it the
    lowest positions that fill the ``k``: equal scores take the lower
    position), their positions by rank (:func:`_ranked_positions`). A row
    with ``k`` scored positions or fewer keeps them all (its ``k``-th key
    is :data:`UNSCORED`, above which lie its scored positions and at
    which nothing counts), so a pad row keeps none. One path for every
    width and ``top``."""
    t, width = scores.shape
    k = min(top, width)
    keys = ordered_keys(scores)
    kth = kth_key(keys, k)
    above = keys > kth
    tied = (keys == kth) & (kth > UNSCORED)
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    ties = jnp.sum(tied, axis=-1, keepdims=True, dtype=jnp.int32)
    # the cut among the ties is a second search; no row of real scores
    # holds more ties than it needs, and the step then skips it
    cut = jax.lax.cond(
        jnp.any(ties > need), lambda: _tie_cut(tied, need),
        lambda: jnp.full((t, 1), width, jnp.int32))
    at = jnp.arange(width, dtype=jnp.int32)[None, :]
    member = above | (tied & (at <= cut))
    return Selection(*_ranked_positions(member, k), member)


def selection_counts(selection: Selection, tables, q_pos,
                     block_size: int, top: int) -> jax.Array:
    """``[8]`` int32 (:data:`COUNT_KINDS`) of one layer of one step: the
    real rows' causal positions by whether the row attended them
    (``selected``, ``passed_over``); the real rows by whether their
    context is longer than ``top`` (``selecting``) or not (``whole``); the
    pool blocks of a real row's context that hold a selected position
    (``named``) and that hold none (``unnamed``: what a kernel that reads
    whole blocks would not have to); and a row's selected positions that
    the row before it also selected, where that row is the position
    before it of the same sequence (``shared_with_previous_row``), or
    not (``new``)."""
    real = q_pos < PAD_POSITION
    member = selection.member
    selected = jnp.sum(member, axis=-1, dtype=jnp.int32)
    causal = jnp.where(real, q_pos + 1, 0)
    selecting = real & (q_pos + 1 > top)
    named = jnp.sum(jnp.any(member.reshape(
        member.shape[0], -1, block_size), axis=-1), axis=-1,
        dtype=jnp.int32)
    blocks = jnp.where(real, q_pos // block_size + 1, 0)
    follows = (real[1:] & real[:-1] & (q_pos[1:] == q_pos[:-1] + 1)
               & (tables[1:, 0] == tables[:-1, 0]))
    shared = jnp.sum(jnp.where(follows[:, None], member[1:] & member[:-1],
                               False), dtype=jnp.int32)
    total = jnp.sum(selected)
    return jnp.stack([
        total, jnp.sum(causal) - total,
        jnp.sum(selecting, dtype=jnp.int32),
        jnp.sum(real & ~selecting, dtype=jnp.int32),
        jnp.sum(named), jnp.sum(blocks) - jnp.sum(named),
        shared, total - shared]).astype(jnp.int32)


def _p_times_v(p, v):
    """``p [T, N, K]`` float32 times ``v [T, K, R]``: from a bf16 pool
    ``p``'s two bf16 parts against the stored values (float32 sums), as
    the latent kernel has it; from a float32 pool the product itself."""
    if v.dtype != jnp.bfloat16:
        return jnp.einsum("tnk,tkr->tnr", p, v.astype(jnp.float32))
    high = p.astype(jnp.bfloat16)
    low = (p - high.astype(jnp.float32)).astype(jnp.bfloat16)
    return (jnp.einsum("tnk,tkr->tnr", high, v,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("tnk,tkr->tnr", low, v,
                         preferred_element_type=jnp.float32))


def attend_selected(q: jax.Array, pool: jax.Array, layer,
                    tables: jax.Array, positions: jax.Array,
                    chosen: jax.Array, rank: int, scale: float) -> jax.Array:
    """``q [T, N, row]`` absorbed queries
    (:func:`.mla_attention.absorb_queries`) over the rows of layer
    ``layer`` of ``pool [L, num_blocks, block_size, row]`` at the rows'
    own ``positions [T, k]`` (``chosen [T, k]``: which of them count),
    found through ``tables [T, max_blocks_per_seq]``. Returns the
    probabilities times the latents ``[T, N, rank]``; zero for a row that
    attends nothing."""
    n_layers, nb, bs, row = pool.shape
    # a position's pool block by a comparison with the table's columns: a
    # gather of single table entries costs the chip 10 ns each
    column = jnp.arange(tables.shape[1], dtype=jnp.int32)
    block = jnp.sum(jnp.where((positions // bs)[:, :, None] == column,
                              tables[:, None, :], 0), axis=-1)
    flat = jnp.where(chosen & (block >= 0), block * bs + positions % bs, 0)
    rows = pool.reshape(n_layers, nb * bs, row)[layer, flat]  # [T, k, row]
    operand = q.dtype if pool.dtype == jnp.bfloat16 else jnp.float32
    s = jnp.einsum("tnw,tkw->tnk", q.astype(operand), rows.astype(operand),
                   preferred_element_type=jnp.float32) * scale
    ok = chosen[:, None, :]
    s = jnp.where(ok, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    out = _p_times_v(p, rows[..., :rank]) / jnp.maximum(total, 1e-30)
    return out.astype(q.dtype)
