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
* ``_paged_attention_pallas`` — a Mosaic TPU kernel over *tiles* of
  ``R`` consecutive packed rows and the *pairs* (table column, pool
  block) that some row of the tile attends. ``R`` (:func:`tile_rows`)
  follows the shapes: with the ``n_rep`` query heads of a K/V head
  stacked a tile is about the MXU's 128 rows, 32 rows under GQA-4 and 128
  under MHA. The grid is the tiles. Inside, a loop over the tile's
  *units* (:func:`pair_runs`): a unit is up to :func:`run_blocks` pairs of
  one narrow group of the tile (a decode row's own blocks, column after
  column: 8 blocks of 320 KiB, 4 of 512 KiB, 2 over a ring of two
  columns), or one pair that rows of the tile share beyond a group (a
  prefill chunk's block). A unit's blocks are copied once from the stacks
  in HBM side by side into one half of a ring in VMEM while the unit
  before it is computed from the other half, and are one step of the
  online softmax, a K/V head at a time on the MXU: scores ``[rows, D] x
  [D, blocks x block_size]`` from the stored operands into float32, one
  read, correction and write of the running max, sum and accumulator
  (float32 scratch), ``p x v`` with ``p`` float32 (:func:`_p_times_v`).
  So a pair's fixed part (copies started and awaited, the statistics'
  round trip, the MXU's fill) is paid once a run and not once a block,
  and the kernel's time follows the blocks' bytes. Where a pool's blocks
  are too large for two ring halves of more than one (32 heads of 128: 2
  MiB) the unit is the pair, the walk is the tile walk as it is and the
  kernel takes a pair a turn (:func:`_paged_kernel`); the length is a
  function of the pools' shapes alone (:func:`unit_blocks`). A prefill
  chunk's rows, which name the same blocks of one slot, fetch them once
  a tile and not once a row, and the kernel's time follows the pairs and
  not the table's width. The rows
  of the tile that do not name a pair's block are masked (``-inf``, as a
  dead slot position is), block by block inside a run; where one packed
  row names it (a decode row's
  blocks: its neighbours are other slots') the products run over a
  narrow group of the tile alone: the whole sublanes that hold the row's
  ``n_rep`` stacked heads wherever they begin (:func:`narrow_rows`,
  :func:`narrow_start`; 8 stacked rows under GQA-4, GQA-8 and MHA, 16
  where the heads cross sublanes, 6 or 9 of them). A run is keyed by its
  group's first row: where the group is one packed row's heads (8 or 16
  of them) the run is that row's blocks and every row of the group names
  each; under GQA-4 two packed rows share a group and their blocks
  interleave in its runs.

  The group is as tall as the packed rows a slot holds side by side in
  the step (``slot_rows``, the family's own number: 1, or the block
  length of a family that decodes blocks, whose slot packs its block's
  rows a step, all attending through the block's last position and so
  all naming each of the slot's pool blocks). Where those rows' heads
  fill whole sublanes the group is all of them, ``slot_rows * n_rep``
  stacked rows beginning on a multiple of that (32 for 4 rows of 8
  heads, four groups a tile of 128): a decoding slot's blocks are narrow
  pairs of its own group and ride its runs, each block under the rows
  that name it, and only what rows beyond one group name (a prefill
  chunk's blocks) or a group that the tile's end cuts runs over the whole
  tile. With one row a slot every function here returns what it returned
  before the argument was there.

  The walk (:func:`tile_walk`) lists, a tile, the distinct pairs that
  are live for at least one of its rows, each once. Live is
  :func:`column_live` (a window-summary cache:
  :func:`window_column_kinds`; a sliding-window layer's ring:
  :func:`sliding_column_live`): an unmapped column, one wholly beyond
  the row's position (or wholly behind its window) and every column of
  a pad row belong to no pair, and a pad row's output is zero. Membership is by the row's own table
  entry, not by its slot: two slots that share a prefix block share its
  fetch. The walk follows the tables and positions alone, so the cached
  forward builds it once a step beside the write indices
  (:func:`step_walk`, ``PagedCacheView.walk``), not once a layer.

  Two things the kernel leans on. Row order: the engine packs decode
  rows first and then one contiguous run a prefill chunk
  (``ServingEngine._build_schedule``), so a slot's rows are neighbours
  and a tile of a chunk has one slot's pairs; any order is correct, a
  scattered one shares less. The pool's layout, ``[.., block_size, KV,
  D]`` with a slot's heads on sublanes
  (:func:`..inference.paging.write_pool_rows`): a head's ``[block_size,
  D]`` operand is a sublane-strided read of the block in VMEM
  (:func:`_head_rows`).

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.kv_cache import PAD_POSITION, dequantize_kv
from ..modules.attention import repeat_kv
from ..utils.device import on_tpu
from ..utils.logger import get_logger
from .pallas_utils import compiler_params as _compiler_params

logger = get_logger(__name__)


def _paged_attention_xla(q, k_pool, v_pool, pool_pos, tables, q_pos, layer,
                         k_scale, v_scale, scale, combine_axis=None,
                         window=None, sliding=None, sink=None):
    t, n, d = q.shape
    nb, bs = k_pool.shape[1:3]
    kv, dv = v_pool.shape[3:]
    n_rep = n // kv
    safe = jnp.clip(tables, 0, nb - 1)
    kg = k_pool[layer, safe]                   # [T, maxb, bs, KV, D]
    vg = v_pool[layer, safe]
    if kg.ndim == 4:
        kg = keys_of_lanes(kg, kv, d)
    pg = pool_pos[safe]                        # [T, maxb, bs]
    # entries gathered through an unmapped (-1) table slot are another
    # sequence's data — force their stored position to the pad sentinel
    pg = jnp.where(tables[:, :, None] >= 0, pg, PAD_POSITION)
    if k_scale is not None:
        kg = dequantize_kv(kg, k_scale[layer, safe], q.dtype)
        vg = dequantize_kv(vg, v_scale[layer, safe], q.dtype)
    length = tables.shape[1] * bs
    k_full = repeat_kv(kg.reshape(t, length, kv, d).astype(q.dtype), n_rep)
    v_full = repeat_kv(vg.reshape(t, length, kv, dv).astype(q.dtype), n_rep)
    pg = pg.reshape(t, length)
    scores = jnp.einsum("bqnd,bknd->bnqk", q[:, None].astype(jnp.float32),
                        k_full.astype(jnp.float32)) * scale
    if sliding is not None:
        # a causal window: the last ``sliding`` positions and no others (a
        # ring's stale rows are earlier ones, or a later tenant's)
        mask = ((q_pos[:, None] >= pg) & (q_pos[:, None] - pg < sliding)
                )[:, None, None, :]
    elif window is None:
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
        if sink is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:
            # a column of the softmax that holds no value: a logit a head
            probs = jax.nn.softmax(jnp.concatenate(
                [scores, jnp.broadcast_to(
                    sink.astype(jnp.float32)[None, :, None, None],
                    scores.shape[:3] + (1,))], axis=-1), axis=-1)[..., :-1]
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
# Keys wider than the values (a K head of 192 beside a V head of 128). Such
# a K pool is ``[L, num_blocks, block_size, KV * D]``, a position's keys one
# row of whole 128-lane chunks with no lane idle in HBM (a minor pair ``(KV,
# 192)`` would be stored as 256 lanes a head): first each head's leading
# whole chunks (head ``h``'s ``j``-th at chunk ``h * whole + j``), then the
# heads' remaining ``rest = D % 128`` values, ``128 // rest`` heads side by
# side a chunk. The kernel reads a chunk as an aligned lane slice of the
# block and meets it with the same chunk of the query, the query's ``rest``
# values zero-padded into its head's share of their chunk (as a head of 64
# is met where two lie on a pool row).
# ---------------------------------------------------------------------------

LANES = 128


def _key_split(kv: int, d: int):
    """``(whole, rest, pack)`` of a K head of ``d`` values: its whole
    chunks, what is left, and the heads whose rest shares a chunk."""
    whole, rest = divmod(d, LANES)
    pack = LANES // rest if rest else 1
    if rest and (LANES % rest or kv % pack):
        raise ValueError(
            f"{kv} K heads of {d} values fill no whole {LANES}-lane chunks: "
            f"what a head has past its whole chunks divides {LANES}, and "
            "the heads divide by how many share a chunk")
    return whole, rest, pack


def key_chunks(kv: int, d: int):
    """Where the pool row holds head ``h``'s keys: ``[h]`` the chunks of
    its whole parts and then, where it has a rest, the chunk its rest
    shares."""
    whole, rest, pack = _key_split(kv, d)
    return tuple(
        tuple(range(h * whole, (h + 1) * whole))
        + ((kv * whole + h // pack,) if rest else ())
        for h in range(kv))


def keys_to_lanes(k: jax.Array) -> jax.Array:
    """``k [..., KV, D]`` as the rows of a wide-key pool ``[..., KV * D]``."""
    *lead, kv, d = k.shape
    whole, rest, _ = _key_split(kv, d)
    return jnp.concatenate(
        [k[..., :whole * LANES].reshape(*lead, kv * whole * LANES),
         k[..., whole * LANES:].reshape(*lead, kv * rest)], axis=-1)


def keys_of_lanes(rows: jax.Array, kv: int, d: int) -> jax.Array:
    """:func:`keys_to_lanes` undone: ``rows [..., KV * D]`` as ``[..., KV,
    D]``."""
    whole, rest, _ = _key_split(kv, d)
    lead = rows.shape[:-1]
    cut = kv * whole * LANES
    return jnp.concatenate(
        [rows[..., :cut].reshape(*lead, kv, whole * LANES),
         rows[..., cut:].reshape(*lead, kv, rest)], axis=-1)


def queries_to_lanes(q: jax.Array, kv: int) -> jax.Array:
    """``q [T, N, D]`` as the kernel meets a wide-key pool's chunks: a
    head's whole chunks as they are and, where it has a rest, one more
    chunk with the rest in its K head's share of the lanes and zeros in
    the other heads' shares."""
    t, n, d = q.shape
    whole, rest, pack = _key_split(kv, d)
    if not rest:
        return q
    share = (jnp.arange(n) // (n // kv)) % pack
    tail = (q[:, :, None, whole * LANES:] * jax.nn.one_hot(
        share, pack, dtype=q.dtype)[None, :, :, None]).reshape(t, n, LANES)
    return jnp.concatenate([q[..., :whole * LANES], tail], axis=-1)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def column_live(entry, column, q_pos, block_size: int):
    """Whether table column ``column`` (holding block id ``entry``) can
    contribute to a row at ``q_pos``: the row is no padding, the column is
    mapped, and its first position ``column * block_size`` is not beyond
    the row's own (a position ``p`` lives in column ``p // block_size``,
    :func:`..inference.paging.flat_write_indices`, and a row attends only
    to positions ``<= q_pos``). Every other column adds exactly nothing to
    the online softmax. Broadcasts over jnp arrays (the kernel's walk)
    and NumPy ones (the engine's ``nxd_paged_columns_total``, the tests).
    """
    return ((entry >= 0) & (column * block_size <= q_pos)
            & (q_pos < PAD_POSITION))


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


def sliding_column_live(entry, column, q_pos, block_size: int, sliding: int,
                        ring: int):
    """Whether ring column ``column`` (holding block id ``entry``) of a
    sliding-window layer's table
    (:class:`..inference.paging.WindowPoolCache`) holds a position a row
    at ``q_pos`` attends: the blocks of positions ``q_pos - sliding + 1 ..
    q_pos`` (block ``b`` in column ``b % ring``), of a row that is no
    padding. Broadcasts over jnp arrays (the kernel's walk) and NumPy ones
    (the engine's ``nxd_window_columns_total``, the tests)."""
    first = (q_pos - sliding + 1).clip(0) // block_size
    held = q_pos // block_size - first + 1           # 1 .. ring
    return ((entry >= 0) & (q_pos < PAD_POSITION)
            & ((column - first) % ring < held))


def tile_rows(n_rep: int, tokens: int) -> int:
    """Rows of a tile of the kernel: with the ``n_rep`` query heads of a
    K/V head stacked (row ``r``'s at ``r * n_rep`` of the tile, unpadded),
    about the MXU's 128 rows (32 under GQA-4, 128 under MHA, 16 under
    GQA-8, 20 under GQA-6, 8 under GQA-9) and whole sublanes of them, and
    no more than the packed rows there are."""
    rows = max(8, 128 // n_rep)
    while rows * n_rep % 8:
        rows -= 1
    return min(rows, -(-tokens // 8) * 8)


def narrow_rows(n_rep: int, slot_rows: int = 1) -> int:
    """Rows (query heads stacked) of the narrow product of a pair that a
    single packed row names: whole sublanes that hold the row's ``n_rep``
    heads wherever they begin. Row ``r``'s heads begin at stacked row ``r
    * n_rep``, a multiple of ``gcd(n_rep, 8)`` past a whole sublane, so
    at most ``8 - gcd(n_rep, 8)`` past it: 8 rows for 1, 4 and 8 heads,
    16 for 6, 9 and 10, and their own number for heads of whole
    sublanes, which begin on one. Where a slot packs ``slot_rows`` rows
    side by side (a family that decodes blocks: its block's rows, which
    name the same pool blocks) and their heads are whole sublanes, the
    group is all of them: 32 rows for a block of 4 rows of 8 heads."""
    return (slot_group(n_rep, slot_rows)
            or -(-(8 - math.gcd(n_rep, 8) + n_rep) // 8) * 8)


def slot_group(n_rep: int, slot_rows: int) -> int:
    """The stacked rows of a narrow group that is a slot's ``slot_rows``
    packed rows' heads (:func:`narrow_rows`), or 0 where the group is one
    row's: more rows than one, whose heads fill whole sublanes. Such
    groups lie side by side from the tile's first row, each on a multiple
    of its height."""
    heads = slot_rows * n_rep
    return heads if slot_rows > 1 and heads % 8 == 0 else 0


def narrow_start(first, last, n_rep: int, wide: int, xp=jnp,
                 slot_rows: int = 1):
    """Where the narrow product of a pair begins in a tile of ``wide``
    stacked rows, or -1 where it runs over the whole tile: ``first`` the
    stacked row of the first head that names the pair, ``last`` one past
    the last such head. The group of :func:`narrow_rows` rows begins at
    the whole sublane ``first`` lies in, or where the tile's last group
    does if that is earlier (a group wider than the heads' own sublanes
    would pass the tile's end from its last rows: at ``n_rep`` 6 row
    19's heads lie at 114..119 of 120, and its group begins at 104). A
    pair that one packed row names always fits; one that neighbouring
    rows name, where they all do. A slot's group (:func:`slot_group`)
    begins on the multiple of its height that ``first`` lies in, not on
    the sublane: a pair first named inside one group and last named in
    the next belongs to neither, and neither does one whose group the
    tile's end cuts. Broadcasts over jnp arrays (the walk) and NumPy
    ones (:func:`pair_kinds`, the tests)."""
    group = slot_group(n_rep, slot_rows)
    if group:
        start = first // group * group
        return xp.where((last <= start + group) & (start + group <= wide),
                        start, -1)
    group = narrow_rows(n_rep)
    start = first // 8 * 8
    if group > -(-n_rep // 8) * 8:      # else no row's group passes the end
        start = xp.minimum(start, wide - group)
    return xp.where(last <= start + group, start, -1)


def tile_pairs(served, rows: int, num_blocks: int, xp=jnp):
    """The kernel's walk over ``served [T, max_blocks_per_seq]``, a row's
    table entry in the columns it attends and -1 elsewhere (tiles of
    ``rows`` consecutive rows, the last one filled up with rows that
    attend nothing): for every tile the distinct
    ``(table column, block id)`` pairs that at least one row of the tile
    attends, each once, in order of column and block.

    Returns ``(count [tiles], blocks [tiles, P], cols [tiles, P])`` with
    ``P = rows * max_blocks_per_seq``, what a tile of rows that share
    nothing lists; entries from a tile's ``count`` on mean nothing.
    ``xp`` is ``jnp`` (the walk the kernel is handed) or ``numpy`` (the
    engine's ``nxd_paged_block_visits_total``, the tests)."""
    t, maxb = served.shape
    none = maxb * num_blocks
    cols = xp.arange(maxb, dtype=xp.int32)[None, :]
    key = xp.where(served >= 0, cols * num_blocks + served, none)
    key = xp.concatenate(
        [key, xp.full((-t % rows, maxb), none, key.dtype)]).reshape(
            -1, rows * maxb)
    key = xp.sort(key, axis=-1)
    # a pair named by several rows of the tile lies in one run: keep the
    # run's first, and sort the rest behind the tile's pairs
    again = xp.concatenate(
        [xp.zeros_like(key[:, :1], dtype=bool), key[:, 1:] == key[:, :-1]],
        axis=1)
    key = xp.sort(xp.where(again, none, key), axis=-1)
    count = xp.sum(key < none, axis=-1).astype(xp.int32)
    return (count, (key % num_blocks).astype(xp.int32),
            (key // num_blocks).astype(xp.int32))


def pair_kinds(served, n_rep: int, num_blocks: int, pairs=None,
               slot_rows: int = 1):
    """A step's pairs (one layer's worth) by how the kernel computes
    them, ``[narrow, one_row_whole, shared]``, from ``served [T,
    max_blocks_per_seq]`` (NumPy: a row's table entry in the columns it
    attends, -1 elsewhere): ``narrow``, over one group of the tile
    (:func:`narrow_start`, the walk's own rule); ``one_row_whole``, a
    pair that one packed row alone names and that runs over the whole
    tile all the same; ``shared``, a pair that several rows of the tile
    name and no group holds. They sum to :func:`tile_pairs`' count. The
    engine's ``nxd_paged_pairs_total`` and
    ``nxd_paged_shared_pairs_total``, counted on the host (``pairs``:
    :func:`host_pairs` of the same step, where the caller has them)."""
    first, last, start, _ = pairs or host_pairs(served, n_rep, num_blocks,
                                                slot_rows)
    narrow = start >= 0
    return np.array([narrow.sum(), (~narrow & (first == last)).sum(),
                     (~narrow & (first != last)).sum()], np.int64)


def host_pairs(served, n_rep: int, num_blocks: Optional[int] = None,
               slot_rows: int = 1):
    """A step's pairs from ``served [T, max_blocks_per_seq]`` (NumPy), one
    entry a (tile, column, block) that some row of the tile attends:
    ``(first, last, start, group)``, the first and last row of its tile
    that names it, where its narrow product begins in the tile's stacked
    rows (:func:`narrow_start`, -1 over the whole tile), and a number of
    its (tile, start)."""
    tokens, maxb = served.shape
    rows = tile_rows(n_rep, tokens)
    blocks = num_blocks or int(served.max(initial=0)) + 1
    row, col = np.nonzero(served >= 0)               # by row, then column
    key = ((row // rows).astype(np.int64) * maxb + col) * blocks + served[
        row, col]
    key, first, pair = np.unique(key, return_index=True, return_inverse=True)
    first = row[first] % rows                        # a pair's first namer
    last = np.zeros_like(first)
    np.maximum.at(last, pair, row % rows)
    wide = rows * n_rep
    start = narrow_start(first * n_rep, (last + 1) * n_rep, n_rep, wide,
                         xp=np, slot_rows=slot_rows)
    return first, last, start, key // (maxb * blocks) * wide + start


class TileWalk(NamedTuple):
    """What the kernel is handed of a step's routing
    (:func:`tile_walk`), the same for every layer: ``count [tiles]``,
    ``blocks`` and ``cols [tiles * P]`` the tiles' pairs
    (:func:`tile_pairs`); ``narrow [tiles * P]``, where one group of
    :func:`narrow_rows` stacked rows of the tile holds the heads of every
    row that names a pair (every pair that one packed row names, a decode
    row's: nothing to share; neighbours that share a block where they
    fit), the group's first row, a whole sublane (:func:`narrow_start`),
    else -1; and a tile at a time with each of its ``rows`` rows repeated
    once a query head of a K/V head (row ``r * n_rep + rep`` of the tile
    is packed row ``r``'s head ``rep``): ``served [tiles, rows * n_rep,
    max_blocks_per_seq]`` the row's table entry in the columns it
    attends, -1 elsewhere; ``q_pos [tiles, rows * n_rep, 1]``; and
    ``q_lo``, the first position the row attends exactly: of its window
    (a window-summary cache) or ``q_pos - sliding + 1`` (a sliding-window
    layer), else ``None``."""

    count: jax.Array
    blocks: jax.Array
    cols: jax.Array
    narrow: jax.Array
    served: jax.Array
    q_pos: jax.Array
    q_lo: Optional[jax.Array]


def tile_walk(tables, q_pos, block_size: int, num_blocks: int, n_rep: int,
              window=None, sliding=None, slot_rows: int = 1) -> TileWalk:
    """The walk of one packed step, from ``tables [T, max_blocks_per_seq]``
    and ``q_pos [T]`` alone: routing, built once a step beside the write
    indices and handed to every layer's kernel. ``T`` is padded to whole
    tiles with pad rows. A pad row attends nothing and belongs to no
    pair. With ``sliding`` the tables are a sliding-window layer's rings
    (:func:`sliding_column_live`): a column wholly behind the window of
    every row of a tile is no pair."""
    t, maxb = tables.shape
    rows = tile_rows(n_rep, t)
    pad = -t % rows
    tables = jnp.pad(tables.astype(jnp.int32), ((0, pad), (0, 0)),
                     constant_values=-1)
    q_pos = jnp.pad(q_pos.astype(jnp.int32), (0, pad),
                    constant_values=PAD_POSITION)
    cols = jnp.arange(maxb, dtype=jnp.int32)[None, :]
    if sliding is not None:
        live = sliding_column_live(tables, cols, q_pos[:, None], block_size,
                                   sliding, maxb)
    elif window is None:
        live = column_live(tables, cols, q_pos[:, None], block_size)
    else:
        live = window_column_kinds(tables, cols, q_pos[:, None], block_size,
                                   *window) > 0
    served = jnp.where(live, tables, -1)
    count, blocks, pair_cols = tile_pairs(served, rows, num_blocks)
    # the first and last row of its tile that names each pair
    names = (jnp.take_along_axis(
        served.reshape(-1, rows, maxb).swapaxes(1, 2),
        jnp.minimum(pair_cols, maxb - 1)[:, :, None], axis=1)
        == blocks[:, :, None])                           # [tiles, P, rows]
    first = jnp.argmax(names, axis=-1) * n_rep
    last = (rows - jnp.argmax(names[:, :, ::-1], axis=-1)) * n_rep
    narrow = narrow_start(first, last, n_rep, rows * n_rep,
                          slot_rows=slot_rows).astype(jnp.int32)

    def by_tile(x):
        return jnp.repeat(x, n_rep, axis=0).reshape(
            (-1, rows * n_rep) + x.shape[1:])

    return TileWalk(
        count=count, blocks=blocks.reshape(-1), cols=pair_cols.reshape(-1),
        narrow=narrow.reshape(-1),
        served=by_tile(served), q_pos=by_tile(q_pos[:, None]),
        q_lo=(by_tile((q_pos - sliding + 1)[:, None]) if sliding is not None
              else None if window is None else by_tile(
                  ((q_pos // window[0]) * window[0])[:, None])))


#: what the ring of a unit's blocks may take of VMEM (two halves), and a
#: unit's float32 scores ``[stacked rows, positions]``: :func:`unit_blocks`
RING_BYTES = 5 << 20
SCORE_BYTES = 384 << 10


def unit_blocks(rows: int, block_bytes: int, block_size: int) -> int:
    """Blocks of a unit of a paged kernel that ``rows`` stacked rows
    attend (a run's narrow group, a shared pair's whole tile), a block
    ``block_bytes`` in the pool (its keys and its values): the power of
    two, 8 at most, whose two ring halves fit :data:`RING_BYTES` and
    whose scores ``[rows, blocks x block_size]`` fit :data:`SCORE_BYTES`.
    The latent kernel's rows of 640 bf16 lanes in blocks of 128 (160
    KiB): 8 blocks for a run of 24 or 64 stacked rows, 4 for a tile of
    192 and for a slab of 128 of a tile of 512, which would take 1 whole
    (``PERF.md``, Findings, PR 39 and 54). The paged kernel's runs: 8 for a
    block of 320 KiB (4 K heads of 192 beside 4 V heads of 128) or less,
    4 for 512 KiB (8 K/V heads of 128), 1 for 2 MiB (32 heads), which is
    the walk and the kernel without runs (``PERF.md``, Findings, PR 46)."""
    most = min(8, RING_BYTES // (2 * block_bytes),
               SCORE_BYTES // (4 * rows * block_size))
    return 1 << max(most, 1).bit_length() - 1


def run_length(n_rep: int, block_bytes: int, block_size: int,
               max_cols: int, slot_rows: int = 1) -> int:
    """Blocks of a run of the paged kernel for rows of ``n_rep`` query
    heads a K/V head over blocks of ``block_bytes`` (keys and values)
    under tables of ``max_cols`` columns: :func:`unit_blocks` of a narrow
    group's rows, and no more than a row has columns (a ring of two is a
    run of two). 1 is the kernel without runs."""
    return min(unit_blocks(narrow_rows(n_rep, slot_rows), block_bytes,
                           block_size),
               1 << max_cols.bit_length() - 1)


def run_blocks(k_pool, v_pool, n_rep: int, max_cols: int,
               slot_rows: int = 1) -> int:
    """:func:`run_length` over these pools (the stacks ``[L, num_blocks,
    block_size, ...]``, arrays or their shapes' structs)."""
    block_bytes = sum(math.prod(x.shape[2:]) * jnp.dtype(x.dtype).itemsize
                      for x in (k_pool, v_pool))
    return run_length(n_rep, block_bytes, k_pool.shape[2], max_cols,
                      slot_rows)


class RunWalk(NamedTuple):
    """What a kernel that takes its pairs in runs is handed of a step's
    routing, the same for every layer: a :class:`TileWalk`'s pairs in the
    order of the kernel's units (:func:`pair_runs`): ``units [tiles]`` the
    units of a tile; ``blocks``, ``cols`` and ``narrow [tiles * P + room]``
    the tile's pairs, the shared ones first and then each narrow group's
    by column; ``lens``, at a unit's first pair its number of pairs
    (:func:`unit_blocks` at most) and 0 elsewhere; ``served``, ``q_pos``
    and ``q_lo`` the walk's own."""

    units: jax.Array
    blocks: jax.Array
    cols: jax.Array
    narrow: jax.Array
    lens: jax.Array
    served: jax.Array
    q_pos: jax.Array
    q_lo: Optional[jax.Array] = None


def pair_runs(count, blocks, cols, narrow, num_blocks: int, max_cols: int,
              stride: int, groups: int, run: int, whole_run: int):
    """A walk's pairs (``count [tiles]``; ``blocks``, ``cols``, ``narrow
    [tiles, P]``, ``P`` no less than the largest count: :func:`tile_walk`
    over tables of ``max_cols`` columns) cut into a kernel's units: the
    pairs that no one group holds (``narrow < 0``) in runs of up to
    ``whole_run``, whichever rows name each; the pairs of one narrow
    group, keyed by the group's first row (``narrow``, a multiple of
    ``stride``, ``groups`` of them a tile: a decode row's own blocks, one
    a column; under GQA-4 the blocks of the two packed rows whose heads
    share a sublane, under GQA-6 those of the rows whose groups begin on
    one) in runs of up to ``run`` successive ones of that group, the last
    shorter. Returns ``(units [tiles], blocks, cols, narrow, lens [tiles,
    P])`` with the pairs reordered, the shared ones first and then group
    by group, each in order of column and block, and ``lens`` the length
    of the unit that starts at a pair, 0 where none does. One sort of one
    key and no gather (a gather of the pairs cost the step 0.6 ms:
    ``PERF.md``, Findings, PR 39)."""
    tiles, per = blocks.shape
    span = max_cols * num_blocks            # a (column, block) as one number
    assert (groups + 2) * span < 2 ** 31
    at = jnp.arange(per, dtype=jnp.int32)[None, :]
    live = at < count[:, None]
    # 0 a shared pair, 1 + g a pair of group g, last what lies beyond the
    # tile's count
    kind = jnp.where(live, jnp.where(narrow < 0, 0, 1 + narrow // stride),
                     groups + 1).astype(jnp.int32)
    key = jnp.sort(
        kind * span + jnp.where(live, cols * num_blocks + blocks, 0), axis=-1)
    kind, pair = key // span, key % span
    # the pairs lie sorted by kind: a kind's first is at the number of
    # pairs of the kinds before it (a handful of kinds: a select each)
    first, end, before = 0, 0, 0
    for k in range(groups + 1):
        mine = kind == k
        many = jnp.sum(mine, axis=-1, keepdims=True)
        first = jnp.where(mine, before, first)
        end = jnp.where(mine, before + many, end)
        before = before + many
    most = jnp.where(kind == 0, whole_run, run)
    lens = jnp.where(live & ((at - first) % most == 0),
                     jnp.minimum(most, end - at), 0).astype(jnp.int32)
    lone = (kind > 0) & (kind <= groups)
    return (jnp.sum(lens > 0, axis=-1).astype(jnp.int32),
            pair % num_blocks, pair // num_blocks,
            jnp.where(lone, (kind - 1) * stride, -1), lens)


def run_stride(n_rep: int, slot_rows: int = 1) -> int:
    """What the first rows of a tile's narrow groups are multiples of
    (:func:`narrow_start`): the group itself where it is one packed row's
    heads (``n_rep`` whole sublanes) or a slot's rows' heads
    (:func:`slot_group`), else a sublane."""
    return (slot_group(n_rep, slot_rows)
            or (n_rep if n_rep % 8 == 0 else 8))


def run_walk(walk: TileWalk, num_blocks: int, n_rep: int, run: int,
             whole_run: int, room: Optional[int] = None,
             slot_rows: int = 1) -> RunWalk:
    """:func:`pair_runs` of a step's :class:`TileWalk` over rows of
    ``n_rep`` stacked heads, in runs of ``run`` blocks of a narrow group
    and ``whole_run`` that a tile shares, with ``room`` entries past the
    last tile's pairs: as far as the kernel reads a unit's pairs whatever
    its length (the longer kind of unit, unless told)."""
    tiles, wide, max_cols = walk.served.shape
    stride = run_stride(n_rep, slot_rows)
    units, *pairs = pair_runs(
        walk.count, *(x.reshape(tiles, -1) for x in
                      (walk.blocks, walk.cols, walk.narrow)),
        num_blocks, max_cols, stride, wide // stride, run, whole_run)
    room = max(run, whole_run) if room is None else room
    blocks, cols, narrow, lens = (
        jnp.pad(x.reshape(-1), (0, room)) for x in pairs)
    return RunWalk(units=units, blocks=blocks, cols=cols, narrow=narrow,
                   lens=lens, served=walk.served, q_pos=walk.q_pos,
                   q_lo=walk.q_lo)


def block_fetches(served, n_rep: int, run: int, pairs=None,
                  slot_rows: int = 1) -> np.ndarray:
    """Pool blocks a kernel that takes its pairs in runs fetches for one
    layer of a packed step whose rows attend ``served [T,
    max_blocks_per_seq]`` (NumPy: a row's table entry in the columns it
    attends, -1 elsewhere), by how: ``[in_run, alone, whole]``, a fetch
    that rode a unit of two or more blocks of one narrow group, a narrow
    pair that is a unit by itself (every one where ``run`` is 1), a pair
    that rows of the tile share beyond a group. What :func:`pair_runs`
    makes of the step, counted on the host from the tables themselves by
    the walk's own rule (:func:`narrow_start`;
    ``nxd_paged_block_fetches_total``, ``nxd_mla_block_fetches_total``;
    ``tests/walk_checks.py`` holds the two to each other; ``pairs``:
    :func:`host_pairs` of the same step, where the caller has them)."""
    *_, start, group = pairs or host_pairs(served, n_rep,
                                           slot_rows=slot_rows)
    # a (tile, group)'s narrow pairs are cut into runs, the last shorter
    many = np.bincount(group[start >= 0])
    alone = many if run == 1 else many % run == 1
    return np.array([many.sum() - alone.sum(), alone.sum(),
                     (start < 0).sum()], np.int64)


def step_walk(tables, q_pos, block_size: int, num_blocks: int, head_dim: int,
              n_rep: int, window=None, force_pallas: Optional[bool] = None,
              sliding=None, *, pools, slot_rows: int = 1):
    """The walk of the layers of one step over ``pools``, the K and V
    stacks they attend, or ``None`` where :func:`paged_attention` runs the
    XLA reference for these shapes (:func:`paged_attention_impl`):
    :func:`tile_walk`, and where the kernel takes these pools' pairs in
    runs (:func:`run_blocks` more than 1) its cut into units
    (:func:`run_walk`; a pair that a tile shares stays a unit by itself).
    A run of 1 is the tile walk as it is: no second sort. ``slot_rows``:
    the packed rows a slot holds side by side in the step, a family's
    own number (a block family's block length; :func:`narrow_rows`)."""
    if paged_attention_impl(head_dim, block_size, force_pallas) == "xla":
        return None
    walk = tile_walk(tables, q_pos, block_size, num_blocks, n_rep, window,
                     sliding, slot_rows)
    run = run_blocks(*pools, n_rep, tables.shape[1], slot_rows)
    return walk if run == 1 else run_walk(walk, num_blocks, n_rep, run, 1,
                                          slot_rows=slot_rows)


def _head_rows(block_ref):
    """The ``[positions, D]`` rows of every K/V head of pool blocks
    ``block_ref [positions, KV, D]`` in VMEM (a block, or a run's blocks
    side by side), widened exactly to float32, a head at a time (``KV``
    arrays, made as they are asked for). The blocks lie as the pool does,
    a slot's heads on sublanes, so a head's rows are a sublane-strided
    read: every ``KV``-th row of the blocks seen as ``[positions * KV,
    D]``. bf16 and int8 rows lie two and four to a 32-bit sublane; those
    are read as words, one strided read for the heads that share them,
    and taken apart with shifts. A wide-key pool's blocks ``[positions,
    KV * D]`` give their 128-lane chunks instead (:func:`key_chunks`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if len(block_ref.shape) == 2:
        # a wide-key pool's blocks: their chunks, aligned lane slices
        for c in range(block_ref.shape[1] // LANES):
            yield block_ref[:, c * LANES:(c + 1) * LANES].astype(jnp.float32)
        return
    bs, kv, d = block_ref.shape
    dtype = block_ref.dtype
    packing = 4 // dtype.itemsize
    if kv % packing:
        # heads that fill no whole word (tiny shapes, off the chip)
        for h in range(kv):
            yield block_ref[:, h, :].astype(jnp.float32)
        return
    flat = block_ref.reshape(bs * kv, d)
    if packing == 1:
        for h in range(kv):
            yield flat[pl.ds(h, bs, stride=kv), :].astype(jnp.float32)
        return
    words = flat.bitcast(jnp.uint32)
    for g in range(kv // packing):
        w = words[pl.ds(g, bs, stride=kv // packing), :]       # [bs, D] u32
        for part in range(packing):
            if dtype == jnp.bfloat16:
                # a bf16 is the upper half of the float32 of its value
                bits = w << 16 if part == 0 else w & jnp.uint32(0xFFFF0000)
                yield pltpu.bitcast(bits, jnp.float32)
            else:
                signed = pltpu.bitcast(w << (24 - 8 * part), jnp.int32)
                yield (signed >> 24).astype(jnp.float32)


def _p_times_v(p, v):
    """``p [rows, block_size]`` float32 times ``v [block_size, D]`` into
    float32. Against a float32 ``v`` that is one float32 product. A bf16
    ``v`` (a bf16 pool's values as stored, an int8 pool's widened exactly)
    has no low part, so ``p`` goes through the MXU in two bf16 parts, its
    upper 16 bits of mantissa, and is not rounded to bf16. On the v5e
    that read the float32 product's own error against the reference at
    18-26% less of the kernel's time, and three parts a quarter of the
    error at 60% more (``PERF.md``, Findings, PR 33)."""
    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    if v.dtype == jnp.float32:
        return dot(p, v)
    high = p.astype(v.dtype)
    return dot(high, v) + dot((p - high.astype(jnp.float32)).astype(v.dtype),
                              v)


def _p_times_v_stacked(p, v):
    """:func:`_p_times_v` in one product: ``p [rows, positions]`` float32
    against ``v [positions, D]``. A bf16 ``v`` meets ``p``'s two bf16
    parts (its upper 16 bits of mantissa, not rounded to bf16) stacked on
    rows, so the values are pushed through the MXU once and the halves
    are added in float32: the same products and sums. Few rows against
    many positions (a run's narrow group, the latent kernel's units) are
    bound by the pushes, a 128 x 128 tile of ``v`` each, not by the rows
    streamed past them."""
    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    if v.dtype == jnp.float32:
        return dot(p, v)
    high = p.astype(v.dtype).astype(jnp.float32)
    both = dot(jnp.concatenate([high, p - high], axis=0).astype(v.dtype), v)
    return both[:p.shape[0]] + both[p.shape[0]:]


def _kernel_refs(refs, quantized: bool, window, sink: bool):
    """The paged kernels' operands after the scalars, by name: the tile's
    rows, the stacks in HBM, the output, then the scratch."""
    import types

    scales = ["ks", "vs"] if quantized else []
    names = (["served", "qpos"] + ["qlo"] * (window is not None)
             + ["q", "k_hbm", "v_hbm", "pos_hbm"] + ["sink"] * sink
             + [f"{x}_hbm" for x in scales]
             + ["o", "k_buf", "v_buf", "pos_buf"]
             + [f"{x}_buf" for x in scales] + ["sems", "m", "l", "acc"])
    assert len(names) == len(refs)
    r = types.SimpleNamespace(**dict(zip(names, refs)))
    # bf16 queries meet bf16 (or exactly widened int8) keys as stored;
    # any other pairing is multiplied in float32
    r.operand = (jnp.bfloat16 if r.q.dtype == jnp.bfloat16
                 and r.k_buf.dtype != jnp.float32 else jnp.float32)
    return r


def _start_softmax(r):
    """The online softmax's starting state: nothing attended, or the
    sink's logit in the denominator."""
    if hasattr(r, "sink"):
        r.m[...] = r.sink[...]
        r.l[...] = jnp.ones_like(r.l)
    else:
        r.m[...] = jnp.full_like(r.m, -jnp.inf)
        r.l[...] = jnp.zeros_like(r.l)
    r.acc[...] = jnp.zeros_like(r.acc)


def _q_times_k(r, h, rows, keys):
    """Head ``h``'s scores of the tile's rows ``rows`` against ``keys``:
    its keys whole (one array ``[positions, D]``), or its 128-lane chunks
    of a wide-key pool, each met by the query's chunk."""
    def dot(a, b):
        return jax.lax.dot_general(
            a.astype(r.operand), b.astype(r.operand),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    if len(keys) == 1:
        return dot(r.q[h, rows, :], keys[0])
    return sum(dot(r.q[h, rows, i * LANES:(i + 1) * LANES], k)
               for i, k in enumerate(keys))


def _softmax_step(r, h, rows, s, ok, v, p_scale=None,
                  p_times_v=_p_times_v):
    """One step of head ``h``'s online softmax over the tile's rows
    ``rows``: scores ``s [rows', positions]`` that count where ``ok``,
    values ``v [positions, Dv]``, ``p_scale`` an int8 pool's value
    scales, ``p_times_v`` the form of the last product."""
    s = jnp.where(ok, s, -jnp.inf)                    # [rows', positions]
    m_prev = r.m[h, rows, :]                          # [rows', 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    r.m[h, rows, :] = m_new
    r.l[h, rows, :] = r.l[h, rows, :] * corr + jnp.sum(
        p, axis=-1, keepdims=True)
    if p_scale is not None:
        p = p * p_scale
    r.acc[h, rows, :] = (r.acc[h, rows, :] * corr
                         + p_times_v(p, v.astype(r.operand)))


def _head_keys(block_ref, key_at):
    """What :func:`_q_times_k` meets of pool blocks ``block_ref``, a K/V
    head at a time: the head's rows (:func:`_head_rows`), or its chunks
    of a wide-key pool (``key_at``, :func:`key_chunks`)."""
    rows = _head_rows(block_ref)
    if key_at is None:
        for k in rows:
            yield [k]
    else:
        chunks = list(rows)             # lane slices, whichever head's
        for mine in key_at:
            yield [chunks[c] for c in mine]


def _named(served, column, col, block):
    """Which of the rows ``served [rows', maxb]`` name pool block
    ``block`` in table column ``col``: ``[rows', 1]``."""
    return jnp.max(jnp.where((column == col) & (served == block), 1, 0),
                   axis=1, keepdims=True) > 0


def _paged_kernel(count_ref, blocks_ref, cols_ref, narrow_ref, layer_ref,
                  *refs, pairs: int, group: int, scale: float,
                  quantized: bool, window: Optional[tuple],
                  key_at: Optional[tuple] = None, sink: bool = False):
    """One tile of packed rows against the pool blocks its rows attend, a
    pair a turn (the kernel of a pool whose blocks ride in no runs,
    :func:`run_blocks` 1): a loop over the tile's ``count_ref[tile]``
    pairs (:func:`tile_pairs`), each block copied once from the stacks in
    HBM into one of two VMEM buffers while the block before it is
    computed, so the kernel's time follows the pairs and not the table's
    width.

    A pair's block serves the rows of the tile that name it in the pair's
    column (``served_ref``); for the others every score is masked, as a
    dead slot position is. A K/V head at a time: scores ``[rows * n_rep,
    D] x [D, block_size]`` on the MXU from the stored operands into
    float32, the online softmax in float32 scratch, and ``p x v`` with
    ``p`` float32 (:func:`_p_times_v`). An int8 block is widened exactly
    and its scales multiply the score and the probability. Where one
    group of ``group`` rows holds every row that names the pair
    (``narrow_ref``, the group's first row, a whole sublane: a decode
    row's block, which its neighbours in the tile do not share) the
    products and the softmax run over that group alone.

    With ``window`` (a window-summary cache, the ``eva_attention``
    kernel) the rows of columns under the ring's width are exact and
    count from the first position of the row's window to the row's own,
    those of the columns from there on are summaries and count whole; one
    softmax runs over both. A sliding-window layer (``swa_attention``) is
    that with a ring as wide as the table, so no column is a summary, and
    the window's first position the row's own less the window.

    ``key_at`` (a wide-key pool, :func:`key_chunks`): head ``h``'s score
    is the sum over its chunks of the query's chunk times the block's.
    ``sink``: a logit a query head that stands in the softmax's
    denominator and holds no value, the online softmax's starting state
    (``m`` the logit, ``l`` 1, the accumulator 0); a row that attends
    nothing still gives zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = _kernel_refs(refs, quantized, window, sink)
    tile = pl.program_id(0)
    count = count_ref[tile]
    layer = layer_ref[0]
    kv = r.q.shape[0]

    def copies(j, slot):
        b = blocks_ref[tile * pairs + j]
        moves = [(r.k_hbm.at[layer, b], r.k_buf),
                 (r.v_hbm.at[layer, b], r.v_buf), (r.pos_hbm.at[b], r.pos_buf)]
        if quantized:
            moves += [(r.ks_hbm.at[layer, b], r.ks_buf),
                      (r.vs_hbm.at[layer, b], r.vs_buf)]
        return [pltpu.make_async_copy(src, buf.at[slot], r.sems.at[slot, i])
                for i, (src, buf) in enumerate(moves)]

    _start_softmax(r)

    @pl.when(count > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def pair(j, carry):
        slot = j % 2

        @pl.when(j + 1 < count)
        def _next():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        block = blocks_ref[tile * pairs + j]
        col = cols_ref[tile * pairs + j]
        pos = r.pos_buf[slot]                           # [1, bs]
        k_heads = list(_head_keys(r.k_buf.at[slot], key_at))
        v_heads = list(_head_rows(r.v_buf.at[slot]))

        def attend(rows):
            """The pair against the tile's rows ``rows`` (a slice)."""
            served = r.served[rows, :]                  # [rows', maxb]
            column = jax.lax.broadcasted_iota(jnp.int32, served.shape, 1)
            named = _named(served, column, col, block)  # [rows', 1]
            ok = pos <= r.qpos[rows, :]                 # [rows', bs]
            if window is not None:
                ok = jnp.logical_or(ok & (pos >= r.qlo[rows, :]),
                                    col >= window[1])
            ok = ok & named
            for h in range(kv):
                s = _q_times_k(r, h, rows, k_heads[h]) * scale
                if quantized:
                    s = s * r.ks_buf[slot, pl.ds(h, 1), :]
                _softmax_step(r, h, rows, s, ok, v_heads[h],
                              r.vs_buf[slot, pl.ds(h, 1), :] if quantized
                              else None)

        wide = r.q.shape[1]
        if group >= wide:
            attend(slice(None))
        else:
            start = narrow_ref[tile * pairs + j]

            @pl.when(start >= 0)
            def _narrow():
                attend(pl.ds(pl.multiple_of(start, 8), group))

            @pl.when(start < 0)
            def _whole():
                attend(slice(None))
        return carry

    jax.lax.fori_loop(0, count, pair, None)
    r.o[...] = (r.acc[...] / jnp.maximum(r.l[...], 1e-30)).astype(r.o.dtype)


def _paged_run_kernel(units_ref, blocks_ref, cols_ref, narrow_ref, lens_ref,
                      layer_ref, *refs, pairs: int, group: int, run: int,
                      whole_named: bool, scale: float, quantized: bool,
                      window: Optional[tuple],
                      key_at: Optional[tuple] = None, sink: bool = False):
    """:func:`_paged_kernel` a *unit* of the walk a turn (:func:`pair_runs`;
    the kernel of a pool whose blocks ride in runs of ``run``): up to
    ``run`` blocks that one narrow group of the tile names (a decode row's
    own blocks, column after column), or one block that rows of the tile
    share beyond a group. A unit's blocks are copied side by side into one
    half of a ring (keys, values, positions, an int8 pool's scales, each
    ``run`` blocks long) while the unit before it is computed from the
    other half, and are one step of the online softmax of the rows they
    serve: a K/V head at a time one score product ``[group, D] x [D, run x
    block_size]`` (the sum of two over a wide-key pool's chunks), one
    read, correction and write of the running max, sum and accumulator,
    and one ``p x v`` against ``[run x block_size, Dv]``; precisions as
    the pair's. Each block of a run stands under its own mask: the rows
    of the group that name it (two packed rows share a group under GQA-4,
    neighbouring rows' groups overlap under GQA-6; ``whole_named``: the
    group is one packed row's heads and names every block of its runs, so
    nothing is compared), its column's kind (a window-summary cache's
    exact and summary columns may share a run), and whether the run has
    it at all: a run is computed over the least power of two of blocks
    that holds it, what is missing of those are dead positions, and the
    ring is zeroed once so that ``p = 0`` meets no stale NaN there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = _kernel_refs(refs, quantized, window, sink)
    tile = pl.program_id(0)
    units = units_ref[tile]
    layer = layer_ref[0]
    base = tile * pairs
    kv = r.q.shape[0]
    bs = r.pos_hbm.shape[2]

    def copies(first, side, then):
        # the copies of the unit that starts at pair ``first``
        n = lens_ref[base + first]
        for k in range(run):
            @pl.when(k < n)
            def _block():
                b = blocks_ref[base + first + k]
                at = pl.ds(k * bs, bs)
                moves = [(r.k_hbm.at[layer, b], r.k_buf.at[side, at]),
                         (r.v_hbm.at[layer, b], r.v_buf.at[side, at]),
                         (r.pos_hbm.at[b], r.pos_buf.at[side, :, at])]
                if quantized:
                    moves += [
                        (r.ks_hbm.at[layer, b], r.ks_buf.at[side, :, at]),
                        (r.vs_hbm.at[layer, b], r.vs_buf.at[side, :, at])]
                for i, (src, dst) in enumerate(moves):
                    then(pltpu.make_async_copy(src, dst,
                                               r.sems.at[side, k, i]))

    @pl.when(tile == 0)
    def _clean():
        # what a short run leaves of the ring is multiplied by p = 0
        for buf in (r.k_buf, r.v_buf) + (
                (r.ks_buf, r.vs_buf) if quantized else ()):
            buf[...] = jnp.zeros_like(buf)

    @pl.when(units > 0)
    def _first():
        copies(0, 0, lambda c: c.start())

    _start_softmax(r)

    def attend(first, side, rows, blocks: int, n, named: bool):
        """The unit's first ``blocks`` blocks, ``n`` of them fetched,
        against the tile's rows ``rows`` (a slice)."""
        span = pl.ds(0, blocks * bs)
        if named:
            served = r.served[rows, :]                  # [rows', maxb]
            column = jax.lax.broadcasted_iota(jnp.int32, served.shape, 1)
        ok = []
        for k in range(blocks):
            pos = r.pos_buf[side, :, k * bs:(k + 1) * bs]       # [1, bs]
            mine = pos <= r.qpos[rows, :]               # [rows', bs]
            col = cols_ref[base + first + k]
            if window is not None:
                mine = mine & (pos >= r.qlo[rows, :])
                if window[1] < r.served.shape[1]:       # summary columns
                    mine = jnp.logical_or(mine, col >= window[1])
            if named:
                mine = mine & _named(served, column, col,
                                     blocks_ref[base + first + k])
            # more than half of ``blocks`` are fetched: the rest may be
            ok.append(mine if k < max(blocks // 2, 1) else mine & (k < n))
        ok = ok[0] if blocks == 1 else jnp.concatenate(ok, axis=1)
        # a head's keys and values are taken apart as its turn comes
        for h, (keys, v) in enumerate(zip(
                _head_keys(r.k_buf.at[side, span], key_at),
                _head_rows(r.v_buf.at[side, span]))):
            s = _q_times_k(r, h, rows, keys) * scale
            if quantized:
                s = s * r.ks_buf[side, pl.ds(h, 1), span]
            _softmax_step(r, h, rows, s, ok, v,
                          r.vs_buf[side, pl.ds(h, 1), span] if quantized
                          else None, p_times_v=_p_times_v_stacked)

    def unit(u, first):
        side = u % 2
        n = lens_ref[base + first]

        @pl.when(u + 1 < units)
        def _next():
            copies(first + n, 1 - side, lambda c: c.start())

        copies(first, side, lambda c: c.wait())
        start = narrow_ref[base + first]

        # a run over the least power of two of blocks that holds it: a
        # group's last run is short, and a ring of five columns is a run
        # of four and one of one
        for blocks in (1 << i for i in range(run.bit_length())):
            @pl.when((start >= 0) & (n > blocks // 2) & (n <= blocks))
            def _run():
                attend(first, side, pl.ds(pl.multiple_of(start, 8), group),
                       blocks, n, not whole_named)

        @pl.when(start < 0)
        def _whole():
            attend(first, side, slice(None), 1, n, True)

        return first + n

    jax.lax.fori_loop(0, units, unit, 0)
    r.o[...] = (r.acc[...] / jnp.maximum(r.l[...], 1e-30)).astype(r.o.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, pool_pos, tables, q_pos,
                            layer, k_scale, v_scale, scale, interpret=False,
                            window=None, walk=None, sliding=None, sink=None,
                            slot_rows=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, n, d = q.shape
    nb, bs = k_pool.shape[1:3]
    kv, dv = v_pool.shape[3:]
    key_at = None
    if k_pool.ndim == 4:
        key_at = key_chunks(kv, d)
        q = queries_to_lanes(q, kv)
        d = q.shape[-1]
    maxb = tables.shape[1]
    n_rep = n // kv
    quantized = k_scale is not None
    run = run_blocks(k_pool, v_pool, n_rep, maxb, slot_rows)
    if walk is None:
        walk = tile_walk(tables, q_pos, bs, nb, n_rep, window, sliding,
                         slot_rows)
        if run > 1:
            walk = run_walk(walk, nb, n_rep, run, 1, slot_rows=slot_rows)
    if isinstance(walk, RunWalk) != (run > 1):
        raise ValueError(
            f"the kernel takes these pools' pairs in runs of {run} and was "
            f"handed a {type(walk).__name__}: build the step's walk with "
            "step_walk(..., pools=) over the pools the layer attends")
    if sliding is not None:
        # the window-summary kernel's exact rows, and no summary column:
        # a row counts from ``q_lo`` to the query's own position
        window = (sliding, maxb)
    tiles, wide, _ = walk.served.shape          # wide = rows * n_rep
    rows = wide // n_rep
    pairs = rows * maxb

    # a tile's queries a K/V head at a time, the rows' n_rep query heads
    # of it stacked: [tiles, KV, rows * n_rep, D], as the walk's rows lie
    def by_tile(x):
        x = jnp.pad(x, ((0, tiles * rows - t), (0, 0), (0, 0)))
        return x.reshape(tiles, rows, kv, n_rep, d).swapaxes(1, 2).reshape(
            tiles, kv, wide, d)

    def row_block(*last):
        return pl.BlockSpec((None, wide) + last, lambda i, *_: (i, 0, 0))

    def head_block(width=d):
        return pl.BlockSpec((None, kv, wide, width),
                            lambda i, *_: (i, 0, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_block(maxb), row_block(1)]
    operands = [walk.served, walk.q_pos]
    if window is not None:
        in_specs.append(row_block(1))
        operands.append(walk.q_lo)
    # the stacks stay in HBM: the kernel copies the blocks it walks. The
    # positions (shared by the layers) ride as [nb, 1, bs] rows
    in_specs += [head_block(), hbm, hbm, hbm]
    operands += [by_tile(q), k_pool, v_pool, pool_pos.reshape(nb, 1, bs)]
    if sink is not None:
        # a query head's logit where the walk's rows have the head: row
        # ``r * n_rep + rep`` of K/V head ``h`` is head ``h * n_rep + rep``
        in_specs.append(pl.BlockSpec((kv, wide, 1), lambda i, *_: (0, 0, 0)))
        operands.append(jnp.tile(
            sink.astype(jnp.float32).reshape(kv, 1, n_rep),
            (1, rows, 1)).reshape(kv, wide, 1))
    # two buffers of a block, or the two halves of a ring of ``run``
    scratch = [pltpu.VMEM((2, run * bs) + k_pool.shape[3:], k_pool.dtype),
               pltpu.VMEM((2, run * bs, kv, dv), v_pool.dtype),
               pltpu.VMEM((2, 1, run * bs), jnp.int32)]
    if quantized:
        # slots on lanes is how the chip stores an array whose last dim is
        # a few heads wide, and how the step's scatter writes it: the swap
        # is then no copy, and a head's scales are a row over the slots
        in_specs += [hbm, hbm]
        operands += [k_scale.swapaxes(2, 3), v_scale.swapaxes(2, 3)]
        scratch += [pltpu.VMEM((2, kv, run * bs), jnp.float32),
                    pltpu.VMEM((2, kv, run * bs), jnp.float32)]
    copies = 5 if quantized else 3
    scratch += [pltpu.SemaphoreType.DMA((2, copies) if run == 1
                                        else (2, run, copies)),
                pltpu.VMEM((kv, wide, 1), jnp.float32),
                pltpu.VMEM((kv, wide, 1), jnp.float32),
                pltpu.VMEM((kv, wide, dv), jnp.float32)]

    # no taller than the tile: the walk gives such a group no pair
    group = min(narrow_rows(n_rep, slot_rows), wide)
    shared = dict(pairs=pairs, group=group, scale=scale,
                  quantized=quantized, window=window, key_at=key_at,
                  sink=sink is not None)
    if run == 1:
        kernel = functools.partial(_paged_kernel, **shared)
        scalars = (walk.count, walk.blocks, walk.cols, walk.narrow)
    else:
        # a group that is one packed row's heads names every block of its
        # runs; any other shares its sublanes with its neighbours' heads,
        # and a slot's rows name the same blocks only by the schedule
        kernel = functools.partial(
            _paged_run_kernel, run=run, whole_named=group == n_rep, **shared)
        scalars = (walk.units, walk.blocks, walk.cols, walk.narrow,
                   walk.lens)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars) + 1,
        grid=(tiles,),
        in_specs=in_specs,
        out_specs=head_block(dv),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, kv, wide, dv), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name=("swa_attention" if sliding is not None else "paged_attention"
              if window is None else "eva_attention"),
    )(*scalars, jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out.reshape(tiles, kv, rows, n_rep, dv).swapaxes(1, 2).reshape(
        tiles * rows, n, dv)[:t]


@functools.lru_cache(maxsize=None)
def paged_attention_impl(head_dim: int, block_size: int,
                         force_pallas: Optional[bool] = None,
                         kernel_only: bool = False) -> str:
    """Which implementation :func:`paged_attention` runs for a pool of this
    ``head_dim`` and ``block_size`` on the default backend: ``"pallas"``
    (the compiled TPU kernel), ``"pallas-interpret"`` (the kernel, forced,
    off TPU) or ``"xla"`` (the gather reference).

    The compiled kernel wants pool rows of whole lanes and whole
    128-slot blocks (the shapes ``tests/test_chip_compile.py`` covers). A
    head narrower than 128 lanes is served by it where the family's cache
    kind lays ``128 // head_dim`` K/V heads side by side on a pool row
    (:class:`..inference.paging.StatePoolCache`: :func:`paged_attention`
    then pads each query head into its share of the lanes); a narrow pool
    that is not laid so falls to the reference where it is traced, and
    says so there. A head of whole lanes and such a share (192) is served
    by it from a wide-key pool (:func:`keys_to_lanes`:
    :class:`..inference.paging.WindowPoolCache` with keys wider than the
    values). A
    TPU that falls to the reference for its shapes says so once, here;
    with ``kernel_only`` (a family whose pool only the kernel can serve at
    its size: the reference gathers every row's whole table) it raises
    instead."""
    if force_pallas is False:
        return "xla"
    if not on_tpu():
        return "pallas-interpret" if force_pallas else "xla"
    rest = head_dim % LANES
    if (rest == 0 or LANES % rest == 0) and block_size % 128 == 0:
        return "pallas"
    if force_pallas or kernel_only:
        raise ValueError(
            f"paged shapes (head_dim={head_dim}, block_size={block_size}) "
            "don't tile for the TPU kernel, which this pool is served by "
            "alone (force_pallas, or a kernel-only family); non-tiling "
            "shapes are only valid off the TPU")
    logger.warning(
        "paged_attention: head_dim=%d block_size=%d does not tile for the "
        "TPU kernel (whole 128-slot blocks, and heads of whole lanes or "
        "of a share of 128 lanes laid side by side on a pool row: "
        "inference/paging.StatePoolCache.pack); this pool is served by "
        "the XLA gather reference", head_dim, block_size)
    return "xla"


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    pool_pos: jax.Array, tables: jax.Array,
                    q_pos: jax.Array, layer,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    force_pallas: Optional[bool] = None,
                    combine_axis: Optional[str] = None,
                    window: Optional[tuple] = None,
                    walk: Optional[TileWalk] = None,
                    sliding: Optional[int] = None,
                    sink: Optional[jax.Array] = None,
                    slot_rows: int = 1) -> jax.Array:
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

    ``sliding``: the causal window of a sliding-window layer
    (:class:`..inference.paging.WindowPoolCache`): ``tables`` are then the
    rows' rings (block ``b`` of a sequence in column ``b % ring``, every
    column mapped), ``pool_pos`` the window pool's, and a row attends the
    positions ``q_pos - sliding < p <= q_pos`` and no others: a ring's
    stale rows fall to the mask by their stored positions. The kernel is
    then named ``swa_attention`` in a device trace. Not with ``window``,
    ``combine_axis`` or an int8 pool.

    ``walk``: the kernel's routing of this step (:func:`step_walk`), built
    once for all layers; built here when not given.

    Keys wider than the values: ``k_pool [L, num_blocks, block_size, KV *
    D]``, a position's keys in whole 128-lane chunks
    (:func:`keys_to_lanes`), beside ``v_pool [.., KV, Dv]``; returns ``[T,
    N, Dv]``. A float pool, full or ``sliding``.

    ``sink [N]``: one logit a query head that stands in the softmax's
    denominator and holds no value (``p_j = exp(s_j) / (exp(sink) + sum
    exp(s))``). Not with ``combine_axis``.

    ``slot_rows``: the packed rows a slot holds side by side in the step,
    what :func:`step_walk` was given (a block family's block length): the
    height of the kernel's narrow group in packed rows. The reference
    takes no notice of it.
    """
    t, n, d = q.shape
    if sink is not None and combine_axis is not None:
        raise ValueError("a sink term is not combined across cp ranks")
    wide_keys = k_pool.ndim == 4
    if wide_keys and (window is not None or combine_axis is not None
                      or k_scale is not None):
        raise ValueError("a wide-key pool is a float pool of a full or a "
                         "sliding layer")
    bs, kv = k_pool.shape[2], v_pool.shape[3]
    lanes = d if wide_keys else k_pool.shape[-1]
    if n % kv != 0:
        raise ValueError(f"q heads {n} not a multiple of kv heads {kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    scale_ = (1.0 / math.sqrt(d)) if scale is None else scale
    if lanes != d:
        # ``lanes // d`` K/V heads side by side on a pool row: a query
        # head zero-padded into its own head's share of the lanes meets
        # that head's keys alone, and its share of the output is that
        # head's values; the products of the other shares are with zeros
        pack = lanes // d
        if lanes % d or n % (kv * pack) or k_scale is not None:
            raise ValueError(
                f"a float pool row of {lanes} lanes holds whole heads of "
                f"{d}, and {n} query heads divide over {kv} x {pack} K/V "
                "heads")
        share = (jnp.arange(n) // (n // (kv * pack))) % pack
        wide = (q[:, :, None, :] * jax.nn.one_hot(
            share, pack, dtype=q.dtype)[None, :, :, None]).reshape(
                t, n, lanes)
        out = paged_attention(
            wide, k_pool, v_pool, pool_pos, tables, q_pos, layer,
            scale=scale_, force_pallas=force_pallas,
            combine_axis=combine_axis, window=window, walk=walk,
            sliding=sliding, sink=sink, slot_rows=slot_rows)
        return jnp.take_along_axis(
            out.reshape(t, n, pack, d), share[None, :, None, None],
            axis=2)[:, :, 0]
    if (bs % 128 == 0 and d % 128 and not wide_keys and on_tpu()
            and force_pallas is None):
        logger.warning(
            "paged_attention: a pool row of %d lanes is served by the XLA "
            "gather reference; lay %d K/V heads side by side on a row "
            "(inference/paging.StatePoolCache.pack)", d, max(128 // d, 1))
        force_pallas = False

    if window is not None and (combine_axis is not None
                               or k_scale is not None):
        raise ValueError("a window-summary cache serves neither a cp-"
                         "sharded nor an int8 pool")
    if sliding is not None and (window is not None or k_scale is not None
                                or combine_axis is not None):
        raise ValueError("a sliding-window layer is served from a float "
                         "ring of its own: no window-summary cache, int8 "
                         "pool or cp-sharded pool")
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
                                    window=window, sliding=sliding,
                                    sink=sink)
    return _paged_attention_pallas(q, k_pool, v_pool, pool_pos, tables,
                                   q_pos, layer, k_scale, v_scale, scale_,
                                   interpret=impl == "pallas-interpret",
                                   window=window, walk=walk, sliding=sliding,
                                   sink=sink, slot_rows=slot_rows)
