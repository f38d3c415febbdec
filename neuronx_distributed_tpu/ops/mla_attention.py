"""Latent (MLA) attention over the paged pool.

Multi-head latent attention caches, a position a layer, one latent
``c_kv`` of ``rank`` values and one rotary key, shared by every query
head, and reads keys and values out of it through ``kv_b``. Absorbed into
the query and the output (:func:`absorb_queries`, :func:`expand_values`),
``kv_b`` never touches the cache: head ``i``'s query becomes ``[W_UK,i^T
q_i^nope, q_i^rope]``, scored against the cached row ``[c_kv, k^rope]``
itself, and its value is the first ``rank`` lanes of the same row. So the
pool is one stack of rows ``[L, num_blocks, block_size, row]``
(:class:`..inference.paging.LatentPagedCache`; ``row`` whole lanes, the
lanes past ``rank + rope`` zero in rows and queries alike) and a fetched
block serves the keys, the values and all the heads.

Two implementations behind one signature, as :mod:`.paged_attention` has:

* :func:`_mla_attention_xla`: the gather reference, every row's whole
  table; CPU tests only.
* :func:`_mla_attention_pallas`: the ``mla_paged_attention`` kernel.
  :mod:`.paged_attention`'s tile walk unchanged (:func:`tile_walk`,
  :func:`tile_pairs`, ``TileWalk.narrow``) with one K/V "head" and the
  query heads as its ``n_rep``, padded to whole sublanes
  (:func:`stacked_heads`: 20 heads ride as 24) so that a decode row's
  heads are exactly one narrow group of the tile: a tile is ``tile_rows``
  packed rows (8) times the stacked heads (192 MXU rows at 24 stacked
  heads, 512 at 64). The kernel's
  unit of work is a *run* of pairs (:func:`.paged_attention.pair_runs`,
  the one cut of a walk into units, which the paged kernel takes too): up
  to 8 pairs that one row alone names (a decode row's own blocks, against
  its stacked heads), or as many pairs that rows of the tile share (a
  chunk's blocks) as the scores of a *slab* of the tile allow
  (:func:`_unit_lengths`, from the shapes alone: the walk, the kernel and
  the host's counts call the one rule). A tile of 192 stacked rows is
  one slab and its shared unit 4 blocks against the whole tile. A tile of
  512 (64 heads), whose float32 scores would leave a unit one block, is
  scored in slabs of 2 packed rows (128 stacked rows) over a unit of 4
  blocks: the statistics of a step are ``[128, 1]`` beside scores ``[128,
  512]``, whether a packed row names a block is one scalar from its
  table row (the stacked heads of a packed row share it), and a slab
  that names none of the unit's blocks is skipped.
  A unit's blocks are copied side by side into one half of a ring while
  the unit before it is computed, scored in one product ``[rows, row] x [row, blocks x
  block_size]`` from the stored operands into float32 and taken through
  one step of the online softmax, and ``p x v`` is one product against
  the blocks' first ``rank`` lanes with ``p``'s two bf16 parts stacked on
  rows (:func:`.paged_attention._p_times_v_stacked`). Prefill chunks,
  decode rows and pad rows take the one path.

On a TPU there is no silent fall to the reference: shapes the kernel
cannot tile raise (:func:`mla_attention_impl`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.kv_cache import PAD_POSITION
from .paged_attention import (RunWalk, TileWalk, host_pairs,
                              paged_attention_impl, tile_rows, tile_walk,
                              unit_blocks)
from .paged_attention import _p_times_v_stacked as _p_times_v
from .paged_attention import block_fetches as _block_fetches
from .paged_attention import run_walk as _run_walk
from .pallas_utils import compiler_params as _compiler_params

LANES = 128
#: stacked rows of a slab of a tile too tall to be scored whole
#: (:func:`_unit_lengths`): the MXU's rows
SLAB_ROWS = 128


def row_width(rank: int, rope: int) -> int:
    """Lanes of a pool row: the latent and the rotary key on whole lanes.
    The chip tiles the minor dimension by 128 in HBM whatever is declared,
    so 576 values occupy 640 lanes either way; declaring them makes the
    idle ones zeros the kernel can multiply through."""
    return -(-(rank + rope) // LANES) * LANES


def stacked_heads(num_heads: int) -> int:
    """Query heads of a packed row as the walk stacks them: whole
    sublanes (20 ride as 24), so each row's heads are one narrow group of
    its tile (:func:`.paged_attention.narrow_rows` of whole sublanes is
    their own number)."""
    return -(-num_heads // 8) * 8


def mla_attention_impl(row: int, rank: int, block_size: int,
                       force_pallas: Optional[bool] = None) -> str:
    """``"pallas"``, ``"pallas-interpret"`` or ``"xla"``, as
    :func:`.paged_attention.paged_attention_impl` answers for a pool row
    of ``row`` lanes; on a TPU the answer is the kernel or an error."""
    impl = paged_attention_impl(row, block_size, force_pallas,
                                kernel_only=True)
    if impl == "pallas" and row % LANES:
        # the paged kernel also serves heads that share a row's lanes; a
        # latent row is one row for all heads
        raise ValueError(f"latent shapes (row={row}, block_size="
                         f"{block_size}) don't tile for the TPU kernel: a "
                         "latent row is whole lanes")
    if impl == "pallas" and rank % LANES:
        raise ValueError(f"mla_paged_attention: a latent of {rank} values "
                         f"is no whole lanes; the kernel slices the value "
                         f"out of the row at lane {rank}")
    return impl


def step_walk(tables, q_pos, block_size: int, num_blocks: int, row: int,
              rank: int, num_heads: int, itemsize: int,
              force_pallas: Optional[bool] = None) -> Optional[RunWalk]:
    """The kernel's walk of one packed step (once for all layers) over a
    pool of ``itemsize`` bytes a value, or ``None`` where the XLA
    reference serves."""
    if mla_attention_impl(row, rank, block_size, force_pallas) == "xla":
        return None
    heads = stacked_heads(num_heads)
    return run_walk(tile_walk(tables, q_pos, block_size, num_blocks, heads),
                    num_blocks, heads, row, block_size, itemsize)


def _unit_lengths(heads: int, wide: int, row: int, block_size: int,
                  itemsize: int):
    """``(run, whole_run, slab)``: blocks of a run of one row's pairs,
    blocks of a unit of pairs that a tile of ``wide`` stacked rows shares,
    and the stacked rows such a unit is scored at a time
    (:func:`.paged_attention.unit_blocks` of a block of latent rows). A
    tile whose scores leave room for two blocks or more is one slab (192
    rows of 24 stacked heads: 4 blocks). A taller one, which would take
    its shared pairs a block a unit, is scored in slabs of whole packed
    rows, halved until the slab is :data:`SLAB_ROWS` stacked rows or one
    packed row's heads, and a unit is as many blocks as a slab's scores
    allow (512 rows of 64 heads: slabs of 128, 4 blocks)."""
    block_bytes = block_size * row * itemsize
    slab, whole_run = wide, unit_blocks(wide, block_bytes, block_size)
    if whole_run == 1:
        while slab > max(SLAB_ROWS, heads) and slab % (2 * heads) == 0:
            slab //= 2
        whole_run = unit_blocks(slab, block_bytes, block_size)
    return unit_blocks(heads, block_bytes, block_size), whole_run, slab


def run_walk(walk: TileWalk, num_blocks: int, heads: int, row: int,
             block_size: int, itemsize: int) -> RunWalk:
    """:func:`.paged_attention.run_walk` of a step's walk at the lengths
    the kernel takes for these shapes."""
    run, whole_run, _ = _unit_lengths(heads, walk.served.shape[1], row,
                                      block_size, itemsize)
    # the kernel reads a shared unit's pairs to its full length, a run's
    # as far as the run goes
    return _run_walk(walk, num_blocks, heads, run, whole_run, room=whole_run)


def block_fetches(served, num_heads: int, row: int, block_size: int,
                  itemsize: int, pairs=None) -> np.ndarray:
    """:func:`.paged_attention.block_fetches` of one layer of a packed
    step, ``[in_run, alone, whole]``, at the run the kernel takes for
    these shapes (``nxd_mla_block_fetches_total``; ``pairs``:
    :func:`.paged_attention.host_pairs` of the same step at
    :func:`stacked_heads`, where the caller has them)."""
    heads = stacked_heads(num_heads)
    run, _, _ = _unit_lengths(heads, tile_rows(heads, len(served)) * heads,
                              row, block_size, itemsize)
    return _block_fetches(served, heads, run, pairs)


def shared_blocks(served, num_heads: int, row: int, block_size: int,
                  itemsize: int, pairs=None) -> np.ndarray:
    """The pairs that rows of a tile share (``block_fetches``' ``whole``)
    of one layer of a packed step by the unit they rode, ``[in_unit,
    alone]``: with one or more other blocks in one unit over the tile (one
    ring half of copies, one step of the online softmax a slab), or a
    unit by itself: every one where a unit is one block, else a tile's
    last where its count leaves one over. What :func:`run_walk` makes of
    the step, counted on the host at the unit the kernel takes for these
    shapes (``nxd_mla_shared_blocks_total``; ``pairs``:
    :func:`.paged_attention.host_pairs` of the same step at
    :func:`stacked_heads`)."""
    heads = stacked_heads(num_heads)
    wide = tile_rows(heads, len(served)) * heads
    _, whole_run, _ = _unit_lengths(heads, wide, row, block_size, itemsize)
    *_, start, group = pairs or host_pairs(served, heads)
    # a tile's shared pairs are cut into units, the last shorter
    many = np.bincount((group - start)[start < 0] // wide)
    alone = many if whole_run == 1 else many % whole_run == 1
    return np.array([many.sum() - alone.sum(), alone.sum()], np.int64)


def absorb_queries(q_nope, q_rope, k_up, row: int):
    """``q_nope [..., N, nope]``, ``q_rope [..., N, rope]`` (rotated),
    ``k_up [N, nope, rank]`` (``W_UK``) -> the queries as pool rows
    ``[..., N, row]``: ``[W_UK^T q_nope, q_rope, 0...]``."""
    q_lat = jnp.einsum("...nd,ndr->...nr", q_nope,
                       k_up.astype(q_nope.dtype))
    pad = row - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (pad,), q_lat.dtype)],
        axis=-1)


def expand_values(ctx, v_up):
    """``ctx [..., N, rank]`` (the probabilities times the latents),
    ``v_up [N, rank, v]`` (``W_UV^T``) -> the heads' outputs ``[..., N,
    v]``."""
    return jnp.einsum("...nr,nrv->...nv", ctx, v_up.astype(ctx.dtype))


def _mla_attention_xla(q, pool, pool_pos, tables, q_pos, layer, rank, scale):
    nb = pool.shape[1]
    safe = jnp.clip(tables, 0, nb - 1)
    rows = pool[layer, safe]                         # [T, maxb, bs, row]
    pg = jnp.where(tables[:, :, None] >= 0, pool_pos[safe], PAD_POSITION)
    t = q.shape[0]
    rows = rows.reshape(t, -1, rows.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum("tnw,tkw->tnk", q.astype(jnp.float32), rows) * scale
    mask = q_pos[:, None] >= pg.reshape(t, -1)
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tnk,tkr->tnr", probs, rows[..., :rank])
    # a pad row attends nothing: zero, as the kernel leaves it
    live = jnp.any(mask, axis=-1)[:, None, None]
    return jnp.where(live, out, 0.0).astype(q.dtype)


def _mla_kernel(units_ref, blocks_ref, cols_ref, narrow_ref, lens_ref,
                layer_ref, served_ref, qpos_ref, q_ref, pool_hbm, pos_hbm,
                o_ref, row_buf, pos_buf, sems, m_ref, l_ref, acc_ref, *,
                pairs: int, group: int, run: int, whole_run: int, slab: int,
                scale: float, rank: int):
    """One tile of packed rows (every row's heads stacked) against the
    pool blocks its rows attend, a unit of the walk at a time
    (:func:`.paged_attention.pair_runs`): the unit's blocks, which are
    the keys whole and
    the values in their first ``rank`` lanes, are copied side by side into
    one half of the ring while the unit before it is computed from the
    other, and are one step of the online softmax
    (:func:`.paged_attention._paged_kernel`'s, over ``blocks x
    block_size`` positions) of the rows it serves: one group's for a run
    of one row's pairs, the tile's for pairs that its rows share (the
    tile whole, or ``slab`` stacked rows of it at a time where it is
    taller: ``served_ref`` is then the packed rows' table rows in SMEM),
    each block under the mask of the rows that name it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = pl.program_id(0)
    units = units_ref[tile]
    layer = layer_ref[0]
    base = tile * pairs
    bs = pool_hbm.shape[2]
    wide = q_ref.shape[0]
    operand = (jnp.bfloat16 if q_ref.dtype == jnp.bfloat16
               and row_buf.dtype != jnp.float32 else jnp.float32)

    def copies(first, side, then):
        # the copies of the unit that starts at pair ``first``
        n = lens_ref[base + first]
        for k in range(run):
            @pl.when(k < n)
            def _block():
                b = blocks_ref[base + first + k]
                at = pl.ds(k * bs, bs)
                then(pltpu.make_async_copy(
                    pool_hbm.at[layer, b], row_buf.at[side, at],
                    sems.at[side, k, 0]))
                then(pltpu.make_async_copy(
                    pos_hbm.at[b], pos_buf.at[side, :, at],
                    sems.at[side, k, 1]))

    @pl.when(tile == 0)
    def _clean():
        # what a short run leaves of the ring is multiplied by p = 0
        row_buf[...] = jnp.zeros_like(row_buf)

    @pl.when(units > 0)
    def _first():
        copies(0, 0, lambda c: c.start())

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(rows, q_pos_ok, keys, values):
        # one step of the online softmax of ``rows`` over ``keys``
        s = jax.lax.dot_general(
            q_ref[rows, :].astype(operand), keys.astype(operand),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(q_pos_ok, s, -jnp.inf)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(q_pos_ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        m_ref[rows, :] = m_new
        l_ref[rows, :] = l_ref[rows, :] * corr + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * corr + _p_times_v(
            p, values.astype(operand))

    def unit(u, first):
        side = u % 2
        n = lens_ref[base + first]

        @pl.when(u + 1 < units)
        def _next():
            copies(first + n, 1 - side, lambda c: c.start())

        copies(first, side, lambda c: c.wait())
        start = narrow_ref[base + first]

        @pl.when(start >= 0)
        def _run():
            # one row's blocks: the group's rows name every one of them
            # (a narrow pair has no other namer), and a short run's
            # missing blocks are dead positions
            rows = pl.ds(pl.multiple_of(start, 8), group)
            pos = pos_buf[side]                         # [1, run * bs]
            lane = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 1)
            ok = (pos <= qpos_ref[rows, :]) & (lane < n * bs)
            attend(rows, ok, row_buf[side], row_buf[side, :, :rank])

        span = whole_run * bs

        def whole():
            served = served_ref[...]                    # [wide, maxb]
            column = jax.lax.broadcasted_iota(jnp.int32, served.shape, 1)
            ok = []
            for k in range(whole_run):
                block = blocks_ref[base + first + k]
                col = cols_ref[base + first + k]
                named = jnp.max(
                    jnp.where((column == col) & (served == block), 1, 0),
                    axis=1, keepdims=True) > 0          # [wide, 1]
                ok.append((pos_buf[side, :, k * bs:(k + 1) * bs]
                           <= qpos_ref[...]) & named & (k < n))
            attend(slice(None), jnp.concatenate(ok, axis=1),
                   row_buf[side, :span], row_buf[side, :span, :rank])

        def slabs():
            # a tall tile, a slab of whole packed rows at a time. The
            # stacked heads of a packed row share its table row, so
            # whether it names a block is one scalar (``served_ref``: the
            # tile's packed rows, in SMEM), and a slab none of whose rows
            # names a block of the unit (rows past a chunk's end, decode
            # rows beside it) is skipped
            each = slab // group

            def one(s, carry):
                named = [[(served_ref[s * each + r,
                                      cols_ref[base + first + k]]
                           == blocks_ref[base + first + k]) & (k < n)
                          for r in range(each)] for k in range(whole_run)]

                @pl.when(functools.reduce(
                    jnp.logical_or, (x for of in named for x in of)))
                def _attend():
                    rows = pl.ds(pl.multiple_of(s * slab, slab), slab)
                    q_pos = qpos_ref[rows, :]               # [slab, 1]
                    packed = jax.lax.broadcasted_iota(
                        jnp.int32, q_pos.shape, 0) // group
                    ok = []
                    for k, of in enumerate(named):
                        live = jnp.zeros_like(q_pos)
                        for r, mine in enumerate(of):
                            live = jnp.where(packed == r,
                                             mine.astype(jnp.int32), live)
                        ok.append((pos_buf[side, :, k * bs:(k + 1) * bs]
                                   <= q_pos) & (live > 0))
                    attend(rows, jnp.concatenate(ok, axis=1),
                           row_buf[side, :span], row_buf[side, :span, :rank])

                return carry

            jax.lax.fori_loop(0, wide // slab, one, 0)

        pl.when(start < 0)(whole if slab == wide else slabs)
        return first + n

    jax.lax.fori_loop(0, units, unit, 0)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def _mla_attention_pallas(q, pool, pool_pos, tables, q_pos, layer, rank,
                          scale, interpret=False, walk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, n, row = q.shape
    _, nb, bs, _ = pool.shape
    maxb = tables.shape[1]
    heads = stacked_heads(n)
    if walk is None:
        walk = run_walk(tile_walk(tables, q_pos, bs, nb, heads), nb, heads,
                        row, bs, pool.dtype.itemsize)
    tiles, wide, _ = walk.served.shape          # wide = rows * heads
    rows = wide // heads
    pairs = rows * maxb
    run, whole_run, slab = _unit_lengths(heads, wide, row, bs,
                                         pool.dtype.itemsize)

    def row_block(last):
        return pl.BlockSpec((None, wide, last), lambda i, *_: (i, 0, 0))

    served, served_block = walk.served, row_block(maxb)
    if slab < wide:
        # slabs ask a packed row's table row for a scalar: every
        # ``heads``-th of the tile's stacked rows, in SMEM
        served = served[:, ::heads]
        served_block = pl.BlockSpec((None, rows, maxb),
                                    lambda i, *_: (i, 0, 0),
                                    memory_space=pltpu.SMEM)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # a tile's queries, each row's heads stacked (and padded with heads
    # of zeros, whose outputs are dropped): [tiles, rows * heads, row]
    q_tiles = jnp.pad(q, ((0, tiles * rows - t), (0, heads - n), (0, 0))
                      ).reshape(tiles, wide, row)
    out = pl.pallas_call(
        functools.partial(_mla_kernel, pairs=pairs, group=heads, run=run,
                          whole_run=whole_run, slab=slab, scale=scale,
                          rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(tiles,),
            in_specs=[served_block, row_block(1), row_block(row), hbm, hbm],
            out_specs=row_block(rank),
            scratch_shapes=[
                pltpu.VMEM((2, run * bs, row), pool.dtype),
                pltpu.VMEM((2, 1, run * bs), jnp.int32),
                pltpu.SemaphoreType.DMA((2, run, 2)),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((wide, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles, wide, rank), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="mla_paged_attention",
    )(walk.units, walk.blocks, walk.cols, walk.narrow, walk.lens,
      jnp.asarray(layer, jnp.int32).reshape(1), served, walk.q_pos,
      q_tiles, pool, pool_pos.reshape(nb, 1, bs))
    return out.reshape(tiles * rows, heads, rank)[:t, :n]


def mla_paged_attention(q: jax.Array, pool: jax.Array, pool_pos: jax.Array,
                        tables: jax.Array, q_pos: jax.Array, layer,
                        rank: int, scale: float,
                        force_pallas: Optional[bool] = None,
                        walk: Optional[RunWalk] = None) -> jax.Array:
    """``q [T, N, row]`` absorbed queries (:func:`absorb_queries`) against
    layer ``layer`` of ``pool [L, num_blocks, block_size, row]`` through
    ``tables [T, max_blocks_per_seq]`` (-1 unmapped), ``pool_pos
    [num_blocks, block_size]`` and ``q_pos [T]`` as
    :func:`.paged_attention.paged_attention` takes them. Returns the
    probabilities times the latents, ``[T, N, rank]``
    (:func:`expand_values` makes the heads' outputs of it); zero for a pad
    row."""
    row, bs = q.shape[-1], pool.shape[2]
    impl = mla_attention_impl(row, rank, bs, force_pallas)
    if impl == "xla":
        return _mla_attention_xla(q, pool, pool_pos, tables, q_pos, layer,
                                  rank, scale)
    return _mla_attention_pallas(q, pool, pool_pos, tables, q_pos, layer,
                                 rank, scale,
                                 interpret=impl == "pallas-interpret",
                                 walk=walk)
