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
  packed rows (8) times the stacked heads (192 MXU rows), a pair's block
  is copied once into one of two VMEM buffers while the block before it
  is computed, scores are ``[rows, row] x [row, block_size]`` from the
  stored operands into float32, and ``p x v`` runs against the block's
  first ``rank`` lanes with ``p`` float32 (:func:`_p_times_v`). Prefill
  chunks, decode rows and pad rows take the one path.

On a TPU there is no silent fall to the reference: shapes the kernel
cannot tile raise (:func:`mla_attention_impl`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION
from .paged_attention import (TileWalk, _p_times_v, narrow_rows,
                              paged_attention_impl, tile_walk)
from .pallas_utils import compiler_params as _compiler_params

LANES = 128


def row_width(rank: int, rope: int) -> int:
    """Lanes of a pool row: the latent and the rotary key on whole lanes.
    The chip tiles the minor dimension by 128 in HBM whatever is declared,
    so 576 values occupy 640 lanes either way; declaring them makes the
    idle ones zeros the kernel can multiply through."""
    return -(-(rank + rope) // LANES) * LANES


def stacked_heads(num_heads: int) -> int:
    """Query heads of a packed row as the walk stacks them: whole
    sublanes, so each row's heads are one narrow group of its tile."""
    return narrow_rows(num_heads)


def mla_attention_impl(row: int, rank: int, block_size: int,
                       force_pallas: Optional[bool] = None) -> str:
    """``"pallas"``, ``"pallas-interpret"`` or ``"xla"``, as
    :func:`.paged_attention.paged_attention_impl` answers for a pool row
    of ``row`` lanes; on a TPU the answer is the kernel or an error."""
    impl = paged_attention_impl(row, block_size, force_pallas,
                                kernel_only=True)
    if impl == "pallas" and rank % LANES:
        raise ValueError(f"mla_paged_attention: a latent of {rank} values "
                         f"is no whole lanes; the kernel slices the value "
                         f"out of the row at lane {rank}")
    return impl


def step_walk(tables, q_pos, block_size: int, num_blocks: int, row: int,
              rank: int, num_heads: int,
              force_pallas: Optional[bool] = None) -> Optional[TileWalk]:
    """The kernel's walk of one packed step (once for all layers), or
    ``None`` where the XLA reference serves."""
    if mla_attention_impl(row, rank, block_size, force_pallas) == "xla":
        return None
    return tile_walk(tables, q_pos, block_size, num_blocks,
                     stacked_heads(num_heads))


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


def _mla_kernel(count_ref, blocks_ref, cols_ref, narrow_ref, layer_ref,
                served_ref, qpos_ref, q_ref, pool_hbm, pos_hbm, o_ref,
                row_buf, pos_buf, sems, m_ref, l_ref, acc_ref, *,
                pairs: int, group: int, scale: float, rank: int):
    """One tile of packed rows (every row's heads stacked) against the
    pool blocks its rows attend: :func:`.paged_attention._paged_kernel`'s
    loop over the tile's pairs with one buffer a block, which is the keys
    whole and the values in its first ``rank`` lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = pl.program_id(0)
    count = count_ref[tile]
    layer = layer_ref[0]
    operand = (jnp.bfloat16 if q_ref.dtype == jnp.bfloat16
               and row_buf.dtype != jnp.float32 else jnp.float32)

    def copies(j, slot):
        b = blocks_ref[tile * pairs + j]
        moves = [(pool_hbm.at[layer, b], row_buf), (pos_hbm.at[b], pos_buf)]
        return [pltpu.make_async_copy(src, buf.at[slot], sems.at[slot, i])
                for i, (src, buf) in enumerate(moves)]

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

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
        pos = pos_buf[slot]                             # [1, bs]
        keys = row_buf[slot].astype(operand)            # [bs, row]
        values = row_buf[slot, :, :rank].astype(operand)

        def attend(rows):
            served = served_ref[rows, :]                # [rows', maxb]
            column = jax.lax.broadcasted_iota(jnp.int32, served.shape, 1)
            named = jnp.max(
                jnp.where((column == col) & (served == block), 1, 0),
                axis=1, keepdims=True) > 0              # [rows', 1]
            ok = (pos <= qpos_ref[rows, :]) & named     # [rows', bs]
            s = jax.lax.dot_general(
                q_ref[rows, :].astype(operand), keys,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, -jnp.inf)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
            corr = jnp.where(jnp.isfinite(m_prev),
                             jnp.exp(m_prev - m_safe), 0.0)
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_ref[rows, :] * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[rows, :] = acc_ref[rows, :] * corr + _p_times_v(p, values)

        start = narrow_ref[tile * pairs + j]

        @pl.when(start >= 0)
        def _narrow():
            attend(pl.ds(pl.multiple_of(start, 8), group))

        @pl.when(start < 0)
        def _whole():
            attend(slice(None))
        return carry

    jax.lax.fori_loop(0, count, pair, None)
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
        walk = tile_walk(tables, q_pos, bs, nb, heads)
    tiles, wide, _ = walk.served.shape          # wide = rows * heads
    rows = wide // heads
    pairs = rows * maxb

    def row_block(last):
        return pl.BlockSpec((None, wide, last), lambda i, *_: (i, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # a tile's queries, each row's heads stacked (and padded with heads
    # of zeros, whose outputs are dropped): [tiles, rows * heads, row]
    q_tiles = jnp.pad(q, ((0, tiles * rows - t), (0, heads - n), (0, 0))
                      ).reshape(tiles, wide, row)
    out = pl.pallas_call(
        functools.partial(_mla_kernel, pairs=pairs, group=heads,
                          scale=scale, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles,),
            in_specs=[row_block(maxb), row_block(1), row_block(row), hbm,
                      hbm],
            out_specs=row_block(rank),
            scratch_shapes=[
                pltpu.VMEM((2, bs, row), pool.dtype),
                pltpu.VMEM((2, 1, bs), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((wide, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles, wide, rank), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="mla_paged_attention",
    )(walk.count, walk.blocks, walk.cols, walk.narrow,
      jnp.asarray(layer, jnp.int32).reshape(1), walk.served, walk.q_pos,
      q_tiles, pool, pool_pos.reshape(nb, 1, bs))
    return out.reshape(tiles * rows, heads, rank)[:t, :n]


def mla_paged_attention(q: jax.Array, pool: jax.Array, pool_pos: jax.Array,
                        tables: jax.Array, q_pos: jax.Array, layer,
                        rank: int, scale: float,
                        force_pallas: Optional[bool] = None,
                        walk: Optional[TileWalk] = None) -> jax.Array:
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
