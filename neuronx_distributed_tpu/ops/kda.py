"""Kimi Delta Attention's recurrence (arXiv:2510.26692): a gated delta rule
whose decay is a vector a head.

A head keeps a state ``S in R^{dk x dv}``, float32, zero before position
0, which a row *reads through its key before it writes it*::

    S'  = exp(g_t)[:, None] * S_{t-1}            g_t in R^dk, g_t <= 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     a rank-1 correction a row
    o_t = S_t^T q_t

``k`` is L2-normalised and ``beta`` in ``(0, 2)`` where negative
eigenvalues are allowed, so ``I - beta k k^T`` never expands. Two forms
of the one recurrence, float32 where a state is carried (``highest``
where a matmul carries one):

* :func:`kda_full` - a whole sequence at positions ``0..S-1``, no cache,
  differentiable (tests, the no-cache forward). Chunks of ``chunk``
  positions; with ``G_t`` the running sum of ``g`` inside a chunk, the
  rows' corrections ``u_t = beta_t (v_t - S'^T k_t)`` solve the unit
  lower-triangular system ``(I + A) U = beta (V - (K exp(G)) S_0)``,
  ``A_ts = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s <
  t`` (the WY / UT transform), and ``o_t = S_0^T (q_t exp(G_t)) + sum_{s
  <= t} u_s sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])``. Every decay formed
  is ``exp`` of a difference ``G_t - G_s <= 0`` with ``s <= t``, never of
  ``-G_s``: a channel that has decayed to nothing inside a chunk gives 0
  and no infinity.
* :func:`kda_packed` - the serving step over :class:`.ssd.StepSegments`:
  the step's ``T`` rows belong to several cache slots (decode rows beside
  prefill chunks), a slot's rows are a segment, and a segment meets its
  own slot's state only. On the TPU a Pallas kernel (``kda_state_update``
  in a device trace) walks the segments by scalar-prefetched slot ids,
  tiles a slot's ``[H, dk, dv]`` state by heads, reads a tile once,
  applies the segment's rows one after another with the head's state in
  registers, and writes it back in place. A row is a latency chain (three
  masked lane reductions on the XLU, the decay, a tree of adds, the rank-1
  add, the read-out) that keeps a third of any unit busy, and a loop of a
  dynamic trip count is a wall the scheduler moves no work across. So the
  one row is iterated two ways, by the segment's row count: a segment of
  one row (every decode row) runs the tile's heads as straight-line code,
  head ``h + 1``'s loads and reductions issuing under head ``h``'s chain
  (117 static bundles a (row, head) where a one-trip loop a head took 179,
  and the grid step falls under its own state's DMA); a segment of
  several (a prefill chunk) takes ``UNROLL`` rows a loop body, the next
  rows' reductions under the current row's chain (125 where it took
  162), and the ``rows % UNROLL`` tail a row a trip. Elsewhere the same
  walk is a gather of the segments' states, a ``lax.scan`` over the rows
  and a scatter, as :mod:`.ssd` has it. A slot whose segment starts at
  position 0 starts from zero inside the step; pad rows and the slots
  without rows are not touched.

The state of the packed form: ``kda [L, J, H, dk, dv]`` float32, a slot's
head one ``[dk, dv]`` tile with ``dv`` on lanes: the decay, the key and
the query of a row are sublane vectors (columns of the transposed row
arrays the wrapper lays out), ``S'^T k`` and the read-out add vregs and
reduce no lane, and ``v`` and the output are lane vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.device import on_tpu
from .pallas_utils import compiler_params as _compiler_params
from .ssd import StepSegments

_HI = jax.lax.Precision.HIGHEST

#: heads of a slot's state a kernel step holds at a time (``[HEADS, dk,
#: dv]`` float32: 512 KiB at 128 x 128, in and out and double-buffered)
HEADS = 8

#: rows of a prefill chunk that one loop body applies, one after another
UNROLL = 4


def kda_step(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array):
    """One row of the recurrence for any leading dimensions: ``state [...,
    dk, dv]``, ``q, k, g [..., dk]``, ``v [..., dv]``, ``beta [...]`` ->
    ``(state, o [..., dv])``."""
    decayed = jnp.exp(g)[..., :, None] * state
    read = jnp.sum(decayed * k[..., :, None], axis=-2)
    state = decayed + k[..., :, None] * (
        beta[..., None] * (v - read))[..., None, :]
    return state, jnp.sum(state * q[..., :, None], axis=-2)


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array) -> jax.Array:
    """The recurrence position by position (what :func:`kda_full` is held
    to): ``q, k, g [B, S, H, dk]``, ``v [B, S, H, dv]``, ``beta [B, S,
    H]`` -> ``o [B, S, H, dv]`` float32."""
    q, k, v, g, beta = (x.astype(jnp.float32).swapaxes(0, 1)
                        for x in (q, k, v, g, beta))

    def row(state, r):
        return kda_step(state, *r)

    zero = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
    return jax.lax.scan(row, zero, (q, k, v, g, beta))[1].swapaxes(0, 1)


def kda_full(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int = 64) -> jax.Array:
    """``q, k, g [B, S, H, dk]`` (``g <= 0``), ``v [B, S, H, dv]``, ``beta
    [B, S, H]`` at positions ``0..S-1`` -> ``o [B, S, H, dv]`` float32."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    size = min(chunk, s)
    pad = -s % size
    # a padded row has beta 0, k 0 and g 0: it corrects and decays nothing
    q, k, v, g, beta = (
        jnp.pad(x.astype(jnp.float32),
                ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        .reshape((bsz, -1, size) + x.shape[2:]).swapaxes(0, 1)
        for x in (q, k, v, g, beta))
    at = jnp.arange(size)
    upto = (at[:, None] >= at[None, :])[None, :, :, None, None]
    before = (at[:, None] > at[None, :])[None, None]

    def step(state, chunk_in):
        qc, kc, vc, gc, bc = chunk_in       # [B, C, H, dk] .. [B, C, H]
        cum = jnp.cumsum(gc, axis=1)
        gap = cum[:, :, None] - cum[:, None, :]           # [B, t, s, H, dk]
        decay = jnp.where(upto, jnp.exp(jnp.where(upto, gap, 0.0)), 0.0)
        kk = jnp.einsum("bthc,btshc,bshc->bhts", kc, decay, kc,
                        precision=_HI)
        qk = jnp.einsum("bthc,btshc,bshc->bhts", qc, decay, kc,
                        precision=_HI)
        into = jnp.exp(cum)                               # [B, C, H, dk]
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bthc,bhcd->bthd", kc * into, state, precision=_HI))
        lower = jnp.where(before, bc.swapaxes(1, 2)[..., None] * kk, 0.0)
        u = jax.scipy.linalg.solve_triangular(
            lower + jnp.eye(size), rhs.swapaxes(1, 2), lower=True,
            unit_diagonal=True)                           # [B, H, C, dv]
        o = jnp.einsum("bthc,bhcd->bthd", qc * into, state, precision=_HI) \
            + jnp.einsum("bhts,bhsd->bthd", qk, u, precision=_HI)
        to_end = jnp.exp(cum[:, -1:] - cum)               # [B, C, H, dk]
        state = (jnp.exp(cum[:, -1])[..., None] * state
                 + jnp.einsum("bshc,bhsd->bhcd", kc * to_end, u,
                              precision=_HI))
        return state, o

    _, o = jax.lax.scan(step, jnp.zeros((bsz, h, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o.swapaxes(0, 1).reshape(bsz, -1, h, dv)[:, :s]


def _kda_kernel(layer_ref, count_ref, slot_ref, start_ref, rows_ref,
                zero_ref, dt_ref, kt_ref, qt_ref, bv_ref, bb_ref, s_in_ref,
                o_ref, s_out_ref):
    """Grid step ``(j, n)``: head tile ``j`` of segment ``n``'s slot.
    ``dt_ref, kt_ref, qt_ref [HEADS, dk, T]`` the rows' decays ``exp(g)``,
    keys and queries by column; ``bv_ref, bb_ref [T, HEADS * dv]`` their
    ``beta v`` and ``beta`` by channel; ``s_in_ref`` and ``s_out_ref [1,
    1, HEADS, dk, dv]`` the tile of the slot's state of this layer (one
    array, aliased); ``o_ref [T, HEADS * dv]`` every row's read-out,
    resident while the tile's segments pass."""
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    heads, dk, dv = s_out_ref.shape[2:]
    steps = dt_ref.shape[-1]

    @pl.when(n == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # a step without a real row still writes its (one) block back
    @pl.when((n == 0) & (count_ref[0] == 0))
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(n < count_ref[0])
    def _():
        start, rows = start_ref[n], rows_ref[n]
        fresh = zero_ref[n] == 1
        column = jax.lax.broadcasted_iota(jnp.int32, (dk, steps), 1)
        sublane = jax.lax.broadcasted_iota(jnp.int32, (8, dv), 0)

        def row(r, s, h):
            t = start + r
            mine = column == t
            lanes = pl.ds(h * dv, dv)

            def col(ref):
                return jnp.sum(jnp.where(mine, ref[h], 0.0), axis=1,
                               keepdims=True)                     # [dk, 1]

            # a row of a [T, lanes] array by its aligned group of eight
            # (a load or store at a row the compiler cannot place is not
            # built)
            group = pl.ds(pl.multiple_of(t // 8 * 8, 8), 8)
            own = sublane == t % 8

            def lane_row(ref):
                return jnp.sum(jnp.where(own, ref[group, lanes], 0.0),
                               axis=0, keepdims=True)             # [1, dv]

            k_t = col(kt_ref)
            s = col(dt_ref) * s
            read = jnp.sum(s * k_t, axis=0, keepdims=True)        # [1, dv]
            s = s + k_t * (lane_row(bv_ref) - lane_row(bb_ref) * read)
            o_ref[group, lanes] = jnp.where(
                own, jnp.sum(s * col(qt_ref), axis=0, keepdims=True),
                o_ref[group, lanes])
            return s

        def state(h):
            return jnp.where(fresh, 0.0, s_in_ref[0, 0, h])

        # the one row iterated two ways (the module's docstring): a
        # decode row's heads in straight-line code, a chunk's rows
        # ``UNROLL`` a loop body and its tail a row a trip
        @pl.when(rows == 1)
        def _():
            for h in range(heads):
                s_out_ref[0, 0, h] = row(0, state(h), h)

        @pl.when(rows != 1)
        def _():
            for h in range(heads):
                def body(i, s, h=h):
                    for r in range(UNROLL):
                        s = row(i * UNROLL + r, s, h)
                    return s

                s = jax.lax.fori_loop(0, rows // UNROLL, body, state(h))
                s_out_ref[0, 0, h] = jax.lax.fori_loop(
                    rows // UNROLL * UNROLL, rows,
                    functools.partial(row, h=h), s)


def _kda_update_pallas(dt, kt, qt, bv, bb, kda, layer, seg: StepSegments,
                       interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, dk, t = dt.shape
    dv = kda.shape[-1]
    tile = HEADS if h % HEADS == 0 else 1
    segments = seg.slot.shape[0]

    def by_column():
        return pl.BlockSpec((tile, dk, t), lambda j, n, *_: (j, 0, 0))

    def by_row():
        return pl.BlockSpec((t, tile * dv), lambda j, n, *_: (0, j))

    def of_slot():
        return pl.BlockSpec(
            (1, 1, tile, dk, dv),
            lambda j, n, layer, count, slot, *_: (layer[0], slot[n], j, 0,
                                                  0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(h // tile, segments),
        in_specs=[by_column(), by_column(), by_column(), by_row(), by_row(),
                  of_slot()],
        out_specs=[by_row(), of_slot()])
    o, kda = pl.pallas_call(
        _kda_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(kda.shape, kda.dtype)],
        input_output_aliases={11: 1},
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="kda_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), seg.count, seg.slot,
      seg.start, seg.rows, seg.zero, dt, kt, qt, bv, bb, kda)
    return o, kda


def _kda_update_xla(q, k, v, g, beta, kda, layer, seg: StepSegments):
    """The kernel's walk in XLA: gather the segments' states, apply the
    rows in order, scatter the states back."""
    segments, slots = seg.slot.shape[0], kda.shape[1]
    states = jax.lax.dynamic_index_in_dim(kda, layer, 0, False)[
        jnp.minimum(seg.scatter_slot, slots - 1)]          # [K, H, dk, dv]
    states = jnp.where((seg.zero == 1)[:, None, None, None], 0.0, states)

    def row(states, r):
        *r, own = r
        s, o = kda_step(states[jnp.minimum(own, segments - 1)], *r)
        return (states.at[own].set(s, mode="drop"),
                jnp.where(own < segments, o, 0.0))

    states, o = jax.lax.scan(row, states, (q, k, v, g, beta, seg.segment))
    return o, kda.at[layer, seg.scatter_slot].set(states, mode="drop")


def kda_packed_impl(dk: int, dv: int, force_pallas=None) -> str:
    """What :func:`kda_packed` runs for a head's state ``[dk, dv]`` on the
    default backend: ``"pallas"`` (the compiled kernel),
    ``"pallas-interpret"`` (the kernel, forced, off the TPU) or ``"xla"``
    (the gather, scan and scatter). The kernel wants ``dv`` on whole lanes
    and ``dk`` on whole sublanes."""
    if force_pallas is False:
        return "xla"
    tiles = dv % 128 == 0 and dk % 8 == 0
    if not on_tpu():
        return "pallas-interpret" if force_pallas and tiles else "xla"
    if tiles:
        return "pallas"
    if force_pallas:
        raise ValueError(f"a head's state of [{dk}, {dv}] does not tile "
                         "for the kda kernel")
    return "xla"


def kda_packed(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, kda: jax.Array, layer, seg: StepSegments,
               force_pallas=None):
    """One packed step of one layer. ``q, k, g [T, H, dk]`` (``g <= 0``),
    ``v [T, H, dv]``, ``beta [T, H]``; ``kda [L, J, H, dk, dv]`` float32,
    every layer's per-slot states, read and written at ``layer``; ``seg``
    the step's segments. Returns ``(o [T, H, dv] float32, kda)``; a pad
    row's ``o`` is zero."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    impl = kda_packed_impl(dk, dv, force_pallas)
    if impl == "xla":
        o, kda = _kda_update_xla(q, k, v, g, beta, kda, layer, seg)
    else:
        # the kernel takes a row by its aligned group of eight
        pad = -t % 8

        def by_column(x):
            return jnp.pad(x, ((0, pad), (0, 0), (0, 0))).transpose(1, 2, 0)

        def by_row(x):
            return jnp.pad(x.reshape(t, h * dv), ((0, pad), (0, 0)))

        o, kda = _kda_update_pallas(
            by_column(jnp.exp(g)), by_column(k), by_column(q),
            by_row(beta[:, :, None] * v), by_row(jnp.repeat(beta, dv, axis=1)),
            kda, layer, seg, interpret=impl == "pallas-interpret")
        o = o[:t].reshape(t, h, dv)
    real = (seg.segment < seg.slot.shape[0])[:, None, None]
    return jnp.where(real, o, 0.0), kda
