"""Mamba-2's selective state-space scan (SSD) and its causal convolution.

A head ``h`` of width ``P`` keeps a state ``S[h] in R^{P x N}`` (``N`` =
``d_state``) whose decay is a function of the row::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

with ``A < 0``, ``dt > 0`` (after its softplus) and ``B_t, C_t in R^N``
shared by the heads of a *group*: ``G`` groups of ``H / G`` consecutive
heads, head ``h`` reading ``B_t[h // (H / G)]`` (``b, c [.., N]`` is one
group, ``[.., G, N]`` several). Two forms of the one recurrence, both in
float32 (``highest`` where a matmul carries a state):

* :func:`ssd_full` - a whole sequence at positions ``0..S-1``, no cache:
  chunks of ``chunk`` positions, within a chunk the masked ``[C, C]``
  product ``C_t . B_s`` shared by the heads times the heads' decays
  ``exp(cum_t - cum_s)``, between chunks the carried state.
  Differentiable (tests, the no-cache forward).
* :func:`ssd_packed` - the serving step: its ``T`` rows belong to several
  cache slots (decode rows beside prefill chunks); the rows of a slot are
  a *segment* (:func:`step_segments`), and a segment meets its own slot's
  state only: there is no ``T x J`` product. On the TPU a Pallas kernel
  (``ssd_state_update`` in a device trace) walks the step's segments by
  scalar-prefetched slot ids, reads the slot's state once, applies the
  segment's rows one after another (one for a decode row, up to the chunk
  for a prefill), and writes the state back in place; elsewhere the same
  walk is a gather of the segments' states, a ``lax.scan`` over the rows
  and a scatter. A decay is ``exp`` of ``dt A <= 0`` a row, never of a
  sum. A slot whose segment starts at position 0 starts from zero inside
  the step, so admission, preemption and re-prefill clear nothing on the
  host; pad rows and the slots without rows are not touched.

The state of the packed form is laid for the kernel: ``ssm [L, J, N, H *
P]`` float32, a slot's ``[N, H * P]`` one contiguous run with the
``d_inner = H * P`` channels on lanes, so that the decay and ``dt x`` of a
row are lane vectors, ``B`` and ``C`` sublane vectors, and the read-out
``sum_n C[n] S[n, :]`` adds vregs and reduces no lane. A group's heads are
``d_inner / G`` consecutive channels, whole tiles of the kernel's, so the
group of a tile is a function of the tile's index.

:func:`causal_conv_step` is the depthwise convolution ahead of the scan
in the packed step: a row needs its slot's ``d_conv - 1`` earlier inputs,
which lie in the step (the segment's earlier rows) or in the slot's
*tail* ``conv [L, d_conv - 1, J, channels]``, a shift register a slot.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION
from ..utils.device import on_tpu
from .pallas_utils import compiler_params as _compiler_params

_HI = jax.lax.Precision.HIGHEST

#: channels of the state a kernel step updates at a time (a [N, TILE]
#: float32 tile: what stays in vregs between a row's decay and read-out)
TILE = 512


def ssd_full(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, chunk: int = 256) -> jax.Array:
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (positive), ``a [H]``
    (negative), ``b, c [B, S, N]`` (one group) or ``[B, S, G, N]``, ``d
    [H]`` at positions ``0..S-1`` -> ``y [B, S, H, P]`` float32."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    groups = 1 if b.ndim == 3 else b.shape[2]
    if groups > 1:
        # a group is the one-group scan over its own heads
        x, dt = (v.reshape(v.shape[:2] + (groups, h // groups)
                           + v.shape[3:]) for v in (x, dt))
        y = jax.vmap(functools.partial(ssd_full, chunk=chunk),
                     in_axes=(2, 2, 0, 2, 2, 0), out_axes=2)(
            x, dt, a.reshape(groups, -1), b, c, d.reshape(groups, -1))
        return y.reshape(bsz, s, h, p)
    b, c = (v.reshape(bsz, s, n) for v in (b, c))
    size = min(chunk, s)
    pad = -s % size
    x, dt, b, c = (jnp.pad(v.astype(jnp.float32),
                           ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                   .reshape((bsz, -1, size) + v.shape[2:]).swapaxes(0, 1)
                   for v in (x, dt, b, c))
    a = a.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((size, size), bool))

    def step(state, chunk_in):
        xc, dtc, bc, cc = chunk_in             # [B, C, H, P] [B, C, H] ..
        cum = jnp.cumsum(dtc * a, axis=1)                      # [B, C, H]
        u = dtc[..., None] * xc
        gap = cum[:, :, None, :] - cum[:, None, :, :]          # [B, t, s, H]
        decay = jnp.where(causal[None, :, :, None],
                          jnp.exp(jnp.where(causal[None, :, :, None], gap,
                                            0.0)), 0.0)
        scores = jnp.einsum("btn,bsn->bts", cc, bc, precision=_HI)
        y = jnp.einsum("btsh,bshp->bthp", scores[..., None] * decay, u,
                       precision=_HI)
        y += jnp.exp(cum)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", cc, state, precision=_HI)
        to_end = jnp.exp(cum[:, -1:, :] - cum)                 # [B, C, H]
        state = (jnp.exp(cum[:, -1])[:, :, None, None] * state
                 + jnp.einsum("bshp,bsn->bhpn", u * to_end[..., None], bc,
                              precision=_HI))
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32),
                        (x, dt, b, c))
    y = y.swapaxes(0, 1).reshape(bsz, -1, h, p)[:, :s]
    x = x.swapaxes(0, 1).reshape(bsz, -1, h, p)[:, :s]
    return y + d.astype(jnp.float32)[:, None] * x


class StepSegments(NamedTuple):
    """The packed step's rows by slot, built once a step
    (:func:`step_segments`) and read by every state-space layer: segment
    ``k`` is rows ``start[k] .. start[k] + rows[k] - 1``, all of slot
    ``slot[k]`` at consecutive positions, the first of them at position 0
    where ``zero[k]``. ``count [1]`` segments are real; ``slot`` repeats
    the last real one behind them (the kernel's walk then names no new
    block) and ``scatter_slot`` holds the slot count there (dropped by a
    scatter). Per row: ``segment [T]`` (``K`` for a pad row) and ``since
    [T]``, the rows of its segment before it."""

    count: jax.Array
    slot: jax.Array
    scatter_slot: jax.Array
    start: jax.Array
    rows: jax.Array
    zero: jax.Array
    segment: jax.Array
    since: jax.Array


def step_segments(slot_ids: jax.Array, positions: jax.Array,
                  slots: int) -> StepSegments:
    """``slot_ids [T]`` (a row outside ``0..slots-1`` is padding),
    ``positions [T]`` (PAD_POSITION: padding). The engine packs a slot's
    rows side by side and in order (one decode row, or a prefill chunk:
    ``ServingEngine._build_schedule``), which is what makes a slot one
    segment; a slot whose rows were apart would be two, and the second
    would not see the first."""
    t = slot_ids.shape[0]
    k = min(t, slots)
    row = jnp.arange(t, dtype=jnp.int32)
    real = (positions < PAD_POSITION) & (slot_ids >= 0) & (slot_ids < slots)
    before = jnp.concatenate([jnp.zeros((1,), bool), real[:-1]])
    same = jnp.concatenate([jnp.zeros((1,), bool),
                            slot_ids[1:] == slot_ids[:-1]])
    starts = real & ~(before & same)
    segment = jnp.where(real, jnp.cumsum(starts) - 1, k).astype(jnp.int32)
    count = jnp.sum(starts).astype(jnp.int32)
    at = jnp.where(starts, segment, k)

    def of_start(values):
        return jnp.zeros((k,), jnp.int32).at[at].set(
            values.astype(jnp.int32), mode="drop")

    start, slot, first = of_start(row), of_start(slot_ids), \
        of_start(positions)
    rows = jnp.zeros((k,), jnp.int32).at[segment].add(1, mode="drop")
    live = jnp.arange(k) < count
    return StepSegments(
        count=count.reshape(1),
        slot=jnp.where(live, slot, slot[jnp.maximum(count - 1, 0)]),
        scatter_slot=jnp.where(live, slot, slots), start=start, rows=rows,
        zero=(live & (first == 0)).astype(jnp.int32), segment=segment,
        since=jnp.where(real, row - start[jnp.minimum(segment, k - 1)], 0))


def causal_conv_step(x: jax.Array, tails: jax.Array, layer,
                     weight: jax.Array, bias: jax.Array,
                     seg: StepSegments):
    """The depthwise causal convolution of one layer over a packed step.
    ``x [T, C]`` the rows' inputs; ``tails [L, W - 1, J, C]`` every
    layer's per-slot tails, read and written at ``layer``: ``tails[l, i,
    j]`` is slot ``j``'s input ``W - 1 - i`` positions before its next one
    (slots before channels: a layer's ``[J, C]`` planes tile whole, where
    ``[W - 1, C]`` planes would be padded to the tile's rows and copied
    into that layout on the way into the layer loops, every step);
    ``weight [C, W]`` (tap ``W - 1`` meets the row's own input), ``bias
    [C]``. Returns ``(silu(conv) [T, C] float32, tails)``: a row's earlier
    inputs come from its segment's earlier rows (the step's rows shifted:
    no gather), else from the slot's tail, zeros before position 0; a
    slot's new tail is its last ``W - 1`` inputs (the old tail shifted
    where the segment is shorter)."""
    t, width = x.shape[0], weight.shape[1]
    slots = tails.shape[2]
    k = seg.slot.shape[0]
    old = jax.lax.dynamic_index_in_dim(tails, layer, 0, False)  # [W-1, J, C]
    own = jnp.minimum(seg.segment, k - 1)
    fresh = seg.zero[own] == 1                                   # [T]
    # by flat row (entry, slot): a gather along the slots of [W-1, J, C]
    # made the compiler lay the whole stack entries-before-channels
    flat = old.reshape((width - 1) * slots, -1)
    kept = flat[jnp.arange(width - 1)[:, None] * slots
                + seg.slot[own][None, :]]                        # [W-1, T, C]
    kept = jnp.where(fresh[None, :, None], jnp.zeros_like(kept), kept)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + w[:, width - 1] * x.astype(jnp.float32)
    for back in range(1, width):
        # the tail's entry for a row with ``since`` rows of its segment
        # before it: W - 1 - back + since, one of the W - 1 by a select
        behind = back - seg.since                                # >= 1 here
        from_tail = kept[width - 2]
        for i in range(width - 2):
            from_tail = jnp.where((behind == width - 1 - i)[:, None],
                                  kept[i], from_tail)
        earlier = jnp.where(
            (back <= seg.since)[:, None],
            jnp.pad(x, ((back, 0), (0, 0)))[:t], from_tail)
        out += w[:, width - 1 - back] * earlier.astype(jnp.float32)
    # every slot's new tail, whole (a scatter of the segments' tails into
    # the stack wrote 240 rows of 8 KiB one by one, 84 us a layer): entry
    # i is the input (W - 2 - i) rows before the slot's last row of the
    # step, else the old tail shifted by the slot's rows (none: as it was)
    rows = jnp.zeros((slots,), jnp.int32).at[seg.scatter_slot].set(
        seg.rows, mode="drop")                                   # [J]
    last = jnp.zeros((slots,), jnp.int32).at[seg.scatter_slot].set(
        seg.start + seg.rows - 1, mode="drop")
    fresh = jnp.zeros((slots,), bool).at[seg.scatter_slot].set(
        seg.zero == 1, mode="drop")
    was = jnp.where(fresh[None, :, None], jnp.zeros_like(old), old)
    new = []
    for i in range(width - 1):
        back = width - 2 - i
        shifted = was[width - 2]
        for r in range(width - 2 - i):
            shifted = jnp.where((rows == r)[:, None], was[i + r], shifted)
        new.append(jnp.where(
            (back < rows)[:, None],
            x[jnp.clip(last - back, 0, t - 1)].astype(tails.dtype), shifted))
    tails = jax.lax.dynamic_update_index_in_dim(tails, jnp.stack(new),
                                                layer, 0)
    return jax.nn.silu(out), tails


def _ssd_kernel(layer_ref, count_ref, slot_ref, start_ref, rows_ref,
                zero_ref, a_ref, u_ref, bt_ref, ct_ref, s_in_ref, y_ref,
                s_out_ref, *, tile: int):
    """Grid step ``k``: segment ``k``'s rows against its slot's state.
    ``a_ref, u_ref [T, C]`` the rows' decays and ``dt x`` by channel,
    ``bt_ref, ct_ref [G * N, T]`` their ``B`` and ``C`` by column, group
    ``g``'s in rows ``g N .. (g + 1) N - 1``, ``s_in_ref`` and ``s_out_ref
    [1, 1, N, C]`` the slot's state of this layer (one array, aliased),
    ``y_ref [T, C]`` every row's read-out, resident for the whole grid.
    Tile ``j`` of the channels is of group ``j * tile // (C / G)``."""
    from jax.experimental import pallas as pl

    k = pl.program_id(0)
    n, chans = s_out_ref.shape[2:]
    steps = a_ref.shape[0]
    groups = bt_ref.shape[0] // n

    @pl.when(k == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # a step without a real row still writes its (one) block back
    @pl.when((k == 0) & (count_ref[0] == 0))
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(k < count_ref[0])
    def _():
        start, rows = start_ref[k], rows_ref[k]
        column = jax.lax.broadcasted_iota(jnp.int32, (n, steps), 1)

        def apply(t, src_ref, fresh):
            mine = column == t

            def of_row(ref, g):
                # the row's column of group g, a sublane vector [N, 1]
                rows = ref[...] if groups == 1 else ref[pl.ds(g * n, n), :]
                return jnp.sum(jnp.where(mine, rows, 0.0), axis=1,
                               keepdims=True)

            for j in range(chans // tile):
                if j * tile * groups % chans == 0:
                    g = j * tile * groups // chans
                    b_t, c_t = of_row(bt_ref, g), of_row(ct_ref, g)
                cols = pl.ds(j * tile, tile)
                s = src_ref[0, 0, :, cols]
                if fresh is not None:
                    s = jnp.where(fresh, 0.0, s)
                s = (a_ref[pl.ds(t, 1), cols] * s
                     + b_t * u_ref[pl.ds(t, 1), cols])
                s_out_ref[0, 0, :, cols] = s
                y_ref[pl.ds(t, 1), cols] = jnp.sum(c_t * s, axis=0,
                                                   keepdims=True)

        apply(start, s_in_ref, zero_ref[k] == 1)

        def later(r, carry):
            apply(start + r, s_out_ref, None)
            return carry

        jax.lax.fori_loop(1, rows, later, 0)


def _state_tile(chans: int, groups: int = 1) -> int:
    """The kernel's tile of the channels: whole tiles a group."""
    return TILE if chans // groups % TILE == 0 else 128


def _ssd_update_pallas(a, u, bt, ct, ssm, layer, seg: StepSegments,
                       interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, chans = a.shape
    n = ssm.shape[2]
    rows = bt.shape[0]                                  # groups * n
    k = seg.slot.shape[0]

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    def of_slot():
        return pl.BlockSpec(
            (1, 1, n, chans),
            lambda i, layer, count, slot, *_: (layer[0], slot[i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(k,),
        in_specs=[whole((t, chans)), whole((t, chans)), whole((rows, t)),
                  whole((rows, t)), of_slot()],
        out_specs=[whole((t, chans)), of_slot()])
    y, ssm = pl.pallas_call(
        functools.partial(_ssd_kernel,
                          tile=_state_tile(chans, rows // n)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, chans), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={10: 1},
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="ssd_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), seg.count, seg.slot,
      seg.start, seg.rows, seg.zero, a, u, bt, ct, ssm)
    return y, ssm


def _ssd_update_xla(a, u, bt, ct, ssm, layer, seg: StepSegments):
    """The kernel's walk in XLA: gather the segments' states, apply the
    rows in order, scatter the states back. ``bt, ct [G * N, T]`` as the
    kernel's."""
    k, slots = seg.slot.shape[0], ssm.shape[1]
    n, chans = ssm.shape[2:]
    groups = bt.shape[0] // n

    def by_channel(v):
        """A row's ``[G * N]`` as ``[N, 1]`` (one group) or ``[N, C]``."""
        if groups == 1:
            return v[:, None]
        return jnp.repeat(v.reshape(groups, n).T, chans // groups, axis=1)
    states = jax.lax.dynamic_index_in_dim(ssm, layer, 0, False)[
        jnp.minimum(seg.scatter_slot, slots - 1)]              # [K, N, C]
    states = jnp.where((seg.zero == 1)[:, None, None], 0.0, states)

    def row(states, r):
        a_t, u_t, b_t, c_t, own = r
        s = states[jnp.minimum(own, k - 1)]
        s = jnp.where(own < k,
                      a_t[None, :] * s + by_channel(b_t) * u_t[None, :], s)
        y = jnp.where(own < k, jnp.sum(by_channel(c_t) * s, axis=0), 0.0)
        return states.at[own].set(s, mode="drop"), y

    states, y = jax.lax.scan(row, states, (a, u, bt.T, ct.T, seg.segment))
    return y, ssm.at[layer, seg.scatter_slot].set(states, mode="drop")


def ssd_packed_impl(d_state: int, channels: int,
                    force_pallas=None, groups: int = 1) -> str:
    """What :func:`ssd_packed` runs for a state ``[d_state, channels]`` of
    ``groups`` groups on the default backend: ``"pallas"`` (the compiled
    kernel), ``"pallas-interpret"`` (the kernel, forced, off the TPU) or
    ``"xla"`` (the gather, scan and scatter). The kernel wants a group's
    channels on whole lanes and the state's rows on whole sublanes."""
    if force_pallas is False:
        return "xla"
    tiles = (channels % groups == 0 and channels // groups % 128 == 0
             and d_state % 8 == 0)
    if not on_tpu():
        return "pallas-interpret" if force_pallas and tiles else "xla"
    if tiles:
        return "pallas"
    if force_pallas:
        raise ValueError(f"a state of [{d_state}, {channels}] does not "
                         "tile for the ssd kernel")
    return "xla"


def ssd_packed(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, d: jax.Array, ssm: jax.Array, layer,
               seg: StepSegments, force_pallas=None):
    """One packed step of one layer. ``x [T, H, P]``, ``dt [T, H]``
    (positive), ``a [H]`` (negative), ``b, c [T, N]`` (one group) or ``[T,
    G, N]``, ``d [H]``; ``ssm [L, J, N, H * P]`` float32, every layer's
    per-slot states, read and written at ``layer``; ``seg`` the step's
    segments. Returns ``(y [T, H, P] float32, ssm)``; a pad row's ``y`` is
    zero."""
    t, h, p = x.shape
    xf, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.repeat(jnp.exp(dt * a.astype(jnp.float32)), p, axis=1)
    u = (dt[:, :, None] * xf).reshape(t, h * p)
    groups = 1 if b.ndim == 2 else b.shape[1]
    bt, ct = (v.astype(jnp.float32).reshape(t, -1).T for v in (b, c))
    impl = ssd_packed_impl(b.shape[-1], h * p, force_pallas, groups)
    if impl == "xla":
        y, ssm = _ssd_update_xla(decay, u, bt, ct, ssm, layer, seg)
    else:
        y, ssm = _ssd_update_pallas(decay, u, bt, ct, ssm, layer, seg,
                                    interpret=impl == "pallas-interpret")
    real = (seg.segment < seg.slot.shape[0])[:, None, None]
    y = y.reshape(t, h, p) + d.astype(jnp.float32)[:, None] * xf
    return jnp.where(real, y, 0.0), ssm
