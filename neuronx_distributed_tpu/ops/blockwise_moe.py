"""Blockwise (dropless) MoE expert kernels: Pallas grouped GLU + reference.

The expert matmul of the dropless MoE path (reference NKI kernel family
``modules/moe/blockwise.py:856``): tokens arrive sorted by expert in
fixed-size blocks (``modules/moe/blockwise.py`` computes the metadata), and
each block runs ``silu(x@Wg_e)·(x@Wu_e) @ Wd_e`` with the weights of the
expert that owns it. TPU-native design, following the
:mod:`.paged_attention` pattern:

* the block→expert table is **scalar-prefetched** into SMEM
  (``pltpu.PrefetchScalarGridSpec``), so the weight BlockSpec index_maps
  read ``block_expert[b]`` and each block streams exactly its expert's
  weight tiles from HBM — consecutive blocks of one expert elide the
  re-fetch (one expert-weight DMA per block *run*, not per block);
* the intermediate dim is tiled (grid dim ``ib``) so weight tiles fit VMEM
  at 7B/70B sizes; the backward is the same pattern transposed — dx is a
  grouped matmul against the transposed weights, dW accumulates per expert
  by *output revisiting* (consecutive blocks of one expert map to the same
  output tile, which Mosaic keeps in VMEM and flushes once);
* a **pure-jnp reference** mirrors the kernel's arithmetic exactly — same
  per-``(b, ib)`` ``dot_general`` shapes, same fp32 accumulation order, same
  sentinel skips — so interpret-mode parity is *bitwise*, and the reference
  doubles as the silent CPU fallback (auto-dispatch below);
* **auto-dispatch**: ``force_pallas=None`` runs the Pallas kernel on
  TPU-like backends and the jnp reference elsewhere; ``True`` forces the
  kernel (interpret mode off-TPU — the parity-test hook); ``False`` forces
  the reference.

Weight layouts are the stacked expert banks of
:class:`...modules.moe.expert_mlps.ExpertMLPs`: ``gate [E, H, I]``,
``up [E, H, I]`` (two operands, the stored form: :mod:`...modules.glu`; a
``stack`` at the call would put back the copy that form removed),
``down [E, I, H]``. Blocks whose ``block_expert[b] >= E`` are *sentinels*
(padding or non-local EP pairs): their compute is skipped and their output
rows are zero; their weight-tile index clamps to the last real expert so a
sentinel run costs no extra DMA.

**The layers' stacks.** A model whose layers run under a scan keeps every
layer's bank in one leaf, ``gate``, ``up`` ``[L, E, H, I]`` and ``down``
``[L, E, I, H]``, and the scan hands a layer ``stack[i]``. An XLA matmul
takes that ``dynamic-slice`` into its own fusion; a Mosaic kernel is a
custom call whose operands must be buffers, so XLA wrote layer ``i``'s three
banks out in front of every call (0.4 GB read and written, 21.7 ms of
``sdar-30b-a3b-chat``'s 58 ms serving step on a v5e: ``PERF.md``, PR 68).
The forward entries therefore also take the stacks themselves with ``layer``,
an int32 scalar: it is prefetched beside ``block_expert``, the weight index
maps lead with it (``(layer, expert, 0, ib)`` over a squeezed leading
dimension, as :mod:`.paged_attention` reads ``pool[layer]``), and the body,
the grid, the clamp and the elided refetch are the same. Forward-only:
serving runs it, and training differentiates the ``[E, H, I]`` entry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.device import on_tpu
from .pallas_utils import compiler_params as _compiler_params

__all__ = ["grouped_glu", "grouped_glu_decode", "grouped_glu_reference",
           "use_pallas"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _dsilu(x):
    s = jax.nn.sigmoid(x)
    return s * (1 + x * (1 - s))


def _gate_up(x, wg, wu):
    """``x [B, H]`` through one gate and one up tile ``[H, bI]``, float32:
    the two dots every kernel body and its reference start with."""
    dims = (((1,), (0,)), ((), ()))
    return (lax.dot_general(x, wg, dims, preferred_element_type=jnp.float32),
            lax.dot_general(x, wu, dims, preferred_element_type=jnp.float32))


# ---------------------------------------------------------------------------
# Pallas kernels (training fwd/bwd + decode fwd)
# ---------------------------------------------------------------------------

def _glu_fwd_kernel(be_ref, x_ref, g_ref, u_ref, dn_ref, y_ref, *,
                    num_ib: int, num_real: int):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        # unconditional: sentinel blocks' outputs must be ZERO (their
        # combine gates are zero, but 0 * uninitialized-HBM could be NaN)
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(be_ref[b] < num_real)
    def _compute():
        x = x_ref[...].astype(jnp.float32)            # [B, H]
        wg = g_ref[0].astype(jnp.float32)             # [H, bI]
        wu = u_ref[0].astype(jnp.float32)
        g, u = _gate_up(x, wg, wu)
        a = _silu(g) * u                              # [B, bI]
        y_ref[...] = y_ref[...] + jax.lax.dot_general(
            a, dn_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(y_ref.dtype)


def _glu_dx_kernel(be_ref, x_ref, g_ref, u_ref, dn_ref, dy_ref, dx_ref, *,
                   num_ib: int, num_real: int):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when(be_ref[b] < num_real)
    def _compute():
        x = x_ref[...].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        wg = g_ref[0].astype(jnp.float32)             # [H, bI]
        wu = u_ref[0].astype(jnp.float32)
        dn = dn_ref[0].astype(jnp.float32)            # [bI, H]
        g, u = _gate_up(x, wg, wu)
        da = jax.lax.dot_general(dy, dn, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dg = da * u * _dsilu(g)
        du = da * _silu(g)
        dx = jax.lax.dot_general(dg, wg, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dx = dx + jax.lax.dot_general(du, wu, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dx_ref[...] = dx_ref[...] + dx.astype(dx_ref.dtype)


def _glu_dw_kernel(be_ref, x_ref, g_ref, u_ref, dn_ref, dy_ref, dg_ref,
                   du_ref, ddn_ref, *, num_ib: int, num_real: int):
    """Grid (ib, b): consecutive b of one expert revisit the same dW output
    block, accumulating in VMEM; zero it on the expert's first block."""
    from jax.experimental import pallas as pl

    b = pl.program_id(1)
    # boundaries on the CLAMPED expert id (what the out index_map uses):
    # sentinel blocks share the last real expert's tile, so the real->
    # sentinel transition must NOT re-zero that expert's accumulated dW
    cur = jnp.minimum(be_ref[b], num_real - 1)
    prev = jnp.minimum(be_ref[jnp.maximum(b, 1) - 1], num_real - 1)
    first_of_expert = jnp.logical_or(b == 0, prev != cur)

    @pl.when(first_of_expert)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        du_ref[...] = jnp.zeros_like(du_ref)
        ddn_ref[...] = jnp.zeros_like(ddn_ref)

    @pl.when(be_ref[b] < num_real)
    def _compute():
        x = x_ref[...].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        wg = g_ref[0].astype(jnp.float32)
        wu = u_ref[0].astype(jnp.float32)
        dn = dn_ref[0].astype(jnp.float32)
        g, u = _gate_up(x, wg, wu)
        a = _silu(g) * u
        da = jax.lax.dot_general(dy, dn, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dg = da * u * _dsilu(g)
        du = da * _silu(g)
        # ddown[e, ib] += a^T @ dy ; dgate/dup[e, :, ib] += x^T @ dg/du
        ddn_ref[0] = ddn_ref[0] + jax.lax.dot_general(
            a, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(ddn_ref.dtype)
        dgw = jax.lax.dot_general(x, dg, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        duw = jax.lax.dot_general(x, du, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dg_ref[0] = dg_ref[0] + dgw.astype(dg_ref.dtype)
        du_ref[0] = du_ref[0] + duw.astype(du_ref.dtype)


def _weight_specs(pl, h, block_i, we, b_first: bool, stacked: bool = False):
    """BlockSpecs of one block's expert tiles: gate and up ``[1, H, bI]``,
    down ``[1, bI, H]``, on a grid ``(b, ib)`` if ``b_first`` else
    ``(ib, b)``; ``we`` clamps a sentinel's expert id. ``stacked``: the
    operands are the layers' stacks ``[L, E, ., .]`` and the second
    prefetched scalar names the layer: the same tiles behind a squeezed
    leading dimension, fetched from ``stack[layer]`` where it lies."""
    def at(i, j):
        return (i, j) if b_first else (j, i)

    def spec(shape, tile):
        def bank(i, j, be):
            b, ib = at(i, j)
            return tile(we(be[b]), ib)

        if not stacked:
            return pl.BlockSpec(shape, bank)
        return pl.BlockSpec(
            (None,) + shape,
            lambda i, j, be, layer: (layer[0],) + bank(i, j, be))

    def col():
        return spec((1, h, block_i), lambda e, ib: (e, 0, ib))

    return [col(), col(), spec((1, block_i, h), lambda e, ib: (e, ib, 0))]


def _stack_operands(kernel, block_expert, layer):
    """``(kernel, scalar operands)`` of a call over ``[E, ., .]`` banks
    (``layer`` None) or over the layers' stacks: there the layer's index
    is prefetched beside ``block_expert``, for the index maps alone (the
    body never reads it)."""
    if layer is None:
        return kernel, (block_expert,)

    def body(be_ref, layer_ref, *refs):
        del layer_ref
        kernel(be_ref, *refs)

    return body, (block_expert, jnp.reshape(layer, (1,)).astype(jnp.int32))


def _grouped_glu_pallas(xs, gate, up, down, block_expert, block_size,
                        block_i, interpret, num_real, layer=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, h = xs.shape
    i = gate.shape[-1]
    nb = p // block_size
    num_ib = i // block_i
    # sentinel blocks (be >= num_real) borrow the LAST real expert's weight
    # tiles via this clamp — the DMA is elided across a run of sentinel
    # blocks and the kernels' pl.when guards skip their compute entirely.
    # Grid order (b, ib): the y block accumulates over consecutive ib steps
    # in VMEM (a non-consecutive revisit would not re-fetch); weight tiles
    # are refetched per block — the layout that favours training, where
    # nb ~ E. Decode uses the (ib, b) grid of :func:`grouped_glu_decode`.
    we = functools.partial(jnp.minimum, num_real - 1)
    kernel, scalars = _stack_operands(
        functools.partial(_glu_fwd_kernel, num_ib=num_ib, num_real=num_real),
        block_expert, layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(nb, num_ib),
        in_specs=[
            pl.BlockSpec((block_size, h), lambda b, ib, *_: (b, 0)),
            *_weight_specs(pl, h, block_i, we, True, layer is not None),
        ],
        out_specs=pl.BlockSpec((block_size, h), lambda b, ib, *_: (b, 0)),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p, h), xs.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="grouped_glu_fwd",
    )(*scalars, xs, gate, up, down)


def _glu_fwd_decode_kernel(be_ref, x_ref, g_ref, u_ref, dn_ref, y_ref, *,
                           num_real: int):
    from jax.experimental import pallas as pl

    b = pl.program_id(1)

    # each (ib, b) output block is written exactly once — no revisits
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(be_ref[b] < num_real)
    def _compute():
        x = x_ref[...].astype(jnp.float32)            # [B, H]
        wg = g_ref[0].astype(jnp.float32)             # [H, bI]
        wu = u_ref[0].astype(jnp.float32)
        g, u = _gate_up(x, wg, wu)
        a = _silu(g) * u                              # [B, bI]
        y_ref[...] = jax.lax.dot_general(
            a, dn_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(y_ref.dtype)[None]


def _grouped_glu_decode_pallas(xs, gate, up, down, block_expert, block_size,
                               block_i, interpret, layer=None):
    """Forward-only grouped GLU tuned for decode HBM traffic.

    Grid order (ib, b) — token blocks INNERMOST — so consecutive blocks of
    one (clamped) expert keep an identical weight-tile index and Pallas
    elides the refetch: total weight traffic is (#hit experts) x weights
    instead of (#blocks) x weights. With ``sentinel_empty`` metadata all
    empty experts clamp into one shared sentinel run, so a T-token decode
    step reads only the experts those tokens hit — the bandwidth property
    the reference's fused token-gen kernel exists for
    (``moe_fused_tkg.py:85``). Each (ib, b) output block is written exactly
    once into a partial layout [num_ib, P, H] summed by XLA (an in-kernel
    accumulation would need non-consecutive output revisits, which do not
    re-fetch). The extra partial-sum traffic is O(num_ib·P·H) — trivial at
    decode's tiny P, which is why training keeps :func:`grouped_glu`.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, h = xs.shape
    num_real, _, i = gate.shape[-3:]
    nb = p // block_size
    num_ib = i // block_i
    we = functools.partial(jnp.minimum, num_real - 1)
    kernel, scalars = _stack_operands(
        functools.partial(_glu_fwd_decode_kernel, num_real=num_real),
        block_expert, layer)
    partial = pl.pallas_call(
        kernel,
        # fp32 partials: the per-ib contributions are summed below, and a
        # bf16 round-trip through HBM before that sum loses mantissa bits
        # the kernel already paid fp32 accumulation for (advisor r3)
        out_shape=jax.ShapeDtypeStruct((num_ib, p, h), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(num_ib, nb),
            in_specs=[
                pl.BlockSpec((block_size, h), lambda ib, b, *_: (b, 0)),
                *_weight_specs(pl, h, block_i, we, False, layer is not None),
            ],
            out_specs=pl.BlockSpec((1, block_size, h),
                                   lambda ib, b, *_: (ib, b, 0)),
        ),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="grouped_glu_fwd_decode",
    )(*scalars, xs, gate, up, down)
    return jnp.sum(partial, axis=0).astype(xs.dtype)


def _grouped_glu_pallas_bwd(xs, gate, up, down, block_expert, dy, block_size,
                            block_i, interpret, num_real):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, h = xs.shape
    i = gate.shape[-1]
    nb = p // block_size
    num_ib = i // block_i
    we = functools.partial(jnp.minimum, num_real - 1)

    dx = pl.pallas_call(
        functools.partial(_glu_dx_kernel, num_ib=num_ib,
                          num_real=num_real),
        out_shape=jax.ShapeDtypeStruct((p, h), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, num_ib),
            in_specs=[
                pl.BlockSpec((block_size, h), lambda b, ib, be: (b, 0)),
                *_weight_specs(pl, h, block_i, we, True),
                pl.BlockSpec((block_size, h), lambda b, ib, be: (b, 0)),
            ],
            out_specs=pl.BlockSpec((block_size, h),
                                   lambda b, ib, be: (b, 0)),
        ),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="grouped_glu_bwd_dx",
    )(block_expert, xs, gate, up, down, dy)

    dgate, dup, ddn = pl.pallas_call(
        functools.partial(_glu_dw_kernel, num_ib=num_ib,
                          num_real=num_real),
        out_shape=[jax.ShapeDtypeStruct(gate.shape, jnp.float32),
                   jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct(down.shape, jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_ib, nb),
            in_specs=[
                pl.BlockSpec((block_size, h), lambda ib, b, be: (b, 0)),
                *_weight_specs(pl, h, block_i, we, False),
                pl.BlockSpec((block_size, h), lambda ib, b, be: (b, 0)),
            ],
            out_specs=_weight_specs(pl, h, block_i, we, False),
        ),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(),
        name="grouped_glu_bwd_dw",
    )(block_expert, xs, gate, up, down, dy)
    return (dx, dgate.astype(gate.dtype), dup.astype(up.dtype),
            ddn.astype(down.dtype))


# ---------------------------------------------------------------------------
# Pure-jnp reference (bit-exact vs the kernels in interpret mode)
#
# Every dot below uses the SAME lax.dot_general dimension numbers, operand
# shapes and fp32 accumulation order as the kernel body executes them per
# (b, ib) grid step, so CPU parity is bitwise, not approximate: a scan over
# blocks is the grid's b loop, the unrolled num_ib loop is the grid's ib
# loop, and sentinel blocks contribute exactly nothing (lax.cond / where,
# never a masked add that could flip a -0.0).
# ---------------------------------------------------------------------------

def _expert_weights(gate, up, down, be_b, num_real):
    """The (clamped) expert's ``gate [H, I]``, ``up [H, I]``,
    ``down [I, H]``, and its clamped index."""
    we = jnp.minimum(be_b, num_real - 1)
    return tuple(lax.dynamic_index_in_dim(w, we, 0, keepdims=False)
                 for w in (gate, up, down)) + (we,)


def _tiles(g_e, u_e, dn_e, ib, block_i):
    """Tile ``ib`` of one expert's weights, float32: ``[H, bI]`` twice and
    ``[bI, H]``."""
    return tuple(
        lax.dynamic_slice_in_dim(w, ib * block_i, block_i,
                                 axis=axis).astype(jnp.float32)
        for w, axis in ((g_e, 1), (u_e, 1), (dn_e, 0)))


def _ref_block_fwd(x_blk, g_e, u_e, dn_e, live, block_i, num_ib, out_dtype):
    """One token block through the GLU with its (clamped) expert weights:
    the per-``ib`` fp32 partials accumulate in ``out_dtype`` exactly like
    ``y_ref[...] = y_ref[...] + partial.astype(y_ref.dtype)``."""
    x = x_blk.astype(jnp.float32)
    y = jnp.zeros((x.shape[0], dn_e.shape[-1]), out_dtype)
    for ib in range(num_ib):
        wg, wu, dn = _tiles(g_e, u_e, dn_e, ib, block_i)
        g, u = _gate_up(x, wg, wu)
        a = _silu(g) * u
        y = y + lax.dot_general(
            a, dn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)
    return jnp.where(live, y, jnp.zeros_like(y))


def _ref_fwd(xs, gate, up, down, block_expert, block_size, block_i,
             num_real):
    p, h = xs.shape
    i = gate.shape[-1]
    nb = p // block_size
    num_ib = i // block_i
    xb = xs.reshape(nb, block_size, h)

    def step(_, inp):
        x_blk, be_b = inp
        g_e, u_e, dn_e, _ = _expert_weights(gate, up, down, be_b, num_real)
        y = _ref_block_fwd(x_blk, g_e, u_e, dn_e, be_b < num_real, block_i,
                           num_ib, xs.dtype)
        return None, y

    _, ys = lax.scan(step, None, (xb, block_expert))
    return ys.reshape(p, h)


def _ref_decode_fwd(xs, gate, up, down, block_expert, block_size, block_i):
    """Decode reference: per-(ib, b) partials land in a [num_ib, P, H]
    fp32 layout summed at the end — the same ``jnp.sum(partial, axis=0)``
    the Pallas decode path performs outside the kernel."""
    p, h = xs.shape
    num_real, _, i = gate.shape
    nb = p // block_size
    num_ib = i // block_i
    xb = xs.reshape(nb, block_size, h)

    def step(_, inp):
        x_blk, be_b = inp
        g_e, u_e, dn_e, _ = _expert_weights(gate, up, down, be_b, num_real)
        x = x_blk.astype(jnp.float32)
        parts = []
        for ib in range(num_ib):
            wg, wu, dn = _tiles(g_e, u_e, dn_e, ib, block_i)
            g, u = _gate_up(x, wg, wu)
            a = _silu(g) * u
            y = lax.dot_general(a, dn, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            parts.append(jnp.where(be_b < num_real, y, jnp.zeros_like(y)))
        return None, jnp.stack(parts)                 # [num_ib, B, H]

    _, parts = lax.scan(step, None, (xb, block_expert))
    partial = jnp.moveaxis(parts, 1, 0).reshape(num_ib, p, h)
    return jnp.sum(partial, axis=0).astype(xs.dtype)


def _ref_dx(xs, gate, up, down, block_expert, dy, block_size, block_i,
            num_real):
    p, h = xs.shape
    i = gate.shape[-1]
    nb = p // block_size
    num_ib = i // block_i
    xb = xs.reshape(nb, block_size, h)
    dyb = dy.reshape(nb, block_size, h)

    def step(_, inp):
        x_blk, dy_blk, be_b = inp
        g_e, u_e, dn_e, _ = _expert_weights(gate, up, down, be_b, num_real)
        x = x_blk.astype(jnp.float32)
        dyf = dy_blk.astype(jnp.float32)
        dx = jnp.zeros((block_size, h), xs.dtype)
        for ib in range(num_ib):
            wg, wu, dn = _tiles(g_e, u_e, dn_e, ib, block_i)
            g, u = _gate_up(x, wg, wu)
            da = lax.dot_general(dyf, dn, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            dg = da * u * _dsilu(g)
            du = da * _silu(g)
            d = lax.dot_general(dg, wg, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            d = d + lax.dot_general(du, wu, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            dx = dx + d.astype(xs.dtype)
        return None, jnp.where(be_b < num_real, dx, jnp.zeros_like(dx))

    _, dxs = lax.scan(step, None, (xb, dyb, block_expert))
    return dxs.reshape(p, h)


def _accumulate(acc, part, start):
    """``acc[start : start + part.shape] += part`` (``part`` leads with a
    1): one fp32 add per tile, as the kernel's VMEM accumulation does."""
    tile = lax.dynamic_slice(acc, start, part.shape)
    return lax.dynamic_update_slice(acc, tile + part, start)


def _ref_dw(xs, gate, up, down, block_expert, dy, block_size, block_i,
            num_real):
    """dW reference: fp32 accumulators updated block-by-block in ascending
    ``b`` order (the kernel's grid (ib, b) VMEM accumulation per expert
    tile is exactly this sequence of fp32 adds); sentinel blocks are
    skipped via ``lax.cond`` so they contribute no add at all."""
    p, h = xs.shape
    i = gate.shape[-1]
    nb = p // block_size
    num_ib = i // block_i
    xb = xs.reshape(nb, block_size, h)
    dyb = dy.reshape(nb, block_size, h)

    def step(carry, inp):
        x_blk, dy_blk, be_b = inp
        g_e, u_e, dn_e, we = _expert_weights(gate, up, down, be_b, num_real)
        x = x_blk.astype(jnp.float32)
        dyf = dy_blk.astype(jnp.float32)

        def upd(c):
            dgate, dup, ddn = c
            for ib in range(num_ib):
                wg, wu, dn = _tiles(g_e, u_e, dn_e, ib, block_i)
                g, u = _gate_up(x, wg, wu)
                a = _silu(g) * u
                da = lax.dot_general(dyf, dn, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                dg = da * u * _dsilu(g)
                du = da * _silu(g)
                ddn_c = lax.dot_general(a, dyf, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                dgw = lax.dot_general(x, dg, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                duw = lax.dot_general(x, du, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                dgate = _accumulate(dgate, dgw[None], (we, 0, ib * block_i))
                dup = _accumulate(dup, duw[None], (we, 0, ib * block_i))
                ddn = _accumulate(ddn, ddn_c[None], (we, ib * block_i, 0))
            return dgate, dup, ddn

        return lax.cond(be_b < num_real, upd, lambda c: c, carry), None

    init = tuple(jnp.zeros(w.shape, jnp.float32) for w in (gate, up, down))
    (dgate, dup, ddn), _ = lax.scan(step, init, (xb, dyb, block_expert))
    return (dgate.astype(gate.dtype), dup.astype(up.dtype),
            ddn.astype(down.dtype))


# ---------------------------------------------------------------------------
# custom_vjp wrappers: one pallas-backed, one reference-backed, identical
# signatures, so autodiff works through whichever path auto-dispatch picks
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_glu_kernel(xs, gate, up, down, block_expert, block_size,
                        block_i, interpret):
    return _grouped_glu_pallas(xs, gate, up, down, block_expert, block_size,
                               block_i, interpret, gate.shape[0])


def _kernel_fwd(xs, gate, up, down, block_expert, block_size, block_i,
                interpret):
    ys = _grouped_glu_pallas(xs, gate, up, down, block_expert, block_size,
                             block_i, interpret, gate.shape[0])
    return ys, (xs, gate, up, down, block_expert)


def _kernel_bwd(block_size, block_i, interpret, res, dy):
    xs, gate, up, down, block_expert = res
    dx, dgate, dup, ddn = _grouped_glu_pallas_bwd(
        xs, gate, up, down, block_expert, dy, block_size, block_i,
        interpret, gate.shape[0])
    dbe = jnp.zeros(block_expert.shape, jax.dtypes.float0)
    return dx, dgate, dup, ddn, dbe


_grouped_glu_kernel.defvjp(_kernel_fwd, _kernel_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def grouped_glu_reference(xs, gate, up, down, block_expert, block_size,
                          block_i):
    """Pure-jnp grouped GLU, arithmetic-identical to the Pallas kernel
    (the golden reference of the interpret-mode parity gate, and the
    silent CPU fallback of :func:`grouped_glu`)."""
    return _ref_fwd(xs, gate, up, down, block_expert, block_size, block_i,
                    gate.shape[0])


def _ref_vjp_fwd(xs, gate, up, down, block_expert, block_size, block_i):
    ys = _ref_fwd(xs, gate, up, down, block_expert, block_size, block_i,
                  gate.shape[0])
    return ys, (xs, gate, up, down, block_expert)


def _ref_vjp_bwd(block_size, block_i, res, dy):
    xs, gate, up, down, block_expert = res
    num_real = gate.shape[0]
    dx = _ref_dx(xs, gate, up, down, block_expert, dy, block_size, block_i,
                 num_real)
    dgate, dup, ddn = _ref_dw(xs, gate, up, down, block_expert, dy,
                              block_size, block_i, num_real)
    dbe = jnp.zeros(block_expert.shape, jax.dtypes.float0)
    return dx, dgate, dup, ddn, dbe


grouped_glu_reference.defvjp(_ref_vjp_fwd, _ref_vjp_bwd)


# ---------------------------------------------------------------------------
# auto-dispatch (the ops/paged_attention.py idiom)
# ---------------------------------------------------------------------------

def use_pallas(force_pallas=None) -> bool:
    """Resolve the dispatch knob: ``None`` (auto) → Pallas only on
    a TPU backend, silent jnp reference elsewhere; ``True`` → always
    the kernel (interpret mode off-TPU — the bit-exactness test hook);
    ``False`` → always the reference."""
    if force_pallas is None:
        return on_tpu()
    return bool(force_pallas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _grouped_glu_stacked(xs, gate, up, down, block_expert, layer,
                         block_size, block_i, decode, interpret):
    """Either forward over the layers' stacks ``[L, E, ., .]`` at
    ``layer``; ``interpret`` None: the reference on ``stack[layer]`` (XLA
    fuses the index into the reference's reads)."""
    if interpret is None:
        banks = tuple(lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
                      for w in (gate, up, down))
        if decode:
            return _ref_decode_fwd(xs, *banks, block_expert, block_size,
                                   block_i)
        return _ref_fwd(xs, *banks, block_expert, block_size, block_i,
                        gate.shape[1])
    if decode:
        return _grouped_glu_decode_pallas(xs, gate, up, down, block_expert,
                                          block_size, block_i, interpret,
                                          layer)
    return _grouped_glu_pallas(xs, gate, up, down, block_expert, block_size,
                               block_i, interpret, gate.shape[1], layer)


def _stacked_fwd(*args):
    raise TypeError(
        "grouped_glu over the layers' stacks (gate, up [L, E, H, I], down "
        "[L, E, I, H] and a layer's index) is forward-only: it is the "
        "serving step's operand form. Differentiate grouped_glu on "
        "stack[layer], [E, H, I], whose custom_vjp holds the backward "
        "kernels")


_grouped_glu_stacked.defvjp(_stacked_fwd, lambda *args: None)


def _stacked(xs, gate, up, down, block_expert, layer, block_size, block_i,
             force_pallas, decode):
    if not gate.ndim == up.ndim == down.ndim == 4:
        raise ValueError(
            "grouped_glu with a layer's index takes the layers' stacks, "
            f"gate and up [L, E, H, I] and down [L, E, I, H]; got "
            f"{gate.shape}, {up.shape}, {down.shape}")
    interpret = (not on_tpu()) if use_pallas(force_pallas) else None
    return _grouped_glu_stacked(xs, gate, up, down, block_expert,
                                jnp.asarray(layer, jnp.int32), block_size,
                                block_i, decode, interpret)


def grouped_glu(xs, gate, up, down, block_expert, block_size, block_i,
                force_pallas=None, layer=None):
    """Block-sparse grouped GLU: ``ys[b] = silu(x_b@Wg_e)·(x_b@Wu_e) @ Wd_e``
    with ``e = block_expert[b]`` (the dropless expert matmul; training
    fwd+bwd).

    Blocks whose ``block_expert[b] >= E`` (the weight arrays' expert count)
    are *sentinels* (padding / bound-EP non-local pairs): their compute is
    skipped and their output rows are zero. Deriving the sentinel threshold
    from the array shape (rather than a parameter) guarantees every real
    expert owns >= 1 block, so no dW tile is left unwritten.

    With ``layer`` (an int32 scalar) the weights are the layers' stacks,
    ``[L, E, H, I]`` twice and ``[L, E, I, H]``, and the product is layer
    ``layer``'s, bit for bit, read where the bank lies (module docstring,
    "The layers' stacks"); forward-only."""
    if layer is not None:
        return _stacked(xs, gate, up, down, block_expert, layer, block_size,
                        block_i, force_pallas, False)
    if use_pallas(force_pallas):
        interpret = not on_tpu()
        return _grouped_glu_kernel(xs, gate, up, down, block_expert,
                                   block_size, block_i, interpret)
    return grouped_glu_reference(xs, gate, up, down, block_expert,
                                 block_size, block_i)


def grouped_glu_decode(xs, gate, up, down, block_expert, block_size,
                       block_i, force_pallas=None, layer=None):
    """Forward-only grouped GLU tuned for decode HBM traffic (token blocks
    innermost so one expert's weight DMA serves its whole block run; pair
    with ``sentinel_empty`` metadata so only hit experts are read).
    ``layer``: as :func:`grouped_glu`'s, over the layers' stacks."""
    if layer is not None:
        return _stacked(xs, gate, up, down, block_expert, layer, block_size,
                        block_i, force_pallas, True)
    if use_pallas(force_pallas):
        interpret = not on_tpu()
        return _grouped_glu_decode_pallas(xs, gate, up, down, block_expert,
                                          block_size, block_i, interpret)
    return _ref_decode_fwd(xs, gate, up, down, block_expert, block_size,
                           block_i)
