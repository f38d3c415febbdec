"""Decomposed (latency-hiding) tensor-parallel collective-matmuls.

The TP layers' hot path interleaves matmuls with collectives: the
sequence-parallel entry all-gathers activations before the column matmul,
and the row matmul exits through a reduce-scatter (or, in plain TP, an
all-reduce). Issued monolithically those collectives serialize with the
compute they feed — the wire sits idle during the matmul and the MXU sits
idle during the collective. This module decomposes each pair into a
``ppermute`` ring that streams remote shards (or partial products) while
each step's partial matmul runs, so XLA can overlap the per-step transfer
with the independent per-step compute (the reference hides the same
latency with hand-scheduled async all-reduce in
``LinearWithAsyncCommunication``, ``parallel_layers/layers.py:434-504``;
see also PAPERS.md on multi-node comm/compute overlap).

Four primitives, each a ``custom_vjp`` whose backward uses the *dual*
decomposition (grad of an all-gather-matmul is a matmul-reduce-scatter and
vice versa):

======================  ===========================  =======================
op                      forward                      backward (dx)
======================  ===========================  =======================
all_gather_matmul       AG(x, dim) @ w  (ring)       matmul_reduce_scatter
matmul_reduce_scatter   RS(x @ w, dim)  (ring)       all_gather_matmul
matmul_all_reduce       AR(x @ w) = AG(RS(x @ w))    x-free: g @ w^T
copy_matmul             x @ w (x replicated)         AR(g @ w^T) = AG(RS(.))
======================  ===========================  =======================

Bit-exactness contract
----------------------
In fp32 ``impl="decomposed"`` is bit-identical (fwd AND grad) to the
ascending-rank sum of the per-block partial products, by construction
rather than by tolerance:

* the decomposed reduce-scatter delivers each partial block directly to its
  destination (per-step shifted ppermutes), buffers them by *source rank*,
  and performs one ordered left-to-right summation, whatever order the
  blocks arrived in.
* gathers are pure data movement and cannot perturb bits, so the gather
  forms (``all_gather_matmul``, and ``copy_matmul``'s forward) equal their
  monolithic counterparts exactly.

Against ``impl="monolithic"`` the reduce forms promise 2 ulp (of the
result's largest value: a sum that cancels keeps its terms' rounding), not
the bit:
``psum`` / ``psum_scatter`` add in an order of XLA's choosing, and the
monolithic path multiplies the whole sequence at once, where a backend's
product of a row block need not be the rows of the whole product to the
last bit (the CPU backend's is not at blocks of two rows; every case the
tests hold bit-equal at tp 2 and 4 is so by that backend's grace).
``tests/test_collective_matmul.py`` holds both: the ordered sum exactly,
the monolithic collective to 2 ulp forward and to rounding in the grads.

Below 32 bits (bf16 compute, full-precision wire) there is no order to
reproduce, and the reduce-scatter ring adds as it forwards
(``_sums_in_transit``, ``_mm_rs_forwarding``): neighbour hops alone, no
buffer, float32 adds rounded to the compute dtype once a hop.

Bidirectional (two-stream) variants split the ring into clockwise and
counter-clockwise halves for even axis sizes — each shard travels at most
``n/2`` hops instead of ``n-1``, halving ring latency on bidirectional ICI
links. The buffered ordered summation makes the fp32 result independent of
the streaming direction, so uni/bidi are bit-identical too.

Quantized wire format (activation-collective compression)
----------------------------------------------------------
Every primitive takes an optional ``wire`` :class:`CompressionConfig`
(frozen/hashable → a static ``custom_vjp`` nondiff arg, never a
recompile). When quantized, the ring payloads — gathered shards in the AG
ring, per-destination partial blocks in the RS ring, and the cotangent
rings of every backward dual — ship as blockwise int8/fp8 values plus
per-block fp32 scales (the shared :mod:`..parallel.wire_codec`, the same
quantizer the gradient collectives use). Payloads keep their original
tensor layout (``encode_payload``: trailing-dim blocks, no flattening), so
block boundaries land at identical trailing-dim offsets in the decomposed
ring and the quantized monolithic fallback, making the two *bitwise*
equal: each source's contribution is ``DQ(Q(p))`` either way, and the
reduce-scatter's ascending-rank accumulation happens in the dequantized
domain in both. ``wire=None`` (or an fp32 config) leaves every code path
byte-identical to the uncompressed module. Cross-step error-feedback
residue for the gathered activation payload threads through
``all_gather_matmul(..., error=)`` exactly like the gradient collectives'
``comm_error`` (see docs/comm_compression.md).

Fallback
--------
Decomposition needs the scattered/pipelined dim to tile evenly over the
axis (and a gather/scatter dim distinct from the contraction dim). When it
doesn't — e.g. the serving engine's single-token decode steps — every
entry point silently falls back to the monolithic path instead of raising;
``will_decompose`` exposes the decision for tests and benchmarks. With a
quantized ``wire`` the monolithic fallbacks stay compressed (codec-encoded
gather / all-to-all reduce-scatter / flat quantized all-reduce) whenever
the shape allows, and silently stay full-precision otherwise — never an
error, never a recompile. The layer-level auto knob (``overlap_comm=None``)
additionally requires the axis size to be ≥ ``MIN_AUTO_AXIS_SIZE`` — below
that a ring is all latency and no pipelining.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel import comm
from ..parallel import comm_compressed
from ..parallel import mesh as ps
from ..parallel.wire_codec import (CompressionConfig, decode_payload,
                                   encode_payload, payload_wire_bytes)

Array = jax.Array
Kernels = Union[Array, Sequence[Array]]


def _record_act_wire(kind: str, shape: Tuple[int, ...],
                     wire: Optional[CompressionConfig],
                     passes: float) -> None:
    """Traced-bytes accounting for one activation collective: ``shape``
    is the per-hop payload, ``passes`` the number of ring hops (or
    monolithic-equivalent passes). Runs in the public wrapper at trace
    time — never inside the compiled program (the custom_vjp internals
    are traced code; a tap there would be flagged by nxdlint and would
    double-count the per-chunk codec calls)."""
    from ..obs.accounting import record_wire_bytes
    from ..obs.metrics import get_registry

    if not get_registry().enabled:
        return
    m = 1
    for d in shape:
        m *= int(d)
    wire_b = payload_wire_bytes(shape, wire) * passes
    raw_b = 4.0 * m * passes
    record_wire_bytes(kind, wire.dtype if wire is not None else "fp32",
                      wire_b, raw_b)


def _record_decision(op: str, decomposed: bool) -> None:
    """One call site's decision, counted at trace time like
    :func:`_record_act_wire` (once per trace of the site — a layer scan's
    body is one site — never per execution):
    ``nxd_tp_collective_matmuls_total{impl, op}``."""
    from ..obs.metrics import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    reg.counter("nxd_tp_collective_matmuls_total",
                "Collective-matmul call sites traced over a bound tp axis, "
                "by the ring decision taken (counted once per trace).",
                labels=("impl", "op")).labels(
                    impl="decomposed" if decomposed else "monolithic",
                    op=op).inc()


#: auto mode (``overlap_comm=None``) engages only at axis sizes where the
#: ring has enough steps to pipeline; below this the monolithic collective
#: is at least as good.
MIN_AUTO_AXIS_SIZE = 4

_IMPLS = ("auto", "decomposed", "monolithic")


# ---------------------------------------------------------------------------
# shape/impl resolution
# ---------------------------------------------------------------------------

def _norm_dim(dim: int, ndim: int) -> int:
    return dim % ndim


def _dim_ok(shape: Tuple[int, ...], dim: int) -> bool:
    """The streamed dim must exist and precede the (last) contraction dim."""
    if len(shape) < 2:
        return False
    return _norm_dim(dim, len(shape)) < len(shape) - 1


def shapes_tile(x_shape: Tuple[int, ...], dim: int,
                axis_size: Optional[int], *,
                needs_divisible: bool) -> bool:
    """Pure shape-tiling predicate behind :func:`will_decompose` /
    :func:`overlap_engaged`.

    True when a ring of ``axis_size`` steps can stream ``x`` along ``dim``:
    the dim must exist and precede the (last) contraction dim, and — for the
    scatter/delivery forms (``needs_divisible=True``) — tile evenly over the
    axis. Takes the axis SIZE, not an axis name, so callers that have no
    bound mesh axis (the placement planner, the ``plan`` lint rule) share
    this exact rule instead of duplicating it. ``axis_size`` of None (axis
    unbound) or ≤ 1 never tiles.
    """
    if axis_size is None or axis_size <= 1:
        return False
    if not _dim_ok(tuple(x_shape), dim):
        return False
    if needs_divisible and x_shape[_norm_dim(dim, len(x_shape))] % axis_size:
        return False
    return True


def will_decompose(impl: str, axis, x_shape: Tuple[int, ...], dim: int,
                   *, needs_divisible: bool) -> bool:
    """Whether the decomposed ring will actually run for this call.

    False means the monolithic path is used — never an error. Mirrors the
    in-op resolution so tests/bench can assert engagement.
    """
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    if impl == "monolithic":
        return False
    return shapes_tile(x_shape, dim, comm._axis_size(axis),
                       needs_divisible=needs_divisible)


def _resolve_bidi(bidirectional: Optional[bool], n: int) -> bool:
    """Two-stream ring only for even axis sizes (auto: even and ≥ 4; at
    n = 4 on a v5e 2x2 the train cell's step read 386.2 ms in two streams
    and 399.4 in one: PERF.md, PR 47)."""
    if bidirectional is None:
        return n % 2 == 0 and n >= 4
    return bool(bidirectional) and n % 2 == 0


def overlap_engaged_at(overlap_comm: Optional[bool],
                       axis_size: Optional[int],
                       x_shape: Tuple[int, ...], dim: int, *,
                       needs_divisible: bool) -> bool:
    """:func:`overlap_engaged` by axis SIZE: the rule itself, for callers
    that decide before the axis is bound (the trainer binds the tp axis
    exactly when the layers' rings would then engage)."""
    if overlap_comm is False:
        return False
    if not shapes_tile(x_shape, dim, axis_size,
                       needs_divisible=needs_divisible):
        return False
    if overlap_comm is None:
        return axis_size >= MIN_AUTO_AXIS_SIZE
    return True


def overlap_engaged(overlap_comm: Optional[bool], axis,
                    x_shape: Tuple[int, ...], dim: int, *,
                    needs_divisible: bool) -> bool:
    """Layer-level engagement decision for the ``overlap_comm`` knob.

    ``None`` (auto): on when the axis is bound with size ≥
    ``MIN_AUTO_AXIS_SIZE`` and the shapes tile; ``True``: on whenever the
    shapes tile (never an error — non-tileable shapes fall back);
    ``False``: off.
    """
    return overlap_engaged_at(overlap_comm, comm._axis_size(axis), x_shape,
                              dim, needs_divisible=needs_divisible)


# ---------------------------------------------------------------------------
# wire compression + reduced-sync knobs
# ---------------------------------------------------------------------------

def wire_config(dtype: Optional[str],
                block_size: int = 256) -> Optional[CompressionConfig]:
    """Activation-wire config for the ``wire=`` argument of every primitive
    here: None (no compression) for ``None``/``"fp32"``, else a hashable
    :class:`CompressionConfig` (``hierarchical``/``error_feedback`` are
    gradient-side concepts and stay off)."""
    if not dtype or dtype == "fp32":
        return None
    return CompressionConfig(dtype=dtype, block_size=int(block_size),
                             hierarchical=False, error_feedback=False)


def _norm_wire(wire: Optional[CompressionConfig]
               ) -> Optional[CompressionConfig]:
    return wire if (wire is not None and wire.quantized) else None


def tp_sync_schedule(num_layers: int,
                     sync_fraction: float) -> Tuple[bool, ...]:
    """Static per-layer schedule for reduced-sync TP (PAPERS.md
    "Tensor-Parallelism with Partially Synchronized Activations").

    ``sync_fraction`` ∈ (0, 1] is the fraction of decoder layers whose
    row-parallel exits run the full all-reduce; the rest elide it (each
    rank keeps its local partial product) and are compensated by the
    periodic residual resync the model inserts before every synced layer.
    Entry ``i`` True → layer ``i`` syncs. 1.0 → all layers sync (the
    schedule is the identity and no resync machinery is built). Synced
    layers are evenly spaced with period ``round(1/f)`` and the last layer
    always syncs so the final norm / lm-head see a fully synchronized
    residual stream. Pure and static — the schedule is baked into the
    compiled program, never a traced branch."""
    if not 0.0 < sync_fraction <= 1.0:
        raise ValueError(
            f"activation_sync_fraction must be in (0, 1], got "
            f"{sync_fraction!r}")
    if num_layers <= 0:
        return ()
    if sync_fraction >= 1.0:
        return (True,) * num_layers
    k = max(1, int(round(1.0 / sync_fraction)))
    sched = [(i % k) == (k - 1) for i in range(num_layers)]
    sched[-1] = True
    return tuple(sched)


# ---------------------------------------------------------------------------
# contraction helpers (shared by both impls so the arithmetic is identical)
# ---------------------------------------------------------------------------

def _as_tuple(ws: Kernels) -> Tuple[Array, ...]:
    if isinstance(ws, (tuple, list)):
        return tuple(ws)
    return (ws,)


def _contract(x: Array, w: Array) -> Array:
    """``x [..., K] × w [K, *rest] -> [..., *rest]`` (last-dim contraction,
    the layout every TP linear in this codebase uses)."""
    return jnp.tensordot(x, w, axes=((x.ndim - 1,), (0,)))


def _contract_sum(xs: Sequence[Array], ws: Sequence[Array]) -> Array:
    """``sum_i xs[i] @ ws[i]`` with a fixed left-to-right pair order."""
    out = _contract(xs[0], ws[0])
    for x, w in zip(xs[1:], ws[1:]):
        out = out + _contract(x, w)
    return out


def _ordered_sum(buf: Array, n: int) -> Array:
    """Left-to-right ascending-source-rank summation of a ``[n, ...]``
    contribution buffer. Callers must materialize the contributions into
    ``buf`` via ``dynamic_update_slice`` stores *before* calling: a DUS
    buffer forces the dequantization multiply to be computed to memory, so
    the backend cannot contract it into the accumulation adds as an fma
    (an optimization_barrier alone does NOT stop LLVM's fp contraction on
    CPU). The adds are then pure fp32 adds in program order, bitwise
    identical whichever program (ring or monolithic all-to-all) produced
    the buffer."""
    buf = lax.optimization_barrier(buf)
    acc = buf[0]
    for r in range(1, n):
        acc = acc + buf[r]
    return acc


def _flat_t(w: Array) -> Array:
    """``w [K, *rest] -> w^T [prod(rest), K]`` for the dual contraction."""
    return w.reshape(w.shape[0], -1).T


def _flat_rest(g: Array, w: Array) -> Array:
    """Collapse ``g``'s trailing ``rest`` dims (matching ``w [K, *rest]``)
    to one: ``[..., L, *rest] -> [..., L, R]``."""
    lead = g.ndim - (w.ndim - 1)
    return g.reshape(g.shape[:lead] + (-1,))


def _dkernel(x_full: Array, g: Array, w_shape: Tuple[int, ...]) -> Array:
    """``dw = x_full^T · g`` contracting every leading dim (batch + the
    gathered dim); one flattened matmul, identical for both impls."""
    k = x_full.shape[-1]
    xf = x_full.reshape(-1, k)
    gf = g.reshape(xf.shape[0], -1)
    return jnp.tensordot(xf, gf, axes=((0,), (0,))).reshape(w_shape)


# ---------------------------------------------------------------------------
# decomposed rings
# ---------------------------------------------------------------------------

def _shift_perm(n: int, shift: int):
    """ppermute pairs moving every shard ``shift`` ranks forward."""
    return [(i, (i + shift) % n) for i in range(n)]


def _ship(pair, axis, perm):
    """ppermute a ``(q, scales)`` wire pair one ring step; scales are
    absent (None) on the fp path, which then matches the uncompressed ring
    byte-for-byte."""
    q, s = pair
    q = comm.ppermute(q, axis, perm)
    if s is not None:
        s = comm.ppermute(s, axis, perm)
    return q, s


def _open(pair, wire, dtype):
    """Dequantize a received wire pair back into compute dtype (identity
    on the fp path)."""
    q, s = pair
    return decode_payload(q, s, wire, dtype)


def _quantized_all_gather(v: Array, axis, dim: int,
                          wire: Optional[CompressionConfig]) -> Array:
    """Monolithic all-gather with the payload codec-encoded on the wire.
    Every rank encodes identically and gathers are pure movement, so the
    result equals the ring's ``DQ(Q(shard))`` concatenation bitwise."""
    if wire is None:
        return comm.all_gather(v, axis, dim)
    q, s = encode_payload(v, wire)
    qg = comm.all_gather(q, axis, dim)
    sg = comm.all_gather(s, axis, dim)
    return decode_payload(qg, sg, wire, v.dtype)


def _quantized_all_reduce(v: Array, axis,
                          wire: Optional[CompressionConfig]) -> Array:
    """Monolithic all-reduce fallback: the codec's flat quantized
    all-reduce (works for any shape via block padding); plain ``psum``
    when uncompressed."""
    if wire is None:
        return comm.all_reduce(v, axis)
    return comm_compressed.all_reduce(
        v, axis, config=dataclasses.replace(wire, hierarchical=False),
        op="sum")


def _ag_matmul_decomposed(x: Array, ws: Tuple[Array, ...], axis, dim: int,
                          bidi: bool,
                          wire: Optional[CompressionConfig]
                          ) -> Tuple[Array, ...]:
    """Ring all-gather-matmul: remote shards stream around the ring while
    each step's block matmul (independent of the in-flight transfer) runs.
    With a quantized ``wire`` each rank encodes its shard ONCE and the
    ``(q, scales)`` pair circulates — one quantization per shard total,
    exactly what the monolithic quantized gather ships."""
    n = comm._axis_size(axis)
    idx = lax.axis_index(axis)
    dim = _norm_dim(dim, x.ndim)
    l = x.shape[dim]

    pair = encode_payload(x, wire)
    # the own block round-trips through DQ(Q(·)) too: every rank then
    # contracts identical gathered values, matching the monolithic path
    # bitwise (fp wire: encode/open are identities and this is just x)
    own = _open(pair, wire, x.dtype)

    # The output's n blocks are one dimension of their own while the ring
    # runs, ``[..., n, l, ...]``: a product is one index of it, so the
    # matmul's output fusion writes the block where it stays (XLA updates
    # a slice of a middle dimension, ``[..., n * l, ...]`` at ``src * l``,
    # by copying the whole output, once a hop), and the reshape at the end
    # moves nothing. lax primitives: a ring is traced a dozen times a step.
    zero = np.int32(0)

    def place(bufs, chunk, ahead):
        """``chunk``'s products, the shard of the rank ``ahead`` ranks on,
        into their block of every output (made at the first)."""
        src = lax.rem(lax.add(idx, np.int32(ahead)), np.int32(n))
        out = []
        for buf, w in zip(bufs, ws):
            p = _contract(chunk, w)
            block = p.shape[:dim] + (1,) + p.shape[dim:]
            if buf is None:
                buf = lax.full(p.shape[:dim] + (n,) + p.shape[dim:], 0,
                               p.dtype)
            starts = [zero] * len(block)
            starts[dim] = src
            out.append(lax.dynamic_update_slice_p.bind(
                buf, lax.reshape(p, block), *starts))
        return out

    bufs = place([None] * len(ws), own, 0)  # own block first: no transfer
    if not bidi:
        for t in range(1, n):
            # receive the next shard from the right neighbour; the matmul
            # below consumes the *previous* chunk's successor, so transfer
            # t+1 can fly while block t multiplies
            pair = _ship(pair, axis, _shift_perm(n, -1))
            bufs = place(bufs, _open(pair, wire, x.dtype), t)
    else:
        fwd = bwd = pair
        for t in range(1, n // 2 + 1):
            fwd = _ship(fwd, axis, _shift_perm(n, -1))
            bufs = place(bufs, _open(fwd, wire, x.dtype), t)
            if t != n - t:  # at t == n/2 both streams carry the same shard
                bwd = _ship(bwd, axis, _shift_perm(n, +1))
                bufs = place(bufs, _open(bwd, wire, x.dtype), n - t)
    return tuple(
        lax.reshape(b, b.shape[:dim] + (n * l,) + b.shape[dim + 2:])
        for b in bufs)


def _ag_matmul_monolithic(x: Array, ws: Tuple[Array, ...], axis, dim: int,
                          wire: Optional[CompressionConfig]
                          ) -> Tuple[Array, ...]:
    xg = _quantized_all_gather(x, axis, _norm_dim(dim, x.ndim), wire)
    return tuple(_contract(xg, w) for w in ws)


def _delivery_shifts(n: int, bidi: bool) -> Tuple[int, ...]:
    """The reduce-scatter ring's hops in issue order: the block for the
    rank ``shift`` ahead ships by one shift-``shift`` ppermute. One stream
    walks the distances up; two alternate ahead and behind, so the nearest
    neighbours' blocks leave first in both directions."""
    if not bidi:
        return tuple(range(1, n))
    shifts = []
    for t in range(1, n // 2 + 1):
        shifts.append(t)
        if t != n - t:
            shifts.append(-t)
    return tuple(shifts)


def _sums_in_transit(dtype, wire: Optional[CompressionConfig]) -> bool:
    """Whether the reduce-scatter ring adds as it forwards
    (:func:`_mm_rs_forwarding`) instead of delivering every partial to its
    owner and buffering by source rank: below 32 bits on a full-precision
    wire, where there is no order to reproduce (the float32 contract and
    the quantized wire's dequantized-domain order keep the buffer)."""
    return wire is None and jnp.dtype(dtype).itemsize < 4


def _mm_rs_forwarding(block, n: int, l: int, axis, dim: int,
                      bidi: bool) -> Array:
    """Accumulate-and-forward ring matmul-reduce-scatter over
    ``block(k, lo, size)``, rows ``lo:lo+size`` of this rank's partial
    product for the rank ``k`` ahead of it: a destination's block starts at
    the rank after it, and every rank on the way adds its own partial
    product and passes the sum to its neighbour — ``n - 1`` neighbour hops
    of one block a link, where direct delivery's shifts of two and more
    load a link once a hop (at n = 4 on a 2x2 ring: 1.0 ms of wire a set of
    [2, 1024, 4096] bf16 blocks against 0.5). Two streams split
    every block's rows, one half a direction. Each hop needs the sum that
    arrived and a product that did not wait for it: the barrier keeps XLA
    from fusing the add into the matmul, which would hold the matmul back
    until the hop before it lands. Sums travel in the compute dtype (one
    rounding a hop, added in float32)."""
    f32 = jnp.float32

    def summed(a, b):
        # lax, not jnp operators: a ring is traced a dozen times a step
        # (forward, recomputation, each dual) and the wrappers' cost is
        # the step's start-up
        return lax.convert_element_type(
            lax.add(lax.convert_element_type(a, f32),
                    lax.convert_element_type(b, f32)), b.dtype)

    def stream(sign, lo, size):
        acc = None
        for t in range(1, n):
            p = lax.optimization_barrier(block(-sign * t, lo, size))
            if acc is not None:
                p = summed(acc, p)
            acc = comm.ppermute(p, axis, _shift_perm(n, sign))
        return summed(acc, lax.optimization_barrier(block(0, lo, size)))

    if bidi and l % 2 == 0:
        return jnp.concatenate(
            [stream(+1, 0, l // 2), stream(-1, l // 2, l // 2)], axis=dim)
    return stream(+1, 0, l)


def _rows(x: Array, start, size: int, dim: int) -> Array:
    """``lax.dynamic_slice_in_dim`` at an int32 ``start`` known to be in
    range, bound as the primitive: the wrapper's index normalisation is
    1.5 ms of tracing a call, 96 calls a train step."""
    zero = np.int32(0)
    starts = [zero] * x.ndim
    starts[dim] = start
    sizes = list(x.shape)
    sizes[dim] = size
    return lax.dynamic_slice_p.bind(x, *starts, slice_sizes=tuple(sizes))


def _mm_rs_decomposed(xs: Tuple[Array, ...], ws: Tuple[Array, ...], axis,
                      dim: int, bidi: bool,
                      wire: Optional[CompressionConfig]) -> Array:
    """Ring matmul-reduce-scatter: each destination's partial block is
    computed, shipped straight to its owner (shift-``t`` ppermute),
    buffered by source rank, and summed once left-to-right in ascending
    rank order. With a quantized ``wire`` each partial block is encoded
    before its ppermute and the accumulation happens in the dequantized
    domain, preserving that same ascending-rank order. Below 32 bits on a
    full-precision wire the ring forwards sums instead
    (:func:`_mm_rs_forwarding`)."""
    n = comm._axis_size(axis)
    idx = lax.axis_index(axis)
    dim = _norm_dim(dim, xs[0].ndim)
    big = xs[0].shape[dim]
    l = big // n

    def block(ahead, lo=0, size=l):
        """Rows ``lo:lo+size`` of the partial block for the rank ``ahead``
        ranks on (lax index arithmetic: see ``_mm_rs_forwarding``)."""
        start = lax.add(
            lax.mul(lax.rem(lax.add(idx, np.int32(ahead % n)), np.int32(n)),
                    np.int32(l)), np.int32(lo))
        parts = [_rows(x, start, size, dim) for x in xs]
        return _contract_sum(parts, ws)

    if _sums_in_transit(jnp.result_type(xs[0], ws[0]), wire):
        return _mm_rs_forwarding(block, n, l, axis, dim, bidi)
    p_own = block(0)
    dt = p_own.dtype
    # the own partial round-trips through DQ(Q(·)) like every shipped one,
    # so rank position doesn't change which contributions are exact —
    # identical to the quantized monolithic all-to-all (fp: identity)
    own = _open(encode_payload(p_own, wire), wire, dt)
    buf = jnp.zeros((n,) + own.shape, own.dtype)

    def store(buf, p, src):
        return lax.dynamic_update_slice(
            buf, p[None], (src,) + (0,) * p.ndim)

    buf = store(buf, own, idx)
    for shift in _delivery_shifts(n, bidi):
        p = encode_payload(block(shift), wire)
        p = _ship(p, axis, _shift_perm(n, shift))
        buf = store(buf, _open(p, wire, dt), (idx - shift) % n)
    return _ordered_sum(buf, n)


def _mm_rs_monolithic(xs: Tuple[Array, ...], ws: Tuple[Array, ...], axis,
                      dim: int,
                      wire: Optional[CompressionConfig]) -> Array:
    y = _contract_sum(list(xs), list(ws))
    dim = _norm_dim(dim, y.ndim)
    if wire is None:
        return comm.reduce_scatter(y, axis, dim)
    names = comm._bound_names(axis)
    n = comm._axis_size(axis)
    if not names or n is None or n == 1:
        return y
    if y.shape[dim] % n:
        # can't form per-destination blocks; the fp collective has the
        # same divisibility contract and raises the pointed error
        return comm.reduce_scatter(y, axis, dim)
    ax = names if len(names) > 1 else names[0]
    # stack the n destination slices, quantize each (trailing-dim blocks —
    # slicing a non-trailing dim never moves a block boundary, so these
    # are the ring's per-destination partials bit-for-bit), all-to-all the
    # wire pair, and sum the received contributions in ascending source
    # rank order in the dequantized domain: bitwise equal to the ring.
    lead = jnp.moveaxis(y, dim, 0)
    stacked = lead.reshape((n, lead.shape[0] // n) + lead.shape[1:])
    q, s = encode_payload(stacked, wire)
    qr = lax.all_to_all(q, ax, split_axis=0, concat_axis=0, tiled=True)
    sr = lax.all_to_all(s, ax, split_axis=0, concat_axis=0, tiled=True)
    dq = decode_payload(qr, sr, wire, y.dtype)
    # Materialize each source's contribution into the ring's contribution
    # buffer (output layout, dynamic_update_slice per source) before the
    # ordered sum. The DUS buffer forces the dequantize multiply to
    # materialize, so XLA cannot contract it into the accumulation adds as
    # an fma here while leaving the ring's adds uncontracted — both
    # programs then perform identical mul-then-add arithmetic.
    first = jnp.moveaxis(dq[0], 0, dim)
    buf = jnp.zeros((n,) + first.shape, first.dtype)
    for r in range(n):
        piece = first if r == 0 else jnp.moveaxis(dq[r], 0, dim)
        buf = lax.dynamic_update_slice(
            buf, piece[None], (r,) + (0,) * piece.ndim)
    return _ordered_sum(buf, n)


def _mm_rs_impl(xs, ws, axis, dim, decomposed, bidi, wire):
    if decomposed:
        return _mm_rs_decomposed(xs, ws, axis, dim, bidi, wire)
    return _mm_rs_monolithic(xs, ws, axis, dim, wire)


def _ag_matmul_impl(x, ws, axis, dim, decomposed, bidi, wire):
    if decomposed:
        return _ag_matmul_decomposed(x, ws, axis, dim, bidi, wire)
    return _ag_matmul_monolithic(x, ws, axis, dim, wire)


# ---------------------------------------------------------------------------
# custom_vjp primitives (dual decomposition in the backward)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _ag_matmul(x, ws, axis, dim, decomposed, bidi, wire):
    return _ag_matmul_impl(x, ws, axis, dim, decomposed, bidi, wire)


def _ag_matmul_fwd(x, ws, axis, dim, decomposed, bidi, wire):
    return _ag_matmul_impl(x, ws, axis, dim, decomposed, bidi, wire), (x, ws)


def _ag_matmul_bwd(axis, dim, decomposed, bidi, wire, res, gs):
    x, ws = res
    # dx: the dual — partial input-grads reduce-scattered back onto the
    # gathered dim, overlapped (and wire-quantized) when the forward was
    g2s = tuple(_flat_rest(g, w) for g, w in zip(gs, ws))
    wts = tuple(_flat_t(w) for w in ws)
    dx = _mm_rs_impl(g2s, wts, axis, dim, decomposed, bidi, wire)
    dx = dx.astype(x.dtype)
    # dw: needs the gathered input; quantized, the re-gather reconstructs
    # the same DQ(Q(x)) the forward contracted, so dw differentiates the
    # function the forward actually computed
    x_full = _quantized_all_gather(x, axis, _norm_dim(dim, x.ndim), wire)
    dws = tuple(_dkernel(x_full, g, w.shape).astype(w.dtype)
                for g, w in zip(gs, ws))
    return dx, dws


_ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _mm_rs(x, w, axis, dim, decomposed, bidi, wire):
    return _mm_rs_impl((x,), (w,), axis, dim, decomposed, bidi, wire)


def _mm_rs_fwd(x, w, axis, dim, decomposed, bidi, wire):
    return _mm_rs_impl((x,), (w,), axis, dim, decomposed, bidi, wire), (x, w)


def _mm_rs_bwd(axis, dim, decomposed, bidi, wire, res, g):
    x, w = res
    # dx: all-gather-matmul of the scattered cotangent against w^T (the
    # cotangent payload rides the same quantized wire — straight-through
    # w.r.t. the forward's quantizer, see docs/tp_overlap.md)
    g2 = _flat_rest(g, w)
    (dx,) = _ag_matmul_impl(g2, (_flat_t(w),), axis, dim, decomposed, bidi,
                            wire)
    dx = dx.astype(x.dtype)
    g_full = _quantized_all_gather(g, axis, _norm_dim(dim, g.ndim), wire)
    dw = _dkernel(x, g_full, w.shape).astype(w.dtype)
    return dx, dw


_mm_rs.defvjp(_mm_rs_fwd, _mm_rs_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _mm_ar(x, w, axis, dim, decomposed, bidi, wire):
    if decomposed:
        y = _mm_rs_decomposed((x,), (w,), axis, dim, bidi, wire)
        return _quantized_all_gather(y, axis, _norm_dim(dim, y.ndim), wire)
    return _quantized_all_reduce(_contract(x, w), axis, wire)


def _mm_ar_fwd(x, w, axis, dim, decomposed, bidi, wire):
    return _mm_ar(x, w, axis, dim, decomposed, bidi, wire), (x, w)


def _mm_ar_bwd(axis, dim, decomposed, bidi, wire, res, g):
    x, w = res
    # the all-reduce's cotangent is replicated: dx needs no collective
    # (identical formula both impls — cf. reduce_from_tensor_parallel_region
    # whose backward is the identity)
    dx = _contract(_flat_rest(g, w), _flat_t(w)).astype(x.dtype)
    dw = _dkernel(x, g, w.shape).astype(w.dtype)
    return dx, dw


_mm_ar.defvjp(_mm_ar_fwd, _mm_ar_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _copy_mm(x, ws, axis, dim, decomposed, bidi, wire):
    return tuple(_contract(x, w) for w in ws)


def _copy_mm_fwd(x, ws, axis, dim, decomposed, bidi, wire):
    return tuple(_contract(x, w) for w in ws), (x, ws)


def _copy_mm_bwd(axis, dim, decomposed, bidi, wire, res, gs):
    x, ws = res
    # dx = psum(sum_i g_i w_i^T): decomposed as reduce-scatter (overlapped
    # with the per-block matmuls) + all-gather, cotangents wire-quantized
    g2s = tuple(_flat_rest(g, w) for g, w in zip(gs, ws))
    wts = tuple(_flat_t(w) for w in ws)
    if decomposed:
        dx = _mm_rs_decomposed(g2s, wts, axis, dim, bidi, wire)
        dx = _quantized_all_gather(dx, axis, _norm_dim(dim, dx.ndim), wire)
    else:
        dx = _quantized_all_reduce(_contract_sum(g2s, wts), axis, wire)
    dx = dx.astype(x.dtype)
    # kernels are axis-sharded: dw is local (x is replicated)
    dws = tuple(_dkernel(x, g, w.shape).astype(w.dtype)
                for g, w in zip(gs, ws))
    return dx, dws


_copy_mm.defvjp(_copy_mm_fwd, _copy_mm_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _prep(impl: str, axis, x: Array, dim: int, needs_divisible: bool,
          bidirectional: Optional[bool]):
    decomposed = will_decompose(impl, axis, x.shape, dim,
                                needs_divisible=needs_divisible)
    n = comm._axis_size(axis) or 1
    return decomposed, (_resolve_bidi(bidirectional, n) if decomposed
                        else False)


def _unwrap(outs: Tuple[Array, ...], kernels: Kernels):
    if isinstance(kernels, (tuple, list)):
        return outs
    return outs[0]


def _scatter_block_shape(x: Array, kernel: Array, dim: int,
                         n: int) -> Tuple[int, ...]:
    """Per-hop payload shape of a matmul-RS/AR: the output block destined
    for one rank — ``x @ kernel``'s shape with ``dim`` cut by ``n``."""
    y_shape = tuple(x.shape[:-1]) + tuple(kernel.shape[1:])
    d = dim % len(y_shape)
    return tuple(max(1, s // n) if i == d else s
                 for i, s in enumerate(y_shape))


def all_gather_matmul(x: Array, kernels: Kernels, axis=ps.TP_AXIS,
                      gather_dim: int = 1, *, impl: str = "auto",
                      bidirectional: Optional[bool] = None,
                      wire: Optional[CompressionConfig] = None,
                      error: Optional[Array] = None):
    """``all_gather(x, gather_dim) @ w`` for one kernel or a fused tuple
    (e.g. Q/K/V share one gathered stream), decomposed into a ppermute
    ring. ``x [..., gather_dim: l_local, ..., K]``, each kernel
    ``[K, *rest]``; returns ``[..., n*l_local, ..., *rest]`` per kernel.

    The sequence-parallel entry of a column-parallel linear. Backward:
    ``dx`` is a (decomposed) matmul-reduce-scatter, ``dw`` a re-gather +
    single flattened matmul. A quantized ``wire`` codec-encodes the ring
    payloads (fwd shards AND bwd cotangents).

    ``error`` threads cross-step error feedback for the gathered payload —
    the same contract as the gradient collectives' ``comm_error``: pass
    last step's residue buffer (``x``'s shape, fp32) and the return becomes
    ``(out, new_error)`` where ``new_error = (x + e) − DQ(Q(x + e))``.
    The residue is stop-gradiented state, not a differentiable input.
    """
    ws = _as_tuple(kernels)
    wire = _norm_wire(wire)
    decomposed, bidi = _prep(impl, axis, x, gather_dim, False, bidirectional)
    n = comm._axis_size(axis)
    if n is None or n <= 1:
        out = _unwrap(tuple(_contract(x, w) for w in ws), kernels)
        return (out, error) if error is not None else out
    new_error = None
    if error is not None:
        if wire is None:
            new_error = jnp.zeros_like(error)
        else:
            x = x + lax.stop_gradient(error).astype(x.dtype)
            q, s = encode_payload(lax.stop_gradient(x), wire)
            dq = decode_payload(q, s, wire, jnp.float32)
            new_error = lax.stop_gradient(
                x.astype(jnp.float32) - dq).astype(error.dtype)
    # ring: each rank's shard takes n-1 hops (monolithic AG moves the same)
    _record_act_wire("act_all_gather_matmul", tuple(x.shape), wire, n - 1)
    _record_decision("all_gather_matmul", decomposed)
    out = _unwrap(_ag_matmul(x, ws, axis, gather_dim, decomposed, bidi,
                             wire), kernels)
    return (out, new_error) if error is not None else out


def matmul_reduce_scatter(x: Array, kernel: Array, axis=ps.TP_AXIS,
                          scatter_dim: int = 1, *, impl: str = "auto",
                          bidirectional: Optional[bool] = None,
                          wire: Optional[CompressionConfig] = None) -> Array:
    """``reduce_scatter(x @ kernel, scatter_dim)`` decomposed so each
    destination's partial block ships while the next block multiplies.

    The sequence-parallel exit of a row-parallel linear. Requires
    ``x.shape[scatter_dim] % axis_size == 0`` to decompose; falls back to
    the monolithic collective otherwise (never an error). A quantized
    ``wire`` encodes each partial block before its ppermute (or the
    all-to-all fallback) — accumulation stays in the dequantized domain in
    ascending rank order.
    """
    wire = _norm_wire(wire)
    decomposed, bidi = _prep(impl, axis, x, scatter_dim, True, bidirectional)
    n = comm._axis_size(axis)
    if n is None or n <= 1:
        return _contract(x, kernel)
    _record_act_wire("act_matmul_reduce_scatter",
                     _scatter_block_shape(x, kernel, scatter_dim, n),
                     wire, n - 1)
    _record_decision("matmul_reduce_scatter", decomposed)
    return _mm_rs(x, kernel, axis, scatter_dim, decomposed, bidi, wire)


def matmul_all_reduce(x: Array, kernel: Array, axis=ps.TP_AXIS,
                      pipeline_dim: int = 1, *, impl: str = "auto",
                      bidirectional: Optional[bool] = None,
                      wire: Optional[CompressionConfig] = None) -> Array:
    """``all_reduce(x @ kernel)`` decomposed as matmul-reduce-scatter over
    ``pipeline_dim`` (overlapped) followed by an all-gather (movement).

    The plain-TP exit of a row-parallel linear. A quantized ``wire``
    compresses both legs when decomposed, and falls back to the codec's
    flat quantized all-reduce monolithically (any shape — the serving
    engine's single-token decode steps stay compressed AND compile-once).
    """
    wire = _norm_wire(wire)
    decomposed, bidi = _prep(impl, axis, x, pipeline_dim, True, bidirectional)
    n = comm._axis_size(axis)
    if n is None or n <= 1:
        return _contract(x, kernel)
    # RS leg + AG leg, each n-1 hops over the same per-destination block
    _record_act_wire("act_matmul_all_reduce",
                     _scatter_block_shape(x, kernel, pipeline_dim, n),
                     wire, 2 * (n - 1))
    _record_decision("matmul_all_reduce", decomposed)
    return _mm_ar(x, kernel, axis, pipeline_dim, decomposed, bidi, wire)


def copy_matmul(x: Array, kernels: Kernels, axis=ps.TP_AXIS,
                pipeline_dim: int = 1, *, impl: str = "auto",
                bidirectional: Optional[bool] = None,
                wire: Optional[CompressionConfig] = None):
    """Plain-TP column entry: forward is a local matmul on the replicated
    input (identical for both impls); the *backward* input-grad all-reduce
    is decomposed into overlapped reduce-scatter + all-gather over
    ``pipeline_dim`` (cotangents wire-quantized when ``wire`` is)."""
    ws = _as_tuple(kernels)
    wire = _norm_wire(wire)
    decomposed, bidi = _prep(impl, axis, x, pipeline_dim, True, bidirectional)
    n = comm._axis_size(axis)
    if n is None or n <= 1:
        return _unwrap(tuple(_contract(x, w) for w in ws), kernels)
    _record_decision("copy_matmul", decomposed)
    return _unwrap(_copy_mm(x, ws, axis, pipeline_dim, decomposed, bidi,
                            wire), kernels)
