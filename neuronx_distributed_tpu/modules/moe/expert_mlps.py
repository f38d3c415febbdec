"""Expert MLP banks.

Analogue of the reference's ``modules/moe/expert_mlps_v2.py``
(``ExpertMLPsV2:46``: ``forward_all_experts:366``, ``forward_all_experts_EP
:394``, ``forward_capacity_factor:484``) and the expert-fused TP layers
(``moe/moe_parallel_layers.py``: 3-D ``[E, in, out]`` column/row parallel).

TPU-native design: expert weights are stacked ``gate``, ``up`` ``[E, H, I]``
(two leaves: :mod:`..glu` says why) and ``down`` ``[E, I, H]`` tensors whose
expert dim shards over ``ep`` and whose intermediate dim shards over ``tp``
(the expert-fused column/row layers are these batched matmuls + the same
collective mappings as the 2-D layers). Dispatch is the capacity-factor
mask-einsum formulation — dense, static-shaped, MXU-friendly (the reference's
dropless/blockwise NKI path maps to a future Pallas block-sparse kernel; the
capacity path is its golden fallback, as in ``moe/blockwise.py:326``).

Expert parallelism: ``enter/exit_expert_parallel_region`` all-to-alls move
capacity slots from token shards to expert shards and back
(reference ``mappings.py:355-556``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...parallel import comm, ep_dispatch, mappings
from ...parallel import layers as pl
from ...parallel import mesh as ps
from .. import glu, layer_stack


#: the forms of an expert (:attr:`ExpertMLPs.act`)
ACTS = ("swiglu", "relu2")


def relu2(x: jax.Array) -> jax.Array:
    """The squared ReLU of an ungated expert."""
    return jnp.square(nn.relu(x))


def compute_capacity(num_tokens: int, num_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    """Per-expert capacity slots (reference capacity computation in
    ``forward_capacity_factor``)."""
    cap = int(capacity_factor * num_tokens * top_k / num_experts)
    return max(cap, top_k)


def build_dispatch_combine(
    gates: jax.Array, idx: jax.Array, num_experts: int, capacity: int,
    valid: Optional[jax.Array] = None,
    held: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity-limited dispatch/combine masks.

    gates/idx: ``[T, K]``. Returns ``(dispatch [T, E, C], combine [T, E, C],
    dropped_fraction scalar)``. Priority is choice-rank-major then token
    order (tokens beyond an expert's capacity are dropped, matching the
    reference's capacity-factor semantics). Rows that ``valid [T]`` (bool)
    marks false choose nothing: they take no slot and count neither as
    kept nor as dropped.

    ``held = (first, count)``: ``idx`` are choices among all the experts
    the router scores and the masks are over the ``count == num_experts``
    experts from ``first`` on that this device holds: a choice of an
    expert held elsewhere takes no slot, adds nothing and counts neither
    as kept nor as dropped.
    """
    t, k = idx.shape
    if held is not None:
        return _held_dispatch_combine(gates, idx, capacity, valid, held)
    choice = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)  # [T,K,E]
    if valid is not None:
        choice = choice * valid.astype(jnp.float32)[:, None, None]
    flat = jnp.transpose(choice, (1, 0, 2)).reshape(k * t, num_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.transpose(pos_flat.reshape(k, t, num_experts), (1, 0, 2))
    keep = choice * (pos < capacity)  # [T,K,E]
    pos_clipped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_clipped, capacity, dtype=jnp.float32)  # [T,K,E,C]
    dispatch = jnp.einsum("tke,tkec->tec", keep, slot)
    combine = jnp.einsum("tk,tke,tkec->tec", gates, keep, slot)
    asked = float(t * k) if valid is None else jnp.sum(choice)
    dropped = 1.0 - jnp.sum(keep) / jnp.maximum(asked, 1.0)
    return dispatch, combine, dropped


def _held_dispatch_combine(gates, idx, capacity, valid, held):
    """:func:`build_dispatch_combine` over the held experts. A choice has
    one expert and one slot, so the masks are two small one-hots' product
    (``[T, K, E]`` by ``[T, K, C]``) and never ``[T, K, E, C]``: 84 MB a
    layer at 128 rows, top-10 and 128 experts of capacity 128."""
    first, count = held
    local = idx - first
    mine = (local >= 0) & (local < count)
    if valid is not None:
        mine = mine & valid[:, None]
    # one_hot of an index out of range is zeros: no slot, no weight
    choice = jax.nn.one_hot(jnp.where(mine, local, count), count,
                            dtype=jnp.float32)                  # [T,K,E]
    t, k = idx.shape
    flat = jnp.transpose(choice, (1, 0, 2)).reshape(k * t, count)
    pos = jnp.transpose((jnp.cumsum(flat, axis=0) - flat).reshape(
        k, t, count), (1, 0, 2))
    at = jnp.sum(pos * choice, axis=-1).astype(jnp.int32)       # [T,K]
    keep = choice * (at < capacity)[:, :, None]
    slot = jax.nn.one_hot(jnp.minimum(at, capacity - 1), capacity,
                          dtype=jnp.float32)                    # [T,K,C]
    dispatch = jnp.einsum("tke,tkc->tec", keep, slot)
    combine = jnp.einsum("tk,tke,tkc->tec", gates, keep, slot)
    dropped = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(choice), 1.0)
    return dispatch, combine, dropped


def _record_operands(operands: str) -> None:
    """One grouped-product call site's operand form, counted at trace time
    (once a compiled call site, a layer scan's body being one; never per
    execution): ``nxd_moe_grouped_calls_total{operands}``."""
    from ...obs.metrics import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    reg.counter("nxd_moe_grouped_calls_total",
                "Grouped expert product call sites traced (the blockwise "
                "dispatch), by what the kernel is handed: stack, the "
                "layers' stacks [L, E, H, I] and the layer's index, read "
                "where the bank lies; slice, one layer's banks [E, H, I] "
                "(outside a layer scan, under an ep axis, or a tree stored "
                "in another dtype than the step's; under a scan XLA copies "
                "the bank out for the custom call). Counted once per "
                "trace.", labels=("operands",)).labels(
                    operands=operands).inc()


class ExpertMLPs(nn.Module):
    """Stacked GLU expert MLPs with capacity-factor dispatch, TP- and
    EP-sharded."""

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    # None: an expert's capacity is the step's rows, so nothing can drop
    capacity_factor: Optional[float] = 2.0
    # "capacity" (mask-einsum, may drop) or "blockwise" (dropless Pallas
    # grouped matmul, reference expert_mlps_v2.py:691)
    dispatch_mode: str = "capacity"
    block_size: int = 512   # tokens per block (blockwise)
    block_i: int = 512      # intermediate-dim tile (blockwise)
    # decode: skip + DMA-elide blocks of experts no token hit (forward-only;
    # see blockwise.compute_block_metadata)
    sentinel_empty: bool = False
    # EP dispatch wire dtype ("fp32" | "int8" | "fp8"): quantizes the token
    # gather + output combine payloads over ep (parallel/ep_dispatch.py)
    ep_wire_dtype: str = "fp32"
    # decomposed (ppermute-ring) EP dispatch overlapping per-chunk expert
    # compute with later hops; None = auto (ep >= MIN_AUTO_AXIS_SIZE)
    ep_overlap: Optional[bool] = None
    # ``(first, count)`` of the experts ``idx`` chooses among that this
    # bank holds (``count == num_experts``; None: ``idx`` are the bank's
    # own). Capacity dispatch under ``valid`` rows, no ep axis: the
    # exchange between devices that share a layer is not built
    held: Optional[Tuple[int, int]] = None
    # an expert's form: "swiglu", ``down(silu(gate x) * up x)``, or
    # "relu2", ungated, ``down(relu(up x)^2)``: two leaves ``up`` and
    # ``down`` and no ``gate`` (capacity dispatch)
    act: str = "swiglu"
    # with ``held``: ``aux["experts_hit"]``, the held experts that took a
    # row of the step and those that took none, ``[hit, idle]`` int32
    count_hit: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    tp_axis: str = ps.TP_AXIS
    ep_axis: str = ps.EP_AXIS

    @nn.compact
    def __call__(self, x: jax.Array, gates: jax.Array, idx: jax.Array,
                 valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict]:
        """x: [T, H] flat tokens; gates/idx: [T, K]. Returns ([T, H], aux).
        ``valid [T]`` (bool; no ep axis) marks the real rows of a packed
        serving step: under the capacity dispatch a pad row takes no
        expert's slot, and ``aux`` then holds ``assignments``, the real
        rows' ``[kept, dropped]`` int32 (with ``held``, ``[kept, dropped,
        elsewhere]``: ``dropped`` of the held experts' alone); under the
        blockwise dispatch, which drops nothing, ``[kept, 0]``."""
        t = x.shape[0]
        e_local = pl._maybe_local(self.num_experts, self.ep_axis)
        i_local = pl._maybe_local(self.intermediate_size, self.tp_axis)
        ep = comm._axis_size(self.ep_axis)

        if self.act not in ACTS or (self.act != "swiglu"
                                    and self.dispatch_mode != "capacity"):
            raise ValueError(f"ExpertMLPs: act is one of {ACTS} (other "
                             f"than swiglu: capacity dispatch), got "
                             f"{self.act!r}")
        in_axes = (self.ep_axis, None, self.tp_axis)
        in_shape = (e_local, self.hidden_size, i_local)
        if self.act == "swiglu":
            gate_up = glu.declare(self, glu.EXPERTS, pl.default_kernel_init,
                                  in_axes, in_shape, self.param_dtype)
        else:
            gate_up = (self.param("up", nn.with_partitioning(
                pl.default_kernel_init, in_axes), in_shape,
                self.param_dtype),)
        down = self.param(
            "down",
            nn.with_partitioning(pl.default_kernel_init,
                                 (self.ep_axis, self.tp_axis, None)),
            (e_local, i_local, self.hidden_size), self.param_dtype)

        if valid is not None and (
                self.dispatch_mode not in ("capacity", "blockwise")
                or (ep is not None and ep > 1)):
            raise ValueError("ExpertMLPs: valid rows are threaded through "
                             "the capacity and the blockwise dispatch "
                             "without an ep axis alone")
        if self.held is not None and (valid is None
                                      or self.dispatch_mode != "capacity"
                                      or self.held[1] != self.num_experts):
            raise ValueError("ExpertMLPs: held=(first, count) is a bank of "
                             "count experts under the packed step's valid "
                             "rows and the capacity dispatch")
        if self.dispatch_mode == "blockwise":
            if ep is not None and ep > 1:
                return self._forward_blockwise_ep(x, gates, idx, gate_up,
                                                  down, i_local, e_local)
            y, aux = self._forward_blockwise(x, gates, idx, gate_up, down,
                                             i_local)
            if valid is not None:
                # dropless: every real row's choice has a slot (a pad row's
                # has one too, and its product is the caller's to discard)
                asked = jnp.sum(valid).astype(jnp.int32) * idx.shape[1]
                aux["assignments"] = jnp.stack([asked, jnp.zeros_like(asked)])
            return y, aux
        if self.dispatch_mode != "capacity":
            raise ValueError(
                f"unknown dispatch_mode {self.dispatch_mode!r}")

        capacity = t if self.capacity_factor is None else compute_capacity(
            t, self.num_experts, self.top_k, self.capacity_factor)
        dispatch, combine, dropped = build_dispatch_combine(
            gates, idx, self.num_experts, capacity, valid, self.held)

        xin = jnp.einsum("tec,th->ech", dispatch.astype(self.dtype),
                         x.astype(self.dtype))  # [E, C, H]
        if ep is not None and ep > 1:
            # all-to-all: expert dim E -> E/ep local, capacity gathers the
            # slots from every token shard (reference
            # enter_expert_parallel_region)
            xin = mappings.enter_expert_parallel_region(
                xin, self.ep_axis, split_dim=0, concat_dim=1)

        # expert-fused column parallel (3-D einsum; reference
        # ExpertFusedColumnParallelLinear moe_parallel_layers.py:175)
        xin = mappings.copy_to_tensor_parallel_region(xin, self.tp_axis)
        if self.act == "swiglu":
            h = glu.gated(*glu.project(
                xin, *(w.astype(self.dtype) for w in gate_up)))
        else:
            h = relu2(jnp.matmul(xin, gate_up[0].astype(self.dtype)))
        out = jnp.einsum("eci,eih->ech", h, down.astype(self.dtype))
        # expert-fused row parallel exit (reference
        # ExpertFusedRowParallelLinear moe_parallel_layers.py:303)
        out = mappings.reduce_from_tensor_parallel_region(out, self.tp_axis)

        if ep is not None and ep > 1:
            out = mappings.exit_expert_parallel_region(
                out, self.ep_axis, split_dim=1, concat_dim=0)

        y = jnp.einsum("tec,ech->th", combine.astype(self.dtype),
                       out)
        aux = {"dropped_fraction": dropped}
        if valid is not None:
            # every kept assignment holds exactly one slot
            kept = jnp.sum(dispatch).astype(jnp.int32)
            asked = jnp.sum(valid).astype(jnp.int32) * idx.shape[1]
            if self.held is None:
                aux["assignments"] = jnp.stack([kept, asked - kept])
            else:
                first, count = self.held
                mine = jnp.sum(valid[:, None] & (idx >= first)
                               & (idx < first + count)).astype(jnp.int32)
                aux["assignments"] = jnp.stack(
                    [kept, mine - kept, asked - mine])
        if self.count_hit:
            # an expert's slots fill from the first: it took a row of the
            # step if and only if its slot 0 is taken
            hit = jnp.sum(jnp.any(dispatch[:, :, 0] > 0, axis=0)
                          ).astype(jnp.int32)
            aux["experts_hit"] = jnp.stack([hit, self.num_experts - hit])
        return y.astype(self.dtype), aux

    def _run_grouped_glu(self, xs, gate_up, down, be, i_local):
        """Shared kernel dispatch for both blockwise paths: bi-tile
        fallback + training kernel vs forward-only decode kernel
        (``sentinel_empty``: reads only hit experts' weights — token blocks
        innermost, empty blocks sentinel'd). Where a layer scan handed this
        bank's stacks in beside the slices (:mod:`..layer_stack`) and no
        ep axis divides the bank, the kernel gets the stacks and the
        layer's index, and no bank is copied out for the custom call."""
        from . import blockwise as bw

        bi = min(self.block_i, i_local)
        if i_local % bi != 0:
            bi = i_local
        kernel = (bw.grouped_glu_decode if self.sentinel_empty
                  else bw.grouped_glu)
        ep = comm._axis_size(self.ep_axis)
        stacked = None if ep is not None and ep > 1 else layer_stack.of(
            self, (*glu.EXPERTS, "down"), (*gate_up, down), self.dtype)
        _record_operands("slice" if stacked is None else "stack")
        # force_pallas=None: Pallas on TPU, the bit-exact jnp reference on
        # CPU (ops.blockwise_moe auto-dispatch)
        if stacked is not None:
            stacks, layer = stacked
            return kernel(xs, *stacks, be, self.block_size, bi, layer=layer)
        return kernel(xs, *(w.astype(self.dtype) for w in gate_up),
                      down.astype(self.dtype), be, self.block_size, bi)

    def _forward_blockwise(self, x, gates, idx, gate_up, down, i_local):
        """Dropless path: sort-by-expert + Pallas block-sparse grouped GLU
        (:mod:`.blockwise`; reference ``forward_blockwise``,
        ``expert_mlps_v2.py:691``). Zero drops by construction."""
        from . import blockwise as bw

        t = x.shape[0]
        order, src, dest, be, _, padded = bw.compute_block_metadata(
            idx, self.num_experts, self.block_size,
            sentinel_empty=self.sentinel_empty)
        xin = mappings.copy_to_tensor_parallel_region(x, self.tp_axis)
        xs = bw.scatter_to_blocks(xin.astype(self.dtype), src, dest, padded)
        ys = self._run_grouped_glu(xs, gate_up, down, be, i_local)
        # combining shard-partial expert outputs is forward-equivalent to
        # combining the tp-reduced ones, but the gates' (hence router's)
        # gradient d y/d gate = expert output must be tp-complete: enter
        # the gates through copy_to (fwd identity, bwd psum of the tiny
        # [T, K] gate cotangent), then reduce the combined [T, H] — the
        # cheapest placement (r2 bug found via the MoE x PP parity test)
        gates = mappings.copy_to_tensor_parallel_region(gates, self.tp_axis)
        y = bw.combine_from_blocks(ys, gates, order, src, dest, t)
        y = mappings.reduce_from_tensor_parallel_region(y, self.tp_axis)
        aux = {"dropped_fraction": jnp.zeros((), jnp.float32)}
        return y.astype(self.dtype), aux

    def _local_expert_partial(self, x_in, gates_in, idx_in, gate_up, down,
                              i_local, e_local, off):
        """Partial expert output of ``x_in``'s tokens through THIS rank's
        local experts: non-local (token, k) pairs map to a *sentinel*
        expert sorted last, whose gates are zeroed — the sentinel blocks
        borrow the last local expert's weights, compute finite garbage, and
        contribute nothing, forward (gate 0) and backward dW/dx (their
        ``dy`` cotangent is 0). Shared by the monolithic (whole gathered
        batch) and per-chunk (one token shard at a time) EP paths."""
        from . import blockwise as bw

        local = (idx_in >= off) & (idx_in < off + e_local)
        idx_local = jnp.where(local, idx_in - off, e_local)  # sentinel last
        gates_local = jnp.where(local, gates_in, 0.0).astype(gates_in.dtype)

        # decode (sentinel_empty): additionally sentinel the blocks of
        # LOCAL experts no token hit — both sentinel classes land >= e_local
        # and the forward-only decode kernel skips them (the training path
        # keeps every local expert's block for the dW zero-init contract)
        order, src, dest, be, _, padded = bw.compute_block_metadata(
            idx_local, e_local + 1, self.block_size,
            sentinel_empty=self.sentinel_empty)

        xin = mappings.copy_to_tensor_parallel_region(x_in, self.tp_axis)
        xs = bw.scatter_to_blocks(xin.astype(self.dtype), src, dest, padded)
        # sentinel (block_expert >= E_local) blocks are compute-skipped
        # in-kernel, so per-rank MXU work tracks the LOCAL routed load —
        # EP shards FLOPs, not just weight memory
        ys = self._run_grouped_glu(xs, gate_up, down, be, i_local)
        # router-grad placement: see _forward_blockwise
        gates_local = mappings.copy_to_tensor_parallel_region(
            gates_local, self.tp_axis)
        y = bw.combine_from_blocks(ys, gates_local, order, src, dest,
                                   x_in.shape[0])
        return mappings.reduce_from_tensor_parallel_region(y, self.tp_axis)

    def _forward_blockwise_ep(self, x, gates, idx, gate_up, down, i_local,
                              e_local):
        """Dropless blockwise under a *bound* ep axis (shard_map).

        Reference-style (``expert_mlps_v2.py:779-817``): there is no
        dispatch all-to-all — every EP rank sees every token (all-gather
        over ep) and masks the routing to its LOCAL experts; per-rank
        partial outputs reduce back to the token shards.

        Two dispatch programs (:mod:`...parallel.ep_dispatch`):

        * **monolithic** (``ep_wire_dtype="fp32"`` and overlap off): one
          all-gather of [T_local, H] + one reduce-scatter of [T_g, H] over
          ep — the baseline layout, bitwise preserved;
        * **per-chunk** (quantized wire and/or ring overlap): the gather
          exposes each source rank's chunk separately (optionally arriving
          hop-by-hop over a ppermute ring, payloads int8/fp8 on the wire),
          the local-expert blockwise matmul runs per chunk — so chunk
          ``t``'s compute overlaps hop ``t+1`` — and the per-destination
          partials ride the dual combine back. The fp32 ring is bitwise
          identical to the monolithic collectives (``_ordered_sum``
          materialization; tested), and quantized ring == quantized
          monolithic bitwise, fwd + bwd.
        """
        r = jax.lax.axis_index(self.ep_axis)
        off = r * e_local
        wire = ep_dispatch.wire_config(self.ep_wire_dtype)
        overlap = ep_dispatch.overlap_engaged(self.ep_overlap, self.ep_axis)
        aux = {"dropped_fraction": jnp.zeros((), jnp.float32)}

        if wire is None and not overlap:
            # gather with REDUCE-SCATTER backward (to_model_parallel=True):
            # each rank produces partial cotangents for EVERY token (its
            # experts' contributions), which must be summed across ranks
            # then re-sharded — a slice-only gather backward would drop the
            # off-rank contributions
            x_g = mappings.gather_from_sequence_parallel_region(
                x, self.ep_axis, seq_dim=0, to_model_parallel=True)
            gates_g = mappings.gather_from_sequence_parallel_region(
                gates, self.ep_axis, seq_dim=0, to_model_parallel=True)
            idx_g = comm.all_gather(idx, self.ep_axis, dim=0)  # int: no grad
            y = self._local_expert_partial(x_g, gates_g, idx_g, gate_up,
                                           down, i_local, e_local, off)
            # sum partial expert outputs over ep AND return to token shards
            y = mappings.reduce_scatter_to_sequence_parallel_region(
                y, self.ep_axis, seq_dim=0)
            return y.astype(self.dtype), aux

        # per-chunk: tokens ride the (quantized, optionally decomposed)
        # dispatch; the tiny [T, K] routing metadata stays full-precision
        # on a monolithic gather (negligible bytes, and the gates keep
        # their reduce-scatter backward for the router gradient)
        n = comm._axis_size(self.ep_axis)
        t_local = x.shape[0]
        gates_g = mappings.gather_from_sequence_parallel_region(
            gates, self.ep_axis, seq_dim=0, to_model_parallel=True)
        idx_g = comm.all_gather(idx, self.ep_axis, dim=0)
        chunks = ep_dispatch.gather_token_chunks(
            x, self.ep_axis, wire=wire, overlap=overlap)
        ys = []
        for ti in range(n):
            src = (r + ti) % n          # chunk ti's source rank (hop order)
            start = src * t_local
            g_t = jax.lax.dynamic_slice_in_dim(gates_g, start, t_local, 0)
            i_t = jax.lax.dynamic_slice_in_dim(idx_g, start, t_local, 0)
            ys.append(self._local_expert_partial(
                chunks[ti], g_t, i_t, gate_up, down, i_local, e_local, off))
        # dual combine: ys[ti] returns to rank (r + ti) % n and sums over
        # source ranks in ascending-rank (psum_scatter) order
        y = ep_dispatch.combine_token_chunks(
            tuple(ys), self.ep_axis, wire=wire, overlap=overlap)
        return y.astype(self.dtype), aux
