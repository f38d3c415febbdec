"""Paged KV cache (vLLM/Orca-style) in fixed-shape JAX.

The contiguous :class:`.kv_cache.KVCache` reserves ``max_len`` slots per
request up front, so a ragged serving mix wastes most of its HBM on
padding. Here every layer shares ONE block pool ``[L, num_blocks,
block_size, KV, D]``; a request owns an arbitrary *set* of blocks, named
by its row of the ``block_tables`` array. Allocation decisions happen on
the host at step boundaries (:class:`BlockAllocator`); everything the
compiled step touches — the pool, the tables, the per-slot positions —
is a fixed-shape device array, so the step compiles once and serves any
live-request mix (the shape-churn hazard nxdlint's recompile-hazard rule
flags).

Masking follows the contiguous cache's convention: each pool slot stores
the true token position it holds (``PAD_POSITION`` when empty), and the
causal mask is ``q_pos >= slot_pos`` — empty slots and unmapped table
entries are never attended, so no separate attention mask is plumbed.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import (Any, Callable, Dict, List, Mapping, NamedTuple, Optional,
                    Sequence, Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from .kv_cache import PAD_POSITION


class CacheExhaustedError(RuntimeError):
    """The block pool has no free block for a required allocation."""


# ---------------------------------------------------------------------------
# A step's counters. What the attention kernel's walk did with the rows of
# a packed step, and what the step's router did with them, is counted by
# the cache kind whose walk it is (``counters`` and ``count_step``: on the
# host, from the step's own arrays, by the kernel's own functions) or by
# the compiled step itself, into a leaf of the cache that the kind or the
# family declares (:class:`DeviceCounts`). :class:`.engine.ServingEngine`
# registers what is declared, calls the hook once a dispatched step, reads
# the leaves with the step's tokens and adds up what it is handed; it
# knows no family's counter.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CounterFamily:
    """One counter of the registry: its name, its help text and its
    ``kind`` labels in the order its counts come in (none: one count, no
    label)."""

    name: str
    help: str
    kinds: Tuple[str, ...] = ()


#: the leaves a :class:`DeviceCounts` may name: a step leaves them for the
#: host to read after it, so the engine keeps them out of the pool it
#: donates (the host may hold such an array, and read it, while the step
#: after the one that made it runs)
COUNT_LEAVES = ("counts", "moe_counts")


@dataclasses.dataclass(frozen=True)
class DeviceCounts:
    """A leaf of the serving cache that the compiled step counts into and
    the engine fetches with the step's tokens: its name (one of
    :data:`COUNT_LEAVES`), its length and, for each counter family it
    feeds, the entries that sum to each of the family's kinds, in the
    kinds' order."""

    leaf: str
    entries: int
    reads: Tuple[Tuple[CounterFamily, Tuple[Tuple[int, ...], ...]], ...]

    def __post_init__(self):
        if self.leaf not in COUNT_LEAVES:
            raise ValueError(f"{self.leaf!r} is not one of the leaves a "
                             f"step counts into ({COUNT_LEAVES})")

    def read(self, values) -> Dict[str, List[int]]:
        """Counts by counter name from the leaf as the host fetched it."""
        return {family.name: [sum(int(values[i]) for i in entry)
                              for entry in entries]
                for family, entries in self.reads}


class StepGeometry(NamedTuple):
    """What a kind's :meth:`FullCache.count_step` needs of the engine and
    the model it was bound to (:func:`step_counter`): the pool's block
    size, its blocks and the bytes of one of its elements, the model's
    query heads, the query heads the paged kernel sees a K/V head (the
    model's, times the K/V heads the kind lays on a row), the K and V
    values a position holds a layer (:meth:`FullCache.row_values`), and
    the packed rows a slot holds side by side in a step (a block family's
    block length, :attr:`ServingFamily.block`; 1 for every other)."""

    block_size: int
    pool_blocks: int
    itemsize: int
    heads: int
    n_rep: int
    row_values: int
    slot_rows: int = 1


def step_counter(kind, model_cfg, *, block_size: int, pool_blocks: int,
                 itemsize: int) -> Callable[..., Dict[str, Any]]:
    """``kind.count_step`` bound, once an engine, to the geometry it
    counts by."""
    block = model_cfg.serving_family().block
    return functools.partial(kind.count_step, StepGeometry(
        block_size, pool_blocks, itemsize, model_cfg.num_heads,
        model_cfg.num_heads // model_cfg.num_kv_heads * kind.pack,
        kind.row_values(model_cfg),
        1 if block is None else block.block_length))


PAGED_COLUMNS = CounterFamily(
    "nxd_paged_columns_total",
    "Table columns of the serving workers' rows by what the paged kernel's "
    "walk does with them: live (mapped and not wholly behind the row's "
    "position) is computed, skipped is not.",
    ("skipped", "live"))
EVA_COLUMNS = CounterFamily(
    "nxd_eva_columns_total",
    "Table columns of the serving workers' rows by what the eva_attention "
    "kernel's walk finds there: exact rows of the row's own window, an "
    "earlier window's chunk summaries, or nothing (skipped).",
    ("skipped", "exact", "summary"))
EVA_WINDOWS = CounterFamily(
    "nxd_eva_windows_total",
    "Windows whose last position was in a packed step: summarised into a "
    "block of the pool by that step.")
PAGED_BLOCK_VISITS = CounterFamily(
    "nxd_paged_block_visits_total",
    "Live (row, table column) of the serving workers' rows by how the "
    "paged kernel came by the column's pool block: fetched, one a (tile of "
    "rows, pair of column and block), or shared, served by a fetch that "
    "another row of the tile is counted for.",
    ("fetched", "shared"))
PAGED_PAIRS = CounterFamily(
    "nxd_paged_pairs_total",
    "Pairs (table column, pool block) that one packed row of its tile "
    "alone names, or neighbouring rows whose heads one group of the tile "
    "holds (one layer's worth), by the rows the paged kernel computes them "
    "over: narrow, the group that holds the naming rows' heads, or "
    "one_row_whole, the whole tile although one row names the pair.",
    ("narrow", "one_row_whole"))
PAGED_SHARED_PAIRS = CounterFamily(
    "nxd_paged_shared_pairs_total",
    "Pairs that several packed rows of a tile name and no one group holds "
    "(a prefill chunk's blocks): the paged kernel computes them over the "
    "whole tile. With nxd_paged_pairs_total's two kinds they sum to "
    "nxd_paged_block_visits_total's fetched.")
PAGED_BLOCK_FETCHES = CounterFamily(
    "nxd_paged_block_fetches_total",
    "Pool blocks the paged kernel fetches for the serving workers' rows "
    "(one layer's worth at the full layers' head count; they sum to "
    "nxd_paged_block_visits_total's fetched), by how: in_run, with one or "
    "more other blocks of the same narrow group in one unit of the kernel "
    "(one step of the online softmax over all of them); alone, a narrow "
    "pair that is a unit by itself (every one where the pool's blocks ride "
    "in no runs); whole, a pair that rows of the tile share beyond a "
    "group, computed over the whole tile.",
    ("in_run", "alone", "whole"))
MLA_BLOCK_FETCHES = CounterFamily(
    "nxd_mla_block_fetches_total",
    "Pool blocks the mla_paged_attention kernel fetches for the serving "
    "workers' rows (one layer's worth, as nxd_paged_block_visits_total's "
    "fetched), by how: in_run, with one or more other blocks of the same "
    "row in one unit of the kernel (one step of the online softmax over "
    "all of them); alone, a one-row pair that is a unit by itself; whole, "
    "a pair that rows of the tile share, computed over the whole tile.",
    ("in_run", "alone", "whole"))
MLA_SHARED_BLOCKS = CounterFamily(
    "nxd_mla_shared_blocks_total",
    "Pool blocks that rows of a tile share (nxd_mla_block_fetches_total's "
    "whole) by the unit of the mla_paged_attention kernel they rode: "
    "in_unit, with one or more other shared blocks of the tile in one unit "
    "(one ring half of copies, one step of the online softmax a slab of "
    "the tile over all of them); alone, a unit by itself.",
    ("in_unit", "alone"))
STATE_RESETS = CounterFamily(
    "nxd_state_resets_total",
    "Packed rows at position 0: each starts its slot's per-slot states (a "
    "lightning layer's, a state-space layer's and its convolution tail) "
    "from zero inside the step.")
STATE_SLOT_STEPS = CounterFamily(
    "nxd_state_slot_steps_total",
    "Slots of a cache with per-slot states, a step: advanced, the slot had "
    "rows in the step and its states moved on by them, or held, the slot "
    "was occupied and the step left its states as they were.",
    ("advanced", "held"))
STATE_SEGMENT_ROWS = CounterFamily(
    "nxd_state_segment_rows_total",
    "Real rows of the serving workers' packed steps that a state-pool "
    "family's recurrent layers apply to per-slot states, by their place in "
    "their segment (a slot's neighbouring rows: ops/ssd.py "
    "step_segments): first, the row opens its segment and the state "
    "update's kernel fetches the slot's state for it (a decode row, a "
    "prefill chunk's first row), or later, it follows another row of its "
    "own segment and meets the state where that row left it, one row "
    "after another (the rest of a prefill chunk). One layer's worth.",
    ("first", "later"))
WINDOW_COLUMNS = CounterFamily(
    "nxd_window_columns_total",
    "Table columns that the serving workers' rows have mapped in the "
    "full-attention layers' table (those not beyond the row's position) by "
    "what a sliding-window layer does with the same positions: live, the "
    "column holds a position of the row's window and the row's ring holds "
    "its block, or behind, the window has passed it and the ring has "
    "overwritten it.",
    ("live", "behind"))
KV_BLOCKS_HELD = CounterFamily(
    "nxd_kv_blocks_held_total",
    "K/V blocks the occupied slots hold, a step, times the layers that hold "
    "them: full, blocks of the full-attention layers' pool (they grow with "
    "the context), or window, blocks of the slots' rings in the "
    "sliding-window layers' pool (at most the ring a slot).",
    ("full", "window"))
KV_BYTES_HELD = CounterFamily(
    "nxd_kv_bytes_held_total",
    "Bytes of K and of V that the occupied slots hold, a step, over the "
    "layers that hold them: in the full-attention layers' pool (full_k, "
    "full_v: they grow with the context) and in the slots' rings of the "
    "sliding-window layers' pool (window_k, window_v). Blocks of the two "
    "pools are unlike in bytes where their K/V heads or their K and V "
    "rows are.",
    ("full_k", "full_v", "window_k", "window_v"))
STATE_BYTES_HELD = CounterFamily(
    "nxd_state_bytes_held_total",
    "Bytes the occupied slots hold, a step, by what holds them: state, the "
    "slots' entries in the per-slot leaves that a layer's recurrence "
    "carries (a state-space layer's, a delta-rule layer's: the same bytes "
    "a slot whatever its context), tail, their entries in the convolution "
    "tails, or kv, the K and V of the blocks they have mapped in the "
    "attention layers' pool (they grow with the context).",
    ("state", "tail", "kv"))
STEP_ROWS_BY_CONTEXT = CounterFamily(
    "nxd_step_rows_by_context_total",
    "Real rows of the serving workers' packed steps by the row's "
    "position: to_2k (under 2,048), to_8k (under 8,192) or past_8k. A "
    "full-attention layer's walk is as long as the row's context, a "
    "sliding-window layer's is not.",
    ("to_2k", "to_8k", "past_8k"))
SPARSE_COLUMNS = CounterFamily(
    "nxd_sparse_columns_total",
    "Grid steps of the sparse_paged_attention kernel's walk (rows x K/V "
    "groups x walk width, summed over the sparse layers) by what is in "
    "them: a pool block the selection picked, one the first blocks or the "
    "local window forced, one of a row below the dense threshold, or "
    "nothing (skipped). Counted on the device, fetched with the step's "
    "tokens.",
    ("selected", "forced", "dense", "skipped"))
SPARSE_POSITIONS = CounterFamily(
    "nxd_sparse_positions_total",
    "Causal positions of the packed rows (x K/V groups x sparse layers) by "
    "whether the selection attended them.",
    ("attended", "skipped"))
SPARSE_BLOCK_VISITS = CounterFamily(
    "nxd_sparse_block_visits_total",
    "Live (row, K/V group, table column) of the packed rows, summed over "
    "the sparse layers, by how the sparse_paged_attention kernel came by "
    "the column's pool block: fetched, for the first row of the tile that "
    "attends it (or the only one), or shared, served by the fetch made for "
    "an earlier row of the tile. Counted on the device, fetched with the "
    "step's tokens.",
    ("fetched", "shared"))
SPARSE_KEY_VISITS = CounterFamily(
    "nxd_sparse_key_visits_total",
    "(Row, table column) of the packed rows whose compressed keys the "
    "selection scores (rows at or past the dense threshold, columns that "
    "hold a whole kernel), summed over the sparse layers, by how the "
    "compressed_key_scores kernel came by the column's keys: fetched, a "
    "(tile, column, pool block) it copied, or shared, served by the copy "
    "made for another row of the tile. Counted on the device from the "
    "step's walk, fetched with the step's tokens.",
    ("fetched", "shared"))
MOE_ASSIGNMENTS = CounterFamily(
    "nxd_moe_assignments_total",
    "Routed-expert assignments (a real row's choice of an expert, top_k a "
    "row an expert layer) of the serving workers' rows by whether the "
    "dispatch gave them a slot (kept) or had none left (dropped). Pad rows "
    "choose nothing. Counted on the device, fetched with the step's "
    "tokens.",
    ("kept", "dropped"))
MOE_HELD = CounterFamily(
    "nxd_moe_held_total",
    "Routed-expert assignments of the serving workers' real rows by where "
    "the chosen expert is: held, among the experts this device holds of "
    "those the router scores (kept or dropped: nxd_moe_assignments_total), "
    "or elsewhere, on a device that shares the layer, where it takes no "
    "slot here and adds nothing. Counted on the device, fetched with the "
    "step's tokens.",
    ("held", "elsewhere"))
MOE_EXPERTS_HIT = CounterFamily(
    "nxd_moe_experts_hit_total",
    "Experts this device holds (a step and an expert layer) by whether "
    "at least one real row of the step chose them (hit) or none did "
    "(idle: the step needed none of the expert's weights). Counted on the "
    "device, fetched with the step's tokens.",
    ("hit", "idle"))
MOE_IDENTITY = CounterFamily(
    "nxd_moe_identity_total",
    "Router choices of the serving workers' real rows (top_k a row an "
    "expert layer) by what the chosen slot is: identity, a "
    "zero-computation expert, whose weight times the row's own input is "
    "added where the row is and which takes no expert's slot anywhere, or "
    "routed, a real expert (held here or elsewhere: nxd_moe_held_total). "
    "Counted on the device, fetched with the step's tokens.",
    ("identity", "routed"))

BLOCK_PASSES = CounterFamily(
    "nxd_block_passes_total",
    "Passes of the decode groups of a family that decodes blocks (a slot's "
    "block of positions run once) by what the pass was: denoise, begun "
    "with a masked row, or store, begun with none, whose K/V later blocks "
    "see. A pass an idle slot was packed for is none. Counted on the "
    "device, fetched with the step's tokens.",
    ("denoise", "store"))
BLOCK_ROWS = CounterFamily(
    "nxd_block_rows_total",
    "Real rows of the decode groups, each once a pass, by what the pass "
    "did with the row: uncovered it by_threshold (its confidence over the "
    "threshold) or by_quota (among the pass's most confident where too few "
    "were over it), left it masked, found it already_uncovered (a denoise "
    "pass's rows uncovered earlier, the prompt's remainder among them), or "
    "stored it (a store pass's rows). They sum to the block length times "
    "nxd_block_passes_total. Counted on the device.",
    ("by_threshold", "by_quota", "left_masked", "already_uncovered",
     "stored"))
BLOCKS_FINISHED = CounterFamily(
    "nxd_blocks_finished_total",
    "Blocks whose store pass ran. Counted on the device.")
#: what the step of a family that decodes blocks counts
#: (:attr:`ServingFamily.block`; ``block_serving``), behind its tokens
BLOCK_COUNTERS = (BLOCK_PASSES, BLOCK_ROWS, BLOCKS_FINISHED)
DSA_POSITIONS = CounterFamily(
    "nxd_dsa_positions_total",
    "Causal positions of the serving workers' real rows, summed over the "
    "layers, of a family whose rows attend a learned selection of their "
    "context (an indexer's top positions): selected, the positions a row "
    "attended, or passed_over, those it scored and left. Counted on the "
    "device, fetched with the step's tokens.",
    ("selected", "passed_over"))
DSA_ROWS = CounterFamily(
    "nxd_dsa_rows_total",
    "Real rows of such a family, summed over the layers: selecting, a row "
    "whose context is longer than the selection, or whole, one that "
    "attends every causal position.",
    ("selecting", "whole"))
DSA_BLOCKS = CounterFamily(
    "nxd_dsa_blocks_total",
    "Pool blocks of the real rows' contexts, a (row, block, layer) each: "
    "named, the block holds a position the row selected, or unnamed, it "
    "holds none: what a kernel that reads whole blocks would and would "
    "not have to read.",
    ("named", "unnamed"))
DSA_SELECTED = CounterFamily(
    "nxd_dsa_selected_total",
    "Selected positions of the real rows, summed over the layers: "
    "shared_with_previous_row, the row before it is the position before "
    "it of the same sequence (a chunk's neighbour) and selected the "
    "position too, or new: what a kernel that fetches a row once a tile "
    "could and could not save.",
    ("shared_with_previous_row", "new"))

#: the ``moe_counts`` leaf of a family that declares it
#: (:class:`ServingFamily`), as the kind that builds it lays it out: the
#: assignments kept and dropped, and, where the device holds a share of
#: the experts, a third count of those that chose an expert held elsewhere;
#: where the router also scores identity experts, a fourth of the choices
#: of one (the first three are then of the real experts alone)
MOE_KEPT_DROPPED = DeviceCounts(
    "moe_counts", 2, ((MOE_ASSIGNMENTS, ((0,), (1,))),))
MOE_KEPT_DROPPED_ELSEWHERE = DeviceCounts(
    "moe_counts", 3, ((MOE_ASSIGNMENTS, ((0,), (1,))),
                      (MOE_HELD, ((0, 1), (2,)))))
MOE_KEPT_DROPPED_ELSEWHERE_IDENTITY = DeviceCounts(
    "moe_counts", 4, ((MOE_ASSIGNMENTS, ((0,), (1,))),
                      (MOE_HELD, ((0, 1), (2,))),
                      (MOE_IDENTITY, ((3,), (0, 1, 2)))))

#: a share of the experts whose family also counts the held experts a step
#: hit and left idle (``MoE(count_hit=True)``)
MOE_KEPT_DROPPED_ELSEWHERE_HIT = DeviceCounts(
    "moe_counts", 5, MOE_KEPT_DROPPED_ELSEWHERE.reads
    + ((MOE_EXPERTS_HIT, ((3,), (4,))),))

#: ``counts`` of a sparse-state cache: a counter family and, in its kinds'
#: order, the names of :data:`..ops.sparse_attention.COUNT_KINDS` it reads
_SPARSE_COUNTS = (
    (SPARSE_COLUMNS, ("selected", "forced", "dense", "skipped")),
    (SPARSE_POSITIONS, ("attended", "skipped_positions")),
    (SPARSE_BLOCK_VISITS, ("fetched", "shared")),
    (SPARSE_KEY_VISITS, ("keys_fetched", "keys_shared")))

#: what the paged kernel's tiles fetch for a step's rows, and over which
#: rows they compute it
_PAGED_FETCHES = (PAGED_BLOCK_VISITS, PAGED_PAIRS, PAGED_SHARED_PAIRS,
                  PAGED_BLOCK_FETCHES)
#: what a step does to the per-slot states of a kind that has ``leaves``
_STATES = (STATE_RESETS, STATE_SLOT_STEPS)


def _count_walk(kind, geo: StepGeometry, columns: CounterFamily, positions,
                slot_ids, tables):
    """What the paged kernel's walk finds in a step's rows, by the walk's
    own functions: the rows' table columns by ``kind.column_kinds`` under
    ``columns``' name (a pad row attends nothing, whatever table row it is
    handed), the pool blocks its tiles fetch, one a (tile, pair), and the
    further live (row, column) each fetch serves. Returns the counts and
    ``served [T, max_blocks_per_seq]``, a row's table entry in the columns
    it attends and -1 elsewhere."""
    from ..ops.paged_attention import tile_pairs, tile_rows

    tbl = tables[np.minimum(slot_ids, tables.shape[0] - 1)]
    kinds = kind.column_kinds(tbl, np.arange(tbl.shape[1]),
                              positions[:, None], geo.block_size)
    served = np.where(kinds > 0, tbl, -1)
    fetched = int(tile_pairs(served, tile_rows(geo.n_rep, len(positions)),
                             geo.pool_blocks, xp=np)[0].sum())
    return {columns.name: np.bincount(kinds.ravel(),
                                      minlength=len(columns.kinds)),
            PAGED_BLOCK_VISITS.name: (
                fetched, np.count_nonzero(kinds) - fetched)}, served


def _count_pairs(geo: StepGeometry, served) -> Dict[str, Any]:
    """A step's pairs (one layer's worth) by the rows the paged kernel
    computes them over, and its fetches by the units they ride in (runs
    of as many blocks as the kernel takes for the pool's block bytes)."""
    from ..ops.paged_attention import (block_fetches, host_pairs, pair_kinds,
                                       run_length)

    pairs = host_pairs(served, geo.n_rep, geo.pool_blocks, geo.slot_rows)
    narrow, one_row_whole, shared = pair_kinds(served, geo.n_rep,
                                               geo.pool_blocks, pairs)
    run = run_length(geo.n_rep,
                     geo.block_size * geo.row_values * geo.itemsize,
                     geo.block_size, served.shape[1], geo.slot_rows)
    return {PAGED_PAIRS.name: (narrow, one_row_whole),
            PAGED_SHARED_PAIRS.name: (shared,),
            PAGED_BLOCK_FETCHES.name: block_fetches(served, geo.n_rep, run,
                                                    pairs)}


def _count_states(positions, slot_ids, held) -> Dict[str, Any]:
    """Rows at position 0, each of which starts its slot's states anew,
    the slots whose states the step advanced (they had rows in it) and the
    occupied slots it held untouched."""
    advanced = len(np.unique(slot_ids[positions < PAD_POSITION]))
    return {STATE_RESETS.name: (np.count_nonzero(positions == 0),),
            STATE_SLOT_STEPS.name: (advanced, len(held) - advanced)}


def _count_segment_rows(positions, slot_ids) -> Tuple[int, int]:
    """The step's real rows that open a segment and those that follow
    one of their own, as :func:`..ops.ssd.step_segments` cuts them: a
    real row whose neighbour before it is a real row of the same slot
    follows it."""
    real = positions < PAD_POSITION
    follows = real[1:] & real[:-1] & (slot_ids[1:] == slot_ids[:-1])
    later = int(np.count_nonzero(follows))
    return int(np.count_nonzero(real)) - later, later


# ---------------------------------------------------------------------------
# Cache kinds: where a sequence's positions live in its row of the block
# table, and what the family is served from (``init_cache``: the pytree
# :func:`init_serving_cache` hands the engine). The engine (block mapping,
# admission, counters), the pool writes and the paged kernel's walk ask
# the model family's kind; none of them divides by block_size itself.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FullCache:
    """Every position's K and V stay for the request's life: position ``p``
    lives in table column ``p // block_size``, and a row attends every
    mapped column that does not lie wholly behind it."""

    name = "full"
    #: table columns holding exact K/V rows (None: all of them)
    ring = None
    #: the per-slot states the family keeps beside the pool
    #: (:class:`StateLeaf`; none for a cache that is blocks alone)
    leaves = ()
    #: K/V heads laid side by side on one pool row (the paged kernel then
    #: sees that many times the query heads a K/V head)
    pack = 1

    def geometry(self, block_size: int, step_rows: int = 0) -> "FullCache":
        return self

    def row_values(self, model_cfg) -> int:
        """K and V values a position holds in a layer of the pool the
        paged kernel's counters follow (the full layers')."""
        return 2 * model_cfg.num_kv_heads * model_cfg.head_dim_

    def column_of(self, positions, block_size: int):
        """Table column of a position's K/V row (ints, NumPy or jnp)."""
        return positions // block_size

    def columns_to_map(self, position: int, block_size: int
                       ) -> Tuple[int, ...]:
        """Table columns that must hold a block before a row at
        ``position`` runs."""
        return (position // block_size,)

    def blocks_for(self, n: int, block_size: int) -> int:
        """Blocks a sequence of ``n`` processed positions holds."""
        return -(-n // block_size)

    def max_positions(self, columns: int, block_size: int) -> int:
        """Longest sequence that ``columns`` table columns (or pool
        blocks) can hold."""
        return columns * block_size

    def column_kinds(self, entry, column, q_pos, block_size: int):
        """Per (row, column): 0 skipped, 1 computed (exact rows)."""
        from ..ops.paged_attention import column_live

        return column_live(entry, column, q_pos, block_size) * 1

    def init_cache(self, model_cfg, **geometry):
        return _uniform_pool_cache(model_cfg, **geometry)

    #: leaves of the kind's cache that its step counts into on the device
    #: (:class:`DeviceCounts`)
    device_counts = ()

    @property
    def counters(self) -> Tuple[CounterFamily, ...]:
        """The counter families :meth:`count_step` counts."""
        return ((PAGED_COLUMNS,) + _PAGED_FETCHES
                + (_STATES if self.leaves else ()))

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """What this kind's kernels and states do with one dispatched
        step, counted on the host from the step's own NumPy arrays:
        ``positions`` and ``slot_ids [W]`` of the worker's rows
        (PAD_POSITION and a slot past the table for a pad row), the
        host's block ``tables``, the blocks each occupied slot ``held``
        and the summary blocks the schedule ``rolled`` into the tables
        for this step. Counts by counter name, a family's in its kinds'
        order (:attr:`counters`)."""
        counts, served = _count_walk(self, geo, PAGED_COLUMNS, positions,
                                     slot_ids, tables)
        counts.update(_count_pairs(geo, served))
        if self.leaves:
            counts.update(_count_states(positions, slot_ids, held))
        return counts


FULL_CACHE = FullCache()


@dataclasses.dataclass(frozen=True)
class WindowSummaryCache:
    """An exact window and chunk summaries, two kinds of row in the one
    pool (EVA, arXiv:2302.04542): a sequence keeps exact K/V for the
    positions of its current ``window`` and one summary row pair per
    ``chunk`` positions of every earlier window.

    With ``bpw = window // block_size`` blocks a window, the exact rows
    live in a ring of ``bpw + 1`` table columns, position ``p`` in column
    ``(p // block_size) % (bpw + 1)`` (the extra column lets a packed
    chunk of up to ``block_size`` rows straddle a window's end without
    overwriting rows that earlier rows of the same step still attend, or
    that the step's summarisation still reads), and window ``w``'s
    ``window // chunk == block_size`` summaries fill the one block of
    column ``bpw + 1 + w``. Stale ring rows are masked by the window's
    lower bound, never cleared. A sequence of ``n`` positions holds
    ``min(bpw + 1, ceil(n / block_size)) + n // window`` blocks."""

    window: int
    chunk: int
    name = "window_summary"
    leaves = ()                 # as :class:`FullCache`'s: blocks alone,
    pack = 1                    # a K/V head a pool row

    def geometry(self, block_size: int, step_rows: int = 0
                 ) -> "WindowSummaryCache":
        """Checks that this kind tiles a pool of ``block_size`` and that a
        packed step of ``step_rows`` rows stays inside the ring's one
        spare column."""
        if step_rows > block_size:
            raise ValueError(
                f"window_summary cache: a packed step of {step_rows} rows "
                f"can straddle a window's end by more than the ring's one "
                f"spare block of {block_size}; token_budget must not "
                "exceed block_size")
        if (self.window % block_size
                or self.window // self.chunk != block_size):
            raise ValueError(
                f"window_summary cache (window {self.window}, chunk "
                f"{self.chunk}) needs block_size == window / chunk = "
                f"{self.window // self.chunk} so that a window is whole "
                f"blocks and its summaries fill one; got {block_size}")
        return self

    @property
    def ring(self) -> int:
        return self.chunk + 1       # window // (window // chunk) + 1

    def column_of(self, positions, block_size: int):
        return (positions // block_size) % self.ring

    def columns_to_map(self, position: int, block_size: int
                       ) -> Tuple[int, ...]:
        exact = int(self.column_of(position, block_size))
        if (position + 1) % self.window:
            return (exact,)
        # the row that completes a window: its step writes the summaries
        return (exact, self.ring + position // self.window)

    def blocks_for(self, n: int, block_size: int) -> int:
        return min(self.ring, -(-n // block_size)) + n // self.window

    def max_positions(self, columns: int, block_size: int) -> int:
        if columns < self.ring:
            return min(columns * block_size, self.window - 1)
        return (columns - self.ring + 1) * self.window - 1

    def column_kinds(self, entry, column, q_pos, block_size: int):
        """Per (row, column): 0 skipped, 1 exact rows of the row's own
        window, 2 the summaries of an earlier window."""
        from ..ops.paged_attention import window_column_kinds

        return window_column_kinds(entry, column, q_pos, block_size,
                                   self.window, self.ring)

    def init_cache(self, model_cfg, **geometry):
        return _uniform_pool_cache(model_cfg, **geometry)

    device_counts = ()
    counters = (EVA_COLUMNS, EVA_WINDOWS) + _PAGED_FETCHES
    row_values = FullCache.row_values

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """As :meth:`FullCache.count_step`, by the eva_attention kernel's
        walk, and the windows the step summarises."""
        counts, served = _count_walk(self, geo, EVA_COLUMNS, positions,
                                     slot_ids, tables)
        counts.update(_count_pairs(geo, served))
        counts[EVA_WINDOWS.name] = (rolled,)
        return counts


@dataclasses.dataclass(frozen=True)
class StateLeaf:
    """A per-slot state that a family keeps beside its pool, which is no
    block at all: one entry a table row, ``lead + (table rows,) + trail``
    in ``dtype`` (None: the pool's), whatever the sequence's length. The
    family names its leaves and their shapes in its cache kind
    (``leaves``); the kind's cache holds them, and the family's layers
    read and write them at ``(layer, slot)``. One contract for all of
    them: a slot whose rows start at position 0 starts every leaf from
    zero *inside the step*, so admission, preemption and re-prefill clear
    nothing on the host and a re-admitted request inherits nothing
    (``nxd_state_resets_total``); a slot without rows in a step keeps its
    leaves as they are (``nxd_state_slot_steps_total``)."""

    name: str
    lead: Tuple[int, ...]
    trail: Tuple[int, ...]
    dtype: Any = None
    #: the kind of ``nxd_state_bytes_held_total`` its bytes count under:
    #: ``state`` (what a layer's recurrence carries) or ``tail`` (a
    #: convolution's last inputs)
    counted_as: str = "state"

    def slot_bytes(self, itemsize: int) -> int:
        """Bytes of one slot's entry, over every layer that has one, where
        a pool's element has ``itemsize``."""
        return int(np.prod(self.lead + self.trail, dtype=np.int64)) * (
            jnp.dtype(self.dtype).itemsize if self.dtype else itemsize)


def init_state_leaves(leaves: Sequence[StateLeaf], table_rows: int,
                      dtype: Any) -> Dict[str, jax.Array]:
    """The leaves' arrays by name, zero."""
    return {leaf.name: jnp.zeros(
        tuple(leaf.lead) + (table_rows,) + tuple(leaf.trail),
        leaf.dtype or dtype) for leaf in leaves}


@dataclasses.dataclass(frozen=True)
class SparseStateCache(FullCache):
    """A cache that differs by layer kind, three kinds of leaf behind one
    block table and one allocator: paged K/V for the ``sparse_layers``
    block-sparse layers only (position ``p`` in column ``p //
    block_size``, as a full cache), a side pool of their compressed keys
    indexed by the same block ids (a freed block frees its compressed
    keys), and the family's per-slot state leaves (:class:`StateLeaf`):
    one, ``state``, the lightning layers' float32 ``[Ll, H, table rows,
    D, D]``, advanced by
    :func:`..ops.lightning_attention.lightning_attention_packed`."""

    sparse_layers: int = 0
    leaves: Tuple[StateLeaf, ...] = ()
    #: positions a compressed key advances by, and a selection block
    stride: int = 16
    select_block: int = 64
    name = "sparse_state"

    def geometry(self, block_size: int, step_rows: int = 0
                 ) -> "SparseStateCache":
        if block_size % self.select_block or block_size % self.stride:
            raise ValueError(
                f"sparse_state cache: pool blocks of {block_size} positions "
                f"must be whole selection blocks of {self.select_block} and "
                f"whole strides of {self.stride}")
        return self

    @property
    def device_counts(self) -> Tuple[DeviceCounts, ...]:
        """``counts``: a sparse layer's selections are known to the
        device alone."""
        from ..ops.sparse_attention import COUNT_KINDS

        return (DeviceCounts("counts", len(COUNT_KINDS), tuple(
            (family, tuple((COUNT_KINDS.index(n),) for n in names))
            for family, names in _SPARSE_COUNTS)),)

    @property
    def counters(self) -> Tuple[CounterFamily, ...]:
        return _STATES if self.leaves else ()

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """The states alone: the walk is the device's to count."""
        if not self.leaves:
            return {}
        return _count_states(positions, slot_ids, held)

    def init_cache(self, model_cfg, *, num_blocks: int, block_size: int,
                   table_rows: int, max_blocks_per_seq: int, dtype: Any,
                   quantized: bool = False) -> "SparseStatePagedCache":
        if quantized:
            raise ValueError("a sparse_state cache has no int8 pool: the "
                             "selection over one is another kernel")
        from ..ops.sparse_attention import COUNT_KINDS

        kv, d = model_cfg.num_kv_heads, model_cfg.head_dim_
        pool = (self.sparse_layers, num_blocks, kv, block_size, d)
        return SparseStatePagedCache(
            k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
            ck=jnp.zeros((self.sparse_layers,
                          num_blocks * (block_size // self.stride), kv * d),
                         dtype),
            counts=jnp.zeros((len(COUNT_KINDS),), jnp.int32),
            **init_state_leaves(self.leaves, table_rows, dtype),
            pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
            block_tables=jnp.full((table_rows, max_blocks_per_seq), -1,
                                  jnp.int32),
            lengths=jnp.zeros((table_rows,), jnp.int32),
            block_size=block_size)


def _rows_by_context(positions) -> np.ndarray:
    """A step's real rows by :data:`STEP_ROWS_BY_CONTEXT`'s kinds."""
    at = positions[positions < PAD_POSITION]
    return np.bincount(np.searchsorted((2048, 8192), at, side="right"),
                       minlength=3)


@dataclasses.dataclass(frozen=True)
class LatentCache(FullCache):
    """A full cache whose row is no ``KV x D`` pair: one latent and one
    rotary key, shared by every query head, laid on ``row`` whole lanes
    (the lanes past the two stay zero). There is no V pool: a value is
    the latent's lanes of the same row (:mod:`..ops.mla_attention`). Every position stays, so block
    mapping, allocation, copy-on-write and preemption are
    :class:`FullCache`'s. A decoder layer with more than one latent
    attention (``attentions``; the family says how many) has as many
    layers of rows in the stack, side by side (:meth:`stack_index`)."""

    row: int = 640
    #: latent attentions, hence layers of rows, a decoder layer
    attentions: int = 1
    #: how the kind lays out the ``moe_counts`` of a family that declares it
    moe_leaf: DeviceCounts = MOE_KEPT_DROPPED
    name = "latent"
    counters = (PAGED_COLUMNS, PAGED_BLOCK_VISITS, MLA_BLOCK_FETCHES,
                MLA_SHARED_BLOCKS, STEP_ROWS_BY_CONTEXT)

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """As :meth:`FullCache.count_step`, with the pool blocks by how the
        mla_paged_attention kernel's units come by them in place of the
        paged kernel's pairs (it takes its pairs in runs), the shared
        ones by the unit they rode, and the real rows by their position
        (the kernel's walk is as long as a row's context)."""
        from ..ops import mla_attention as mla
        from ..ops.paged_attention import host_pairs

        counts, served = _count_walk(self, geo, PAGED_COLUMNS, positions,
                                     slot_ids, tables)
        shapes = (geo.heads, self.row, geo.block_size, geo.itemsize)
        pairs = host_pairs(served, mla.stacked_heads(geo.heads))
        counts[MLA_BLOCK_FETCHES.name] = mla.block_fetches(served, *shapes,
                                                           pairs)
        counts[MLA_SHARED_BLOCKS.name] = mla.shared_blocks(served, *shapes,
                                                           pairs)
        counts[STEP_ROWS_BY_CONTEXT.name] = _rows_by_context(positions)
        return counts

    def stack_index(self, layer, which: int = 0):
        """Where in the row stack attention ``which`` of decoder layer
        ``layer`` (an int or a traced index) keeps its rows."""
        return layer * self.attentions + which

    def init_cache(self, model_cfg, *, num_blocks: int, block_size: int,
                   table_rows: int, max_blocks_per_seq: int, dtype: Any,
                   quantized: bool = False) -> "LatentPagedCache":
        if quantized:
            raise ValueError("a latent cache has no int8 pool: a row's "
                             "latent and rotary key want scales of their "
                             "own, and the kernel reads neither")
        return LatentPagedCache(
            rows=jnp.zeros((self.stack_index(model_cfg.num_layers),
                            num_blocks, block_size, self.row), dtype),
            moe_counts=(jnp.zeros((self.moe_leaf.entries,), jnp.int32)
                        if model_cfg.serving_family().moe_counts else None),
            pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
            block_tables=jnp.full((table_rows, max_blocks_per_seq), -1,
                                  jnp.int32),
            lengths=jnp.zeros((table_rows,), jnp.int32),
            block_size=block_size)


@dataclasses.dataclass(frozen=True)
class IndexedLatentCache(LatentCache):
    """A latent cache whose positions hold a second row a layer: the key
    of an indexer that scores a row's context and selects the positions it
    attends (:mod:`..ops.indexed_attention`). Two pool leaves under one
    table and one allocator, ``rows`` and ``index_keys`` (``index_row``
    lanes), so that a block, whoever maps, copies, ships or frees it,
    carries the pair; and a ``counts`` leaf for what the selection did,
    which the device alone knows."""

    index_row: int = 128
    name = "indexed_latent"
    counters = (STEP_ROWS_BY_CONTEXT,)

    @property
    def device_counts(self) -> Tuple[DeviceCounts, ...]:
        from ..ops.indexed_attention import COUNT_KINDS

        families = (DSA_POSITIONS, DSA_ROWS, DSA_BLOCKS, DSA_SELECTED)
        return (DeviceCounts("counts", len(COUNT_KINDS), tuple(
            (family, tuple((COUNT_KINDS.index(kind),)
                           for kind in family.kinds))
            for family in families)),)

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """The real rows by their position: no kernel walks the table's
        blocks here, and what the selection reads is the device's to
        count."""
        return {STEP_ROWS_BY_CONTEXT.name: _rows_by_context(positions)}

    def init_cache(self, model_cfg, **geometry) -> "IndexedLatentPagedCache":
        latent = super().init_cache(model_cfg, **geometry)
        return IndexedLatentPagedCache(
            index_keys=jnp.zeros(latent.rows.shape[:-1] + (self.index_row,),
                                 latent.rows.dtype),
            counts=jnp.zeros((self.device_counts[0].entries,), jnp.int32),
            **{f.name: getattr(latent, f.name)
               for f in dataclasses.fields(latent)})


@dataclasses.dataclass(frozen=True)
class StatePoolCache(FullCache):
    """A full K/V pool over the ``pool_layers`` attention layers only, and
    the family's named per-slot state leaves (:class:`StateLeaf`) for the
    layers that keep no K/V: a slot costs its leaves' bytes whatever its
    length and the pool's bytes a position, so the slots and not the
    blocks may be what bounds the batch. ``pack`` K/V heads lie side by
    side on one pool row (a head narrower than the 128 lanes: two heads
    of 64), which :func:`..ops.paged_attention.paged_attention` meets
    with each query head zero-padded into its share of the lanes. Block
    mapping, allocation, copy-on-write and preemption are
    :class:`FullCache`'s."""

    pool_layers: int = 0
    pack: int = 1
    leaves: Tuple[StateLeaf, ...] = ()
    #: as :attr:`LatentCache.moe_leaf`; its device may hold a share of the
    #: experts
    moe_leaf: DeviceCounts = MOE_KEPT_DROPPED_ELSEWHERE
    name = "state_pool"

    @property
    def counters(self) -> Tuple[CounterFamily, ...]:
        return super().counters + (STATE_BYTES_HELD, STATE_SEGMENT_ROWS)

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """As :meth:`FullCache.count_step`, the bytes the occupied slots
        hold (their entries in the leaves, by what each leaf is counted
        as, and their mapped blocks over the pool's layers) and the
        step's rows by their place in their slot's segment."""
        counts = super().count_step(geo, positions, slot_ids, tables, held,
                                    rolled)
        counts[STATE_SEGMENT_ROWS.name] = _count_segment_rows(positions,
                                                              slot_ids)
        a_slot = [sum(leaf.slot_bytes(geo.itemsize) for leaf in self.leaves
                      if leaf.counted_as == kind)
                  for kind in ("state", "tail")]
        counts[STATE_BYTES_HELD.name] = (
            len(held) * a_slot[0], len(held) * a_slot[1],
            self.pool_layers * sum(held) * geo.block_size * geo.row_values
            * geo.itemsize)
        return counts

    def init_cache(self, model_cfg, *, num_blocks: int, block_size: int,
                   table_rows: int, max_blocks_per_seq: int, dtype: Any,
                   quantized: bool = False) -> "StatePoolPagedCache":
        if quantized:
            raise ValueError("a state_pool cache has no int8 pool: its "
                             "states are float32 beside it")
        kv, d = model_cfg.num_kv_heads, model_cfg.head_dim_
        if kv % self.pack:
            raise ValueError(f"{kv} K/V heads do not lie {self.pack} a "
                             "pool row")
        pool = (self.pool_layers, num_blocks, block_size, kv // self.pack,
                d * self.pack)
        return StatePoolPagedCache(
            k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
            states=init_state_leaves(self.leaves, table_rows, dtype),
            moe_counts=(jnp.zeros((self.moe_leaf.entries,), jnp.int32)
                        if model_cfg.serving_family().moe_counts else None),
            pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
            block_tables=jnp.full((table_rows, max_blocks_per_seq), -1,
                                  jnp.int32),
            lengths=jnp.zeros((table_rows,), jnp.int32),
            block_size=block_size)


@dataclasses.dataclass(frozen=True)
class WindowPoolCache(FullCache):
    """Two kinds of block in one cache, for a model whose layers are
    full-attention or sliding-window. The ``full_layers`` keep every
    position: a K/V pool of their own under the one block table and the
    one allocator, :class:`FullCache`'s in every respect (position ``p`` in
    column ``p // block_size``; mapping, admission, preemption,
    copy-on-write and the freed-position hygiene count and touch these
    blocks alone). The ``window_layers`` attend the last ``window``
    positions and keep no others: a second pool in which a slot owns a
    ring of ``window_ring = window / block_size + 1`` blocks for as long
    as it is a slot (blocks ``slot * ring .. slot * ring + ring - 1``,
    block ``b`` of the sequence in ring column ``b % ring``; the spare
    block lets a packed chunk of up to ``block_size`` rows overwrite only
    rows that no row of the same step still attends). A ring is no
    allocation: it never exhausts, the host maps, releases and clears
    nothing of it, and a request that takes a slot over (admission, or
    re-admission after preemption) overwrites it from position 0. Stale
    ring rows hold positions behind every window that can see them, or a
    former tenant's positions beyond the new one's, and fall to the
    attention mask by their stored positions
    (:func:`..ops.paged_attention.paged_attention`, ``sliding``). So the
    window layers cost ``ring`` blocks a slot whatever the context, where
    one table for all layers would cost them the context's."""

    full_layers: int = 0
    window_layers: int = 0
    window: int = 0
    #: ``(K/V heads, K row, V row)`` of a position in the full layers'
    #: pool and in the rings, from the family. A K row wider than the V
    #: row lies in whole lanes
    #: (:func:`..ops.paged_attention.keys_to_lanes`)
    full_rows: Tuple[int, int, int] = (0, 0, 0)
    window_rows: Tuple[int, int, int] = (0, 0, 0)
    name = "window_pool"
    #: as :attr:`LatentCache.moe_leaf`; its device holds a share of the
    #: experts
    moe_leaf = MOE_KEPT_DROPPED_ELSEWHERE

    @property
    def counters(self) -> Tuple[CounterFamily, ...]:
        return super().counters + (WINDOW_COLUMNS, KV_BLOCKS_HELD,
                                   KV_BYTES_HELD, STEP_ROWS_BY_CONTEXT)

    def count_step(self, geo: StepGeometry, positions, slot_ids, tables,
                   held: Sequence[int], rolled: int) -> Dict[str, Any]:
        """As :meth:`FullCache.count_step` for the full layers, and for
        the window layers, by the sliding kernel's own guard: of the
        columns up to a row's position those its ring still holds, and
        the blocks the occupied slots hold, times the layers that hold
        them, in the full layers' pool and in the rings, their bytes by
        pool and operand, and the real rows by their position."""
        from ..ops.paged_attention import sliding_column_live

        counts = super().count_step(geo, positions, slot_ids, tables, held,
                                    rolled)
        bs = geo.block_size
        ring = self.window_ring(bs)
        at = positions[positions < PAD_POSITION]
        live = np.count_nonzero(sliding_column_live(
            0, np.arange(ring), at[:, None], bs, self.window, ring))
        counts[WINDOW_COLUMNS.name] = (live, (at // bs + 1).sum() - live)
        blocks = (self.full_layers * sum(held),
                  self.window_layers * sum(min(n, ring) for n in held))
        counts[KV_BLOCKS_HELD.name] = blocks
        counts[KV_BYTES_HELD.name] = tuple(
            n * bs * kv * width * geo.itemsize
            for n, (kv, *widths) in zip(blocks, (self.full_rows,
                                                 self.window_rows))
            for width in widths)
        counts[STEP_ROWS_BY_CONTEXT.name] = _rows_by_context(positions)
        return counts

    def geometry(self, block_size: int, step_rows: int = 0
                 ) -> "WindowPoolCache":
        if self.window % block_size:
            raise ValueError(
                f"window_pool cache: a window of {self.window} positions "
                f"is whole blocks; got block_size {block_size}")
        if step_rows > block_size:
            raise ValueError(
                f"window_pool cache: a packed step of {step_rows} rows "
                f"overwrites ring rows that its first rows still attend; "
                f"token_budget must not exceed block_size ({block_size})")
        return self

    def row_values(self, model_cfg) -> int:
        kv, k_row, v_row = self.full_rows
        return kv * (k_row + v_row)

    def window_ring(self, block_size: int) -> int:
        """Blocks of a slot's ring in the window pool."""
        return self.window // block_size + 1

    def init_cache(self, model_cfg, *, num_blocks: int, block_size: int,
                   table_rows: int, max_blocks_per_seq: int, dtype: Any,
                   quantized: bool = False) -> "WindowPoolPagedCache":
        if quantized:
            raise ValueError("a window_pool cache has no int8 pool: the "
                             "ring's rows want scales of their own")
        self.geometry(block_size)
        ring_blocks = table_rows * self.window_ring(block_size)

        def pool(layers, blocks, rows):
            kv, k_row, v_row = rows
            lead = (layers, blocks, block_size)
            return (jnp.zeros(lead + ((kv, k_row) if k_row == v_row
                                      else (kv * k_row,)), dtype),
                    jnp.zeros(lead + (kv, v_row), dtype))

        k, v = pool(self.full_layers, num_blocks, self.full_rows)
        wk, wv = pool(self.window_layers, ring_blocks, self.window_rows)
        return WindowPoolPagedCache(
            k=k, v=v, wk=wk, wv=wv,
            wpos=jnp.full((ring_blocks, block_size), PAD_POSITION,
                          jnp.int32),
            moe_counts=(jnp.zeros((self.moe_leaf.entries,), jnp.int32)
                        if model_cfg.serving_family().moe_counts else None),
            pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
            block_tables=jnp.full((table_rows, max_blocks_per_seq), -1,
                                  jnp.int32),
            lengths=jnp.zeros((table_rows,), jnp.int32),
            block_size=block_size)


@dataclasses.dataclass(frozen=True)
class CountedFullCache(FullCache):
    """:class:`FullCache` for a family of routed experts that declares
    ``moe_counts`` and holds every expert: the cache is a
    :class:`StatePoolPagedCache` over all the layers with no state leaf,
    so that the step has a ``moe_counts [2]`` leaf to count into. Block
    mapping, the host's counters and everything else are
    :class:`FullCache`'s."""

    moe_leaf = MOE_KEPT_DROPPED

    def init_cache(self, model_cfg, *, quantized: bool = False, **geometry):
        if quantized:
            raise ValueError("a counted full cache has no int8 pool")
        return StatePoolCache(
            pool_layers=model_cfg.num_layers, moe_leaf=self.moe_leaf
        ).init_cache(model_cfg, **geometry)


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    """What :class:`.engine.ServingEngine` asks of a model config
    (``model_cfg.serving_family()``): the cached forward with the
    ``llama_forward_with_cache`` paged signature, the cache kind its
    table rows follow, and the engine features the family cannot serve,
    each with why (refused by name at construction: ``prefix_sharing``,
    ``speculation``, ``cp``, ``quantized``, ``session_export``).
    ``moe_counts``: the family's forward leaves in the cache's
    ``moe_counts`` the routed-expert assignments of the step's real
    rows that were kept and that were dropped (``[2]``; ``[3]`` where
    the device holds a share of the experts: and those that chose an
    expert held elsewhere; ``[4]`` where the router also scores identity
    experts: and the choices of one, the first three then of the real
    experts alone; ``[5]`` where a share's family counts its experts
    instead: and the held experts the step hit and left idle), which the
    engine fetches with the step's tokens (``nxd_moe_assignments_total``,
    ``nxd_moe_held_total``, ``nxd_moe_identity_total``,
    ``nxd_moe_experts_hit_total``); the family's cache kind builds the
    leaf (its ``moe_leaf``). ``block``: how a family that decodes a block
    of positions at a time decodes it
    (:class:`.sampling.BlockDecoding`; None: a token a sequence a step).
    The engine then packs a decoding slot's whole block a step, keeps the
    block's tokens on the device between passes and delivers a block when
    its store pass has run."""

    forward: Callable
    cache_kind: Any = FULL_CACHE
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
    moe_counts: bool = False
    block: Any = None

    def device_counts(self) -> Tuple[DeviceCounts, ...]:
        """The leaves of the family's cache that its step counts into:
        the kind's own and, where the family declares ``moe_counts``,
        that leaf as the kind lays it out."""
        return tuple(self.cache_kind.device_counts) + (
            (self.cache_kind.moe_leaf,) if self.moe_counts else ())

    def counters(self) -> Tuple[CounterFamily, ...]:
        """Every counter family of the family's step: those its kind
        counts on the host, those its leaves feed and, where it decodes
        blocks, the blocks' own."""
        return tuple(self.cache_kind.counters) + tuple(
            family for leaf in self.device_counts()
            for family, _ in leaf.reads) + (
                BLOCK_COUNTERS if self.block is not None else ())


class _BlockPool:
    """The geometry of a cache whose pool leaves (``POOL_LEAVES``: what
    copy-on-write clones and block transport ships) are ``[layers,
    num_blocks, block_size, ...]``."""

    @property
    def num_blocks(self) -> int:
        return getattr(self, self.POOL_LEAVES[0]).shape[1]

    @property
    def capacity(self) -> int:
        shape = getattr(self, self.POOL_LEAVES[0]).shape
        return shape[1] * shape[2]

    @property
    def max_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def max_blocks_per_seq(self) -> int:
        return self.block_tables.shape[1]


class PagedKVCache(_BlockPool, struct.PyTreeNode):
    """Shared-pool paged cache.

    ``k``/``v`` ``[L, num_blocks, block_size, KV, D]``; ``pos``
    ``[num_blocks, block_size]`` true token position per pool slot
    (PAD_POSITION when empty; shared by all layers); ``block_tables``
    ``[max_slots, max_blocks_per_seq]`` int32, entry ``-1`` = unmapped;
    ``lengths`` ``[max_slots]`` int32 tokens resident per slot
    (host-maintained bookkeeping, not read by the compiled step).
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=16)
    POOL_LEAVES = ("k", "v")


class QuantizedPagedKVCache(_BlockPool, struct.PyTreeNode):
    """Int8 pool variant: K/V int8 with one fp32 scale per pool vector
    (``[L, num_blocks, block_size, KV]``), same symmetric per-vector
    scheme as :class:`.kv_cache.QuantizedKVCache` (``quantize_kv``)."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=16)
    POOL_LEAVES = ("k", "v", "k_scale", "v_scale")


class LatentPagedCache(_BlockPool, struct.PyTreeNode):
    """The cache of :class:`LatentCache`. ``rows`` ``[L, num_blocks,
    block_size, row]``: a position's latent, its rotary key and idle
    lanes, one row for all heads, and the only leaf that holds the
    sequence (no V, nothing a head), ``L`` the kind's layers of rows
    (:meth:`LatentCache.stack_index`: a decoder layer's attentions side by
    side); ``moe_counts``, where the family declares it
    (:class:`ServingFamily`; else None), the routed experts' assignments
    of the last step's real rows as the kind's ``moe_leaf`` lays them out
    (kept and dropped; elsewhere and identity where the kind says so),
    summed over the expert layers; ``pos``, ``block_tables`` and
    ``lengths`` as :class:`PagedKVCache`."""

    rows: jax.Array
    moe_counts: Optional[jax.Array]
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=128)
    POOL_LEAVES = ("rows",)


class IndexedLatentPagedCache(LatentPagedCache):
    """The cache of :class:`IndexedLatentCache`: :class:`LatentPagedCache`
    with ``index_keys`` ``[L, num_blocks, block_size, index_row]``, a
    position's index key beside its row in ``rows`` (the same layer, block
    and slot), and ``counts``, what the last step's selections did
    (:data:`..ops.indexed_attention.COUNT_KINDS`, summed over the
    layers)."""

    index_keys: jax.Array = None
    counts: jax.Array = None
    POOL_LEAVES = ("rows", "index_keys")


class StatePoolPagedCache(_BlockPool, struct.PyTreeNode):
    """The cache of :class:`StatePoolCache`. ``k``/``v`` ``[La,
    num_blocks, block_size, KV / pack, D * pack]`` over the ``La``
    attention layers; ``states`` the family's per-slot leaves by name
    (:class:`StateLeaf`: ``lead + (table rows,) + trail`` each);
    ``moe_counts`` (the kind's ``moe_leaf``: ``[3]``, or ``[5]``) where
    the family declares it (:class:`ServingFamily`; else None); ``pos``,
    ``block_tables`` and ``lengths`` as :class:`PagedKVCache`."""

    k: jax.Array
    v: jax.Array
    states: Dict[str, jax.Array]
    moe_counts: Optional[jax.Array]
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=128)
    POOL_LEAVES = ("k", "v")


class WindowPoolPagedCache(_BlockPool, struct.PyTreeNode):
    """The cache of :class:`WindowPoolCache`. ``k``/``v`` ``[Lf,
    num_blocks, block_size, KV, D]`` over the ``Lf`` full-attention
    layers, with ``pos``, ``block_tables`` and ``lengths`` as
    :class:`PagedKVCache`; ``wk``/``wv`` ``[Lw, table rows * ring,
    block_size, KV, D]`` over the ``Lw`` sliding-window layers, a table
    row's ring the blocks ``row * ring ..`` (no table: a ring is its
    slot's), and ``wpos [table rows * ring, block_size]`` the positions
    its rows hold; ``moe_counts [3]`` where the family declares it
    (:class:`ServingFamily`; else None)."""

    k: jax.Array
    v: jax.Array
    wk: jax.Array
    wv: jax.Array
    wpos: jax.Array
    moe_counts: Optional[jax.Array]
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=128)
    POOL_LEAVES = ("k", "v")

    @property
    def window_ring(self) -> int:
        return self.wk.shape[1] // self.block_tables.shape[0]


class SparseStatePagedCache(struct.PyTreeNode):
    """The cache of :class:`SparseStateCache`. ``k``/``v`` ``[Ls,
    num_blocks, KV, block_size, D]`` over the ``Ls`` block-sparse layers
    (heads before slots: with two K/V heads a minor pair ``(KV, D)`` would
    be padded to the 16-row tile, eight times its bytes); ``ck`` ``[Ls,
    num_blocks * block_size / stride, KV * D]`` their compressed keys,
    entry ``b * block_size / stride + i`` the kernel that starts at slot
    ``stride * i`` of block ``b``; ``state`` ``[Ll, H, table rows, D, D]``
    float32 over the ``Ll`` lightning layers (a slot's ``S^T`` a head:
    :func:`..ops.lightning_attention.lightning_attention_packed`); ``counts [10]`` what the last
    step's selections attended
    (:data:`..ops.sparse_attention.COUNT_KINDS`, summed over the sparse
    layers); ``pos``, ``block_tables`` and ``lengths`` as
    :class:`PagedKVCache`."""

    k: jax.Array
    v: jax.Array
    ck: jax.Array
    state: jax.Array
    counts: jax.Array
    pos: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    block_size: int = struct.field(pytree_node=False, default=128)

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def capacity(self) -> int:
        return self.k.shape[1] * self.k.shape[3]

    @property
    def max_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def max_blocks_per_seq(self) -> int:
        return self.block_tables.shape[1]


class PagedCacheView(struct.PyTreeNode):
    """The pool's whole stacks, the layer the holder is at, and this
    step's routing arrays, threaded through ``LlamaDecoderLayer`` in
    place of the contiguous ``(k, v, slot_pos)`` cache tuple. ``k``/``v``
    (and the int8 pool's scales) are ``[L, num_blocks, block_size, ...]``
    and ride the layer scan as its carry: a layer writes and reads rows
    at ``(layer, block)`` and never holds a slice of its own (a slice is
    a copy of one layer's pool in and another out, every layer of every
    step). ``tables [T, max_blocks_per_seq]`` is the per-token block
    table (each packed token carries its own slot's row); ``write_idx
    [T]`` is the precomputed flat index, within a layer, of this step's
    K/V rows (== pool capacity for rows that must not land:
    :func:`write_pool_rows`). ``walk`` is the attention kernel's routing
    of the step (:func:`..ops.paged_attention.step_walk`: which pool
    blocks each tile of rows fetches; None where the XLA path serves).
    ``roll`` is the routing of the summaries a window-summary family
    writes in this step (:func:`window_roll`; None for a full cache).
    ``sliding`` is the causal window of a sliding-window layer, whose
    ``k``/``v``, ``pos``, ``tables`` and ``write_idx`` are then its ring
    pool's (:func:`ring_write_indices`; None: every earlier position)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    layer: jax.Array
    pos: jax.Array
    tables: jax.Array
    write_idx: jax.Array
    walk: Any = None
    roll: Any = None
    sliding: Optional[int] = struct.field(pytree_node=False, default=None)


class SparseLayerView(struct.PyTreeNode):
    """What a block-sparse layer of a :class:`SparseStatePagedCache` is
    handed in :class:`PagedCacheView`'s place: the K/V and compressed-key
    stacks and the running ``counts`` (the layer scan's carry), the
    layer's index in them, the per-token block tables, the flat write
    indices, the rows' true positions (PAD_POSITION for padding) and the
    step's walk of the compressed keys
    (:func:`..ops.sparse_attention.score_walk`: which keys each tile of
    rows scores, the same for every sparse layer; None: the layer builds
    its own)."""

    k: jax.Array
    v: jax.Array
    ck: jax.Array
    counts: jax.Array
    layer: jax.Array
    tables: jax.Array
    write_idx: jax.Array
    q_pos: jax.Array
    walk: Any = None


class LatentLayerView(struct.PyTreeNode):
    """What a latent-attention layer is handed in :class:`PagedCacheView`'s
    place: the row stack (the layer scan's carry), the layer's index in
    it, the pool's positions, the per-token block tables, the flat write
    indices, the rows' true positions (PAD_POSITION for padding) and the
    kernel's walk of the step (None where the XLA path serves). Of an
    :class:`IndexedLatentPagedCache` also the index keys' stack and the
    selections' counts so far in the step (both the scan's carry; None for
    a plain latent cache) and the table's rows (``slots``)."""

    rows: jax.Array
    layer: jax.Array
    pos: jax.Array
    tables: jax.Array
    write_idx: jax.Array
    q_pos: jax.Array
    walk: Any = None
    index_keys: Optional[jax.Array] = None
    counts: Optional[jax.Array] = None
    slots: int = struct.field(pytree_node=False, default=0)

    def next_attention(self) -> "LatentLayerView":
        """The view of the same decoder layer's next latent attention
        (:meth:`LatentCache.stack_index`: side by side), over the stack as
        the attention that handed this view back left it."""
        return self.replace(layer=self.layer + 1)


class StateLayerView(struct.PyTreeNode):
    """What a lightning layer is handed: the per-slot state stack (the
    layer scan's carry), the layer's index in it, and the rows' slots and
    true positions."""

    state: jax.Array
    layer: jax.Array
    slot_ids: jax.Array
    q_pos: jax.Array


class StateSpaceLayerView(struct.PyTreeNode):
    """What a state-space (Mamba-2) or a delta-rule (KDA) layer is
    handed: its two per-slot state leaves' stacks (the layer scan's carry:
    ``ssm`` the recurrence's float32 states, ``[L, J, d_state, d_inner]``
    or ``[L, J, H, dk, dv]``, and the convolution's tails ``conv [L,
    d_conv - 1, J, channels]``), the layer's index in them, and the step's
    rows by slot (:class:`..ops.ssd.StepSegments`, built once a step)."""

    ssm: jax.Array
    conv: jax.Array
    layer: jax.Array
    seg: Any


class CPPrefillView(struct.PyTreeNode):
    """The LOCAL pool shard's stacks, the layer the holder is at, and
    this rank's write routing for context-parallel ring prefill: the
    attention itself is ring attention over the cp axis (no block-table
    gather — every rank sees the whole prompt via the rotating KV
    chunks), so only the scatter routing rides: ``write_idx [W_local]``
    flat indices into a layer of this rank's pool shard (pool capacity =
    drop, for pad rows and rows another rank owns)."""

    k: jax.Array
    v: jax.Array
    layer: jax.Array
    pos: jax.Array
    write_idx: jax.Array


# Registered for jax.export bundles like the contiguous caches
# (model_builder packages the KV state spec in its manifest).
try:
    from jax import export as _jax_export

    for _cls, _nm in ((PagedKVCache, "PagedKVCache"),
                      (QuantizedPagedKVCache, "QuantizedPagedKVCache")):
        _jax_export.register_pytree_node_serialization(
            _cls,
            serialized_name=f"neuronx_distributed_tpu.inference.{_nm}",
            serialize_auxdata=lambda aux: json.dumps(list(aux)).encode(),
            deserialize_auxdata=lambda b: tuple(json.loads(b)))
except ValueError:  # pragma: no cover - double import/registration
    pass


def init_paged_kv_cache(num_layers: int, num_blocks: int, block_size: int,
                        num_kv_heads: int, head_dim: int, max_slots: int,
                        max_blocks_per_seq: int,
                        dtype: Any = jnp.bfloat16) -> PagedKVCache:
    shape = (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
        block_tables=jnp.full((max_slots, max_blocks_per_seq), -1,
                              jnp.int32),
        lengths=jnp.zeros((max_slots,), jnp.int32),
        block_size=block_size)


def pool_accounting(num_layers: int, num_blocks: int, block_size: int,
                    num_kv_heads: int, head_dim: int, *,
                    kv_bytes: int = 2, quantized: bool = False,
                    tp_size: int = 1, cp_size: int = 1) -> float:
    """Bytes per device for the K+V pool arrays the two init functions
    above allocate (K and V of shape ``[L, num_blocks, block_size, KV,
    D]``; the quantized variant stores int8 plus one fp32 scale per pool
    vector, i.e. per ``shape[:-1]`` entry). The KV-head dimension shards
    over ``tp_size``; under context-parallel serving the BLOCK dimension
    shards over ``cp_size`` (each cp rank is resident for ``num_blocks /
    cp_size`` blocks — the long-context memory term: total pool blocks ÷
    cp per device). The placement planner's memory model (``plan.cost``)
    charges serving plans through this function so its numbers track the
    engine's real allocations."""
    if cp_size < 1:
        raise ValueError(f"cp_size must be >= 1, got {cp_size}")
    elems = num_layers * num_blocks * block_size * num_kv_heads * head_dim
    if quantized:
        per_pool = elems * 1 + (elems // max(1, head_dim)) * 4
    else:
        per_pool = elems * kv_bytes
    return 2.0 * per_pool / max(1, tp_size) / cp_size


def init_quantized_paged_kv_cache(num_layers: int, num_blocks: int,
                                  block_size: int, num_kv_heads: int,
                                  head_dim: int, max_slots: int,
                                  max_blocks_per_seq: int
                                  ) -> QuantizedPagedKVCache:
    shape = (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
    return QuantizedPagedKVCache(
        k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
        k_scale=jnp.ones(shape[:-1], jnp.float32),
        v_scale=jnp.ones(shape[:-1], jnp.float32),
        pos=jnp.full((num_blocks, block_size), PAD_POSITION, jnp.int32),
        block_tables=jnp.full((max_slots, max_blocks_per_seq), -1,
                              jnp.int32),
        lengths=jnp.zeros((max_slots,), jnp.int32),
        block_size=block_size)


def _uniform_pool_cache(model_cfg, *, num_blocks: int, block_size: int,
                        table_rows: int, max_blocks_per_seq: int, dtype: Any,
                        quantized: bool = False):
    """One uniform K/V pool over every layer, float or int8."""
    shape = (model_cfg.num_layers, num_blocks, block_size,
             model_cfg.num_kv_heads, model_cfg.head_dim_, table_rows,
             max_blocks_per_seq)
    if quantized:
        return init_quantized_paged_kv_cache(*shape)
    return init_paged_kv_cache(*shape, dtype=dtype)


def init_serving_cache(model_cfg, *, num_blocks: int, block_size: int,
                       table_rows: int, max_blocks_per_seq: int, dtype: Any,
                       quantized: bool = False):
    """The cache a model family is served from, built by its cache kind
    (``model_cfg.serving_family().cache_kind``) from the model config and
    the engine's geometry: what :class:`.engine.ServingEngine` holds, and
    its draft model's pool. A full and a window-summary cache are
    :func:`init_paged_kv_cache`'s (``quantized``:
    :func:`init_quantized_paged_kv_cache`'s) pytree; a
    :class:`SparseStateCache` is a :class:`SparseStatePagedCache`, a
    :class:`LatentCache` a :class:`LatentPagedCache`, an
    :class:`IndexedLatentCache` an :class:`IndexedLatentPagedCache`, a
    :class:`StatePoolCache` a :class:`StatePoolPagedCache`, a
    :class:`WindowPoolCache` a :class:`WindowPoolPagedCache`."""
    return model_cfg.serving_family().cache_kind.init_cache(
        model_cfg, num_blocks=num_blocks, block_size=block_size,
        table_rows=table_rows, max_blocks_per_seq=max_blocks_per_seq,
        dtype=dtype, quantized=quantized)


# ---------------------------------------------------------------------------
# Host-side block allocation. Runs between compiled steps; the device only
# ever sees the resulting (fixed-shape) block tables.
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list over the shared pool's ``num_blocks`` block
    ids. ``alloc`` hands out blocks with refcount 1; :meth:`ref` lets a
    second owner (another slot sharing a prefix, or the
    :class:`PrefixCache` itself) pin the same block; :meth:`free` is an
    *unref* — a block returns to the free list only when its last
    reference drops, and :meth:`free` reports exactly which blocks did
    (the engine's freed-position hygiene must clear those, and only
    those: wiping a still-shared block's positions would blind every
    surviving reader).

    ``cp_size > 1`` splits the id space into ``cp_size`` contiguous rank
    slices (rank ``r`` owns ``[r * num_blocks/cp, (r+1) * num_blocks/cp)``
    — exactly how the engine shards the pool's block dim over the ``cp``
    mesh axis). ``alloc(rank=r)`` is strict placement (CP ring prefill:
    a token's K/V rows are computed on the rank holding its sequence
    slice and must land there); ``alloc(rank=None)`` spills to whichever
    slice has the most free blocks (decode blocks — the flash-decoding
    combine is position-masked, so any rank may own any decode block) and
    raises :class:`CacheExhaustedError` only when *every* rank's slice is
    exhausted of the remaining demand."""

    def __init__(self, num_blocks: int, cp_size: int = 1):
        if num_blocks <= 0:
            raise ValueError(f"num_blocks must be positive, got {num_blocks}")
        if cp_size < 1:
            raise ValueError(f"cp_size must be >= 1, got {cp_size}")
        if num_blocks % cp_size != 0:
            raise ValueError(
                f"num_blocks ({num_blocks}) must divide evenly over "
                f"cp_size ({cp_size}) rank slices")
        self.num_blocks = num_blocks
        self.cp_size = cp_size
        self.blocks_per_rank = num_blocks // cp_size
        self.reset()

    def rank_of(self, block: int) -> int:
        """cp rank whose pool slice holds ``block``."""
        return block // self.blocks_per_rank

    @property
    def num_free(self) -> int:
        return sum(len(f) for f in self._free)

    def free_per_rank(self) -> List[int]:
        """Free-block count per cp rank slice (``[num_free]`` at cp=1)."""
        return [len(f) for f in self._free]

    @property
    def num_allocated(self) -> int:
        return self.num_blocks - self.num_free

    @property
    def num_shared(self) -> int:
        """Blocks currently held by more than one reference."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def alloc(self, n: int = 1, rank: Optional[int] = None) -> List[int]:
        """Take ``n`` blocks off the free list (refcount 1 each); raises
        :class:`CacheExhaustedError` (allocating nothing) when fewer than
        ``n`` are free — the caller decides whether to preempt, defer, or
        reject. ``rank`` pins the allocation to one cp rank's slice
        (strict: raises when *that slice* cannot cover ``n``); ``None``
        balances across slices and fails only when the whole pool can't."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if rank is not None:
            if not 0 <= rank < self.cp_size:
                raise ValueError(
                    f"rank {rank} out of range for cp_size {self.cp_size}")
            pool = self._free[rank]
            if n > len(pool):
                raise CacheExhaustedError(
                    f"requested {n} block(s) on cp rank {rank} but only "
                    f"{len(pool)} of {self.blocks_per_rank} are free")
            out = [pool.pop() for _ in range(n)]
        else:
            if n > self.num_free:
                raise CacheExhaustedError(
                    f"requested {n} block(s) but only {self.num_free} of "
                    f"{self.num_blocks} are free")
            out = []
            for _ in range(n):
                out.append(max(self._free, key=len).pop())
        self._allocated.update(out)
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, block: int) -> None:
        """Add a reference to an already-allocated block."""
        if block not in self._allocated:
            raise ValueError(f"cannot ref unallocated block {block}")
        self._refs[block] += 1

    def free(self, blocks: Sequence[int]) -> List[int]:
        """Drop one reference per listed block; returns the blocks whose
        refcount hit zero and were actually returned to the free list."""
        freed: List[int] = []
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(
                    f"block {b} is not allocated (double free?)")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._allocated.discard(b)
                self._free[self.rank_of(b)].append(b)
                freed.append(b)
        return freed

    def reset(self) -> None:
        # lowest block ids pop first (per rank slice) — keeps tests/debug
        # dumps readable
        self._free = [
            list(range((r + 1) * self.blocks_per_rank - 1,
                       r * self.blocks_per_rank - 1, -1))
            for r in range(self.cp_size)]
        self._allocated: set = set()
        self._refs: dict = {}


# ---------------------------------------------------------------------------
# jit-compatible pool writes. Allocation already happened on the host; the
# device work is pure index arithmetic + scatter with OOB-drop, so these
# trace into the fixed-shape serving step.
# ---------------------------------------------------------------------------

def flat_write_indices(tok_tables: jax.Array, positions: jax.Array,
                       block_size: int, capacity: int,
                       kind=FULL_CACHE) -> jax.Array:
    """``[T, max_blocks_per_seq]`` per-token block tables + ``[T]`` true
    positions -> ``[T]`` flat pool indices within a layer. This is where
    the pool's layout is decided: position ``p`` of a sequence lives in
    the block its table names in column ``kind.column_of(p)`` (a full
    cache: ``p // block_size``), at slot ``p % block_size``, in every
    layer alike; the paged kernel's walk
    (:func:`..ops.paged_attention.column_live`, ``window_column_kinds``)
    and the engine's block mapping rely on it. Rows whose position is
    padding (PAD_POSITION), beyond the table, or mapped to ``-1`` get
    index == ``capacity`` — out of bounds of a layer's rows, so the
    ``mode="drop"`` scatters discard them (:func:`write_pool_rows`)."""
    blk_of_pos = kind.column_of(positions, block_size)
    maxb = tok_tables.shape[1]
    safe = jnp.clip(blk_of_pos, 0, maxb - 1)
    blk = jnp.take_along_axis(tok_tables, safe[:, None], axis=1)[:, 0]
    flat = blk * block_size + positions % block_size
    valid = (positions < PAD_POSITION) & (blk_of_pos < maxb) & (blk >= 0)
    return jnp.where(valid, flat, capacity)


def ring_write_indices(slot_ids: jax.Array, positions: jax.Array,
                       block_size: int, ring: int, table_rows: int):
    """``(tables [T, ring], flat [T])`` of a sliding-window layer's pool
    (:class:`WindowPoolCache`) for the packed rows: a row's ring (block
    ``slot * ring + c`` in column ``c``; -1 for a row without a slot) and
    the flat index within a layer of its own K/V row (position ``p`` in
    ring column ``(p // block_size) % ring``, slot ``p % block_size``;
    the pool's capacity, which the scatters drop, for a pad row)."""
    real = ((positions < PAD_POSITION) & (slot_ids >= 0)
            & (slot_ids < table_rows))
    base = jnp.where(real, slot_ids, 0) * ring
    tables = jnp.where(
        real[:, None], base[:, None] + jnp.arange(ring, dtype=jnp.int32),
        -1)
    safe = jnp.where(real, positions, 0)
    flat = ((base + (safe // block_size) % ring) * block_size
            + safe % block_size)
    return tables, jnp.where(real, flat, table_rows * ring * block_size)


def write_pool_rows(pool: jax.Array, rows: jax.Array,
                    flat_idx: jax.Array, layer) -> jax.Array:
    """Scatter ``rows [T, ...]`` into layer ``layer`` of the stack
    ``pool [L, num_blocks, block_size, ...]`` at the flat indices from
    :func:`flat_write_indices`: one scatter into the stack, in place
    where the stack is the layer scan's carry.

    The pool's layout: a row of layer ``l``, block ``b``, slot ``s`` is
    ``pool[l, b, s]``, and a flat index counts ``b * block_size + s``
    within one layer. The stack is therefore addressed as ``[L,
    num_blocks * block_size, ...]`` by the pair ``(layer, flat)`` and
    never as one run of ``L * capacity`` rows: the drop sentinel
    ``flat_idx == capacity`` lies past the end of its own column of the
    pair, so ``mode="drop"`` discards the row in every layer, where
    ``layer * capacity + capacity`` would be the next layer's first
    row."""
    n_layers, nb, bs = pool.shape[:3]
    flat = pool.reshape((n_layers, nb * bs) + pool.shape[3:])
    # a row's heads as the pool lays them (side by side on a row where
    # the cache kind packs them: the same values in the same order)
    rows = rows.reshape(rows.shape[:1] + pool.shape[3:])
    flat = flat.at[layer, flat_idx].set(rows.astype(pool.dtype),
                                        mode="drop")
    return flat.reshape(pool.shape)


def window_roll(kind: WindowSummaryCache, block_tables: jax.Array,
                slot_ids: jax.Array, positions: jax.Array,
                block_size: int, num_blocks: int):
    """Routing of the summaries this step writes, once for all layers:
    ``(any, src [S, bpw], dst [S])``. A slot whose packed rows hold the
    last position of a window ``w`` reads that window's ``bpw`` ring
    blocks ``src`` (in position order) and writes their ``block_size``
    chunk summaries into the block of column ``ring + w``, ``dst``
    (``num_blocks``, dropped by the scatter, for every other slot).
    A packed step is at most ``window`` rows, so a slot completes at most
    one window in it."""
    slots = block_tables.shape[0]
    ends = (positions < PAD_POSITION) & ((positions + 1) % kind.window == 0)
    done = jnp.full((slots,), -1, jnp.int32).at[slot_ids].max(
        jnp.where(ends, positions // kind.window, -1), mode="drop")
    bpw = kind.window // block_size
    w = jnp.maximum(done, 0)
    cols = (w[:, None] * bpw + jnp.arange(bpw, dtype=jnp.int32)) % kind.ring
    src = jnp.take_along_axis(block_tables, cols, axis=1)
    dst = jnp.take_along_axis(
        block_tables,
        jnp.minimum(kind.ring + w, block_tables.shape[1] - 1)[:, None],
        axis=1)[:, 0]
    ok = (done >= 0) & (dst >= 0) & (kind.ring + w < block_tables.shape[1])
    return (jnp.any(ok), jnp.clip(src, 0, num_blocks - 1),
            jnp.where(ok, dst, num_blocks))


def write_pool_positions(pos: jax.Array, positions: jax.Array,
                         flat_idx: jax.Array) -> jax.Array:
    """Record this step's true token positions in the ``[num_blocks,
    block_size]`` slot-position table (shared by all layers, written once
    per step)."""
    nb, bs = pos.shape
    flat = pos.reshape(nb * bs).at[flat_idx].set(
        positions.astype(pos.dtype), mode="drop")
    return flat.reshape(nb, bs)


def mask_pool_positions(pos: jax.Array, flat_idx: jax.Array,
                        reject: jax.Array) -> jax.Array:
    """Atomically un-publish pool rows: set the stored position of every
    ``flat_idx[i]`` with ``reject[i]`` back to PAD_POSITION, so those
    K/V rows can never pass the causal mask again. This is the
    speculation rollback — rejected draft-branch rows vanish in one
    fixed-shape scatter. Rows whose ``flat_idx`` is already out of bounds
    (pad rows, ``== capacity``) are dropped either way."""
    nb, bs = pos.shape
    idx = jnp.where(reject, flat_idx, nb * bs)
    flat = pos.reshape(nb * bs).at[idx].set(PAD_POSITION, mode="drop")
    return flat.reshape(nb, bs)


# ---------------------------------------------------------------------------
# Prefix sharing: a host-side trie over full prompt blocks. KV for a token
# depends only on (token, position, params), so two prompts with a common
# prefix produce bit-identical pool rows for it — the trie lets later
# requests map those rows instead of re-prefilling them.
# ---------------------------------------------------------------------------

class _PrefixNode:
    """One cached full block: ``tokens`` (a ``block_size`` tuple starting
    at position ``depth * block_size``), the pool block holding its KV,
    and the chain hash addressing it (hash of the whole token path from
    the root, so equal block content at different depths never collides
    semantically)."""

    __slots__ = ("chain", "parent", "tokens", "block", "tick")

    def __init__(self, chain: int, parent: Optional[int],
                 tokens: Tuple[int, ...], block: int, tick: int):
        self.chain = chain
        self.parent = parent
        self.tokens = tokens
        self.block = block
        self.tick = tick


class PrefixCache:
    """Trie of full prompt blocks → pool block ids.

    The cache holds one allocator reference per inserted block, so a
    cached block outlives the request that wrote it; a later request's
    :meth:`match` maps the longest cached prefix into its own table (the
    caller takes its own refs). Cached blocks are never written — a
    request that diverges *mid-block* copies first (see
    :func:`cow_copy_blocks`) — so sharing can't leak KV between tenants.
    Under pool pressure :meth:`evict` drops least-recently-matched leaf
    nodes until enough blocks actually return to the free list.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._nodes: Dict[int, _PrefixNode] = {}
        self._children: Dict[Optional[int], Set[int]] = {None: set()}
        self._tick = 0

    @property
    def size(self) -> int:
        return len(self._nodes)

    @staticmethod
    def _hash(parent: Optional[int], tokens: Tuple[int, ...]) -> int:
        return hash((parent, tokens))

    def _touch(self, node: _PrefixNode) -> None:
        self._tick += 1
        node.tick = self._tick

    def match(self, prompt: Sequence[int], max_tokens: int
              ) -> Tuple[List[int], int, Optional[Tuple[int, int]],
                         Optional[int]]:
        """Longest cached prefix of ``prompt``, capped at ``max_tokens``.

        Returns ``(full_blocks, matched, partial, chain)``: pool ids of
        fully-matched blocks, the token count they cover, an optional
        ``(block, m)`` partial-tail match (a cached block whose first
        ``m < block_size`` tokens extend the prefix — the one case that
        later forces a copy-on-write, since the mapper will write its own
        divergent rows mid-block), and the chain hash of the last full
        node (``None`` at the root) for continued insertion."""
        bs = self.block_size
        full: List[int] = []
        chain: Optional[int] = None
        matched = 0
        while matched + bs <= max_tokens:
            tokens = tuple(prompt[matched:matched + bs])
            child = self._hash(chain, tokens)
            node = self._nodes.get(child)
            if node is None or node.tokens != tokens:
                break
            self._touch(node)
            full.append(node.block)
            chain = child
            matched += bs
        partial: Optional[Tuple[int, int]] = None
        tail = tuple(prompt[matched:max_tokens])
        if tail:
            best, best_node = 0, None
            for child in self._children.get(chain, ()):
                node = self._nodes[child]
                m = 0
                for a, b in zip(node.tokens, tail):
                    if a != b:
                        break
                    m += 1
                if m > best:
                    best, best_node = m, node
            if best_node is not None:
                self._touch(best_node)
                partial = (best_node.block, best)
        return full, matched, partial, chain

    def lookup(self, prompt: Sequence[int], max_tokens: int) -> int:
        """Peek: how many tokens of ``prompt`` the cache covers right now
        (full blocks + partial tail), without touching recency."""
        bs = self.block_size
        chain: Optional[int] = None
        matched = 0
        while matched + bs <= max_tokens:
            tokens = tuple(prompt[matched:matched + bs])
            child = self._hash(chain, tokens)
            node = self._nodes.get(child)
            if node is None or node.tokens != tokens:
                break
            chain = child
            matched += bs
        best = 0
        tail = tuple(prompt[matched:max_tokens])
        if tail:
            for child in self._children.get(chain, ()):
                m = 0
                for a, b in zip(self._nodes[child].tokens, tail):
                    if a != b:
                        break
                    m += 1
                best = max(best, m)
        return matched + best

    def insert(self, parent: Optional[int], tokens: Sequence[int],
               block: int) -> Tuple[Optional[int], bool]:
        """Register ``block`` as holding the full block ``tokens`` under
        ``parent`` (a chain hash from :meth:`match`/a prior insert).

        Returns ``(chain, inserted)``. Idempotent: an existing node with
        the same tokens just advances the chain (``inserted`` False, the
        caller keeps its own block). ``(None, False)`` means the chain is
        unusable — hash collision, or the parent node was evicted — and
        the caller should stop inserting for this request."""
        tokens = tuple(tokens)
        if len(tokens) != self.block_size:
            raise ValueError(
                f"prefix nodes cache full blocks only: got {len(tokens)} "
                f"tokens for block_size {self.block_size}")
        if parent is not None and parent not in self._nodes:
            return None, False
        chain = self._hash(parent, tokens)
        node = self._nodes.get(chain)
        if node is not None:
            if node.tokens != tokens:     # hash collision: leave the trie
                return None, False        # alone, stop this chain
            self._touch(node)
            return chain, False
        self.allocator.ref(block)
        node = _PrefixNode(chain, parent, tokens, block, 0)
        self._touch(node)
        self._nodes[chain] = node
        self._children.setdefault(parent, set()).add(chain)
        self._children.setdefault(chain, set())
        return chain, True

    def snapshot(self, max_nodes: Optional[int] = None
                 ) -> List[Dict[str, Any]]:
        """Portable dump of (up to ``max_nodes``) trie nodes for shipping
        to another replica, hottest subtrees first.

        Chain hashes are process-local (Python ``hash``), so entries name
        their parent by *list index* instead: each entry is ``{"parent":
        index-into-this-list | None, "tokens": tuple, "block": local
        block id}``, and parents always precede their children — the
        importer replays the list in order, re-deriving its own chain
        hashes via :meth:`insert`. When truncating, whole root-to-leaf
        paths survive (a child never ships without its parent), ranked by
        the subtree's most recent match."""
        # hotness of a node = newest tick anywhere below it, so a hot
        # leaf keeps its whole ancestor path ahead of cold siblings
        hot: Dict[int, int] = {}

        def heat(chain: int) -> int:
            got = hot.get(chain)
            if got is None:
                node = self._nodes[chain]
                got = max([node.tick] + [heat(c) for c in
                                         self._children.get(chain, ())])
                hot[chain] = got
            return got

        out: List[Dict[str, Any]] = []
        index: Dict[int, int] = {}

        def walk(parent: Optional[int]) -> None:
            kids = sorted(self._children.get(parent, ()),
                          key=heat, reverse=True)
            for chain in kids:
                if max_nodes is not None and len(out) >= max_nodes:
                    return
                node = self._nodes[chain]
                index[chain] = len(out)
                out.append({"parent": index.get(parent),
                            "tokens": node.tokens, "block": node.block})
                walk(chain)

        walk(None)
        return out

    def chain_of(self, parent: Optional[int],
                 tokens: Sequence[int]) -> Optional[int]:
        """Chain hash of the live node for ``tokens`` under ``parent``,
        or None — lets a snapshot importer resolve local chains without
        re-inserting."""
        chain = self._hash(parent, tuple(tokens))
        node = self._nodes.get(chain)
        if node is None or node.tokens != tuple(tokens):
            return None
        return chain

    def evict(self, want_free: int) -> List[int]:
        """Drop least-recently-matched *leaf* nodes until ``want_free``
        blocks have actually returned to the pool (a dropped node whose
        block other slots still reference frees nothing — keep going).
        Returns the block ids that did free, so the engine can schedule
        its freed-position hygiene for them."""
        freed: List[int] = []
        while len(freed) < want_free:
            leaves = [n for n in self._nodes.values()
                      if not self._children.get(n.chain)]
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.tick)
            freed.extend(self._remove(victim))
        return freed

    def clear(self) -> List[int]:
        """Drop every node (e.g. engine teardown); returns the blocks
        that actually returned to the free list."""
        freed: List[int] = []
        for node in list(self._nodes.values()):
            if node.chain in self._nodes:
                freed.extend(self._remove(node))
        return freed

    def _remove(self, node: _PrefixNode) -> List[int]:
        del self._nodes[node.chain]
        self._children.pop(node.chain, None)
        self._children.get(node.parent, set()).discard(node.chain)
        return self.allocator.free([node.block])


# ---------------------------------------------------------------------------
# Block transport: lift a block set out of one replica's pool / land it in
# another's. Used by live session migration (router drain/preempt) and by
# prefix-trie warm-up of fresh replicas. Eager host-side code — migrations
# happen at step boundaries, never inside the compiled step, and the two
# pools generally live in different engines (possibly different processes
# round-tripped through pickle), so there is nothing to fuse.
# ---------------------------------------------------------------------------

#: Block axis of each :func:`extract_blocks` payload tensor — ``k``/``v``
#: (and their scales) are pool-shaped ``[layers, blocks, ...]`` gathered on
#: axis 1, ``pos`` is ``[blocks, block_size]``. Single source of truth for
#: per-block integrity fingerprints over shipped payloads
#: (``resilience.integrity.kv_payload_fingerprints``).
PAYLOAD_BLOCK_AXES = {"k": 1, "v": 1, "pos": 0, "k_scale": 1, "v_scale": 1,
                      "rows": 1, "index_keys": 1}


def extract_blocks(cache: Any, blocks: Sequence[int],
                   keep_upto: int) -> Dict[str, Any]:
    """Lift ``blocks`` out of the pool as host arrays.

    Rows with stored position ``>= keep_upto`` are masked to
    ``PAD_POSITION`` in the extracted ``pos`` (same hygiene as
    :func:`cow_copy_blocks`): a migrating session must not carry another
    tenant's stale rows, only its own ``n_cached`` tokens. Pass
    ``keep_upto=PAD_POSITION`` to keep every real row (prefix-trie
    shipments, where the block is full by construction). The payload is
    ordered like ``blocks`` and is self-contained — :func:`inject_blocks`
    lands it at arbitrary block ids in an arbitrary compatible pool."""
    idx = jnp.asarray(list(blocks), jnp.int32)
    pos = jnp.take(cache.pos, idx, axis=0)
    pos = jnp.where(pos < keep_upto, pos, PAD_POSITION)
    payload = {name: jnp.take(getattr(cache, name), idx, axis=1)
               for name in cache.POOL_LEAVES}
    payload["pos"] = pos
    return {name: jax.device_get(arr) for name, arr in payload.items()}


def inject_blocks(cache: Any, blocks: Sequence[int],
                  payload: Dict[str, Any]) -> Any:
    """Land an :func:`extract_blocks` payload at ``blocks`` (same order,
    freshly allocated by the destination). Every row of the target
    blocks — K, V, and positions — is overwritten by the payload, so the
    destination needs no freed-position wipe for them."""
    if len(blocks) != payload["pos"].shape[0]:
        raise ValueError(
            f"payload carries {payload['pos'].shape[0]} block(s) but "
            f"{len(blocks)} destination ids were given")
    idx = jnp.asarray(list(blocks), jnp.int32)
    updates = {name: getattr(cache, name).at[:, idx].set(
        jnp.asarray(payload[name], getattr(cache, name).dtype))
        for name in cache.POOL_LEAVES}
    updates["pos"] = cache.pos.at[idx].set(
        jnp.asarray(payload["pos"], jnp.int32))
    return cache.replace(**updates)


# ---------------------------------------------------------------------------
# Copy-on-write. Fixed-shape and jitted: the engine batches this step's
# pending copies into [M] src/dst/keep arrays (pad entries carry dst ==
# num_blocks, dropped by the OOB scatters) so the clone pass compiles once.
# ---------------------------------------------------------------------------

@jax.jit
def cow_copy_blocks(cache: Any, src: jax.Array, dst: jax.Array,
                    keep_upto: jax.Array) -> Any:
    """Clone pool blocks ``src[i] → dst[i]`` before a writer lands in a
    shared block. Rows with stored position ``>= keep_upto[i]`` (the
    writer's first divergent position) become padding in the clone — the
    writer owns them from here on. Pad entries: ``src == 0, dst ==
    num_blocks`` (``mode="drop"`` discards them)."""

    def cp(pool):
        return pool.at[:, dst].set(jnp.take(pool, src, axis=1),
                                   mode="drop")

    rows_pos = jnp.take(cache.pos, src, axis=0)
    rows_pos = jnp.where(rows_pos < keep_upto[:, None], rows_pos,
                         PAD_POSITION)
    updates = {name: cp(getattr(cache, name)) for name in cache.POOL_LEAVES}
    updates["pos"] = cache.pos.at[dst].set(rows_pos, mode="drop")
    return cache.replace(**updates)
