"""Analytic cost model for parallelism placement.

The model behind ``python -m neuronx_distributed_tpu.plan`` (PAPERS.md
"Synthesizing Optimal Parallelism Placement and Reduction Strategies on
Hierarchical Systems", arXiv:2110.10548): a per-step time and per-device
memory estimate for one (mesh layout, reduction strategy) candidate, built
from

* **link tiers** — every mesh axis rides either ICI (within a slice) or
  DCN (across slices, the ``dcn_data_parallel_size`` portion of the dp
  axis). A ring collective over *n* ranks moves ``2·B·(n-1)/n`` bytes per
  rank for an all-reduce (half for reduce-scatter / all-gather) and pays
  ``n-1`` hop latencies per direction — the α-β model the paper's
  synthesizer scores reduction strategies with.
* **matmul shapes** from the model config (hidden/intermediate/heads/
  vocab/seq): dense-layer FLOPs give the compute term, the Megatron-SP
  activation footprint ``[tokens, hidden]`` gives the TP collective
  volume, the parameter count gives the gradient collective volume.
* **memory** — fp32 master params + grads + Adam moments (moments divided
  by the ZeRO-1 shard group), activations under remat/SP, and the paged-KV
  pool for serving plans (``inference.paging.pool_accounting``).

Pure Python/maths on purpose: no jax import at module load, so the ``plan``
lint rule and unit tests score thousands of candidates in milliseconds.
The two places the model must agree with runtime behavior exactly — the
TP-overlap engagement predicate and the compressed-collective wire ratio —
delegate to ``ops.collective_matmul.shapes_tile`` (lazily) and mirror
``parallel.comm_compressed.CompressionConfig.wire_bytes_per_element``
(regression-pinned in tests/test_plan.py).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional


# ---------------------------------------------------------------------------
# Hardware description
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LinkSpec:
    """One link tier: sustained per-rank bandwidth and per-hop latency."""

    bandwidth: float      # bytes/s each direction, per rank
    latency: float        # seconds per ring hop


@dataclass(frozen=True)
class HardwareSpec:
    """Per-device compute/memory plus the two link tiers.

    Defaults approximate a TPU-v4-class chip. The absolute numbers only
    set the scale — rankings depend on the *ratios* (ICI:DCN bandwidth,
    FLOPs:bandwidth), which is what the refinement mode re-measures.
    """

    name: str = "tpu"
    flops: float = 275e12          # peak bf16 FLOP/s per device
    mfu: float = 0.4               # achievable fraction on dense matmuls
    hbm_bytes: float = 32 * 2**30
    ici: LinkSpec = LinkSpec(bandwidth=9.0e10, latency=1e-6)
    dcn: LinkSpec = LinkSpec(bandwidth=3.125e9, latency=25e-6)
    #: fraction of HBM a plan may budget (runtime/XLA scratch takes the rest)
    memory_fraction: float = 0.92
    #: fixed per-step host overhead of one packed serving step (schedule,
    #: dispatch, token readback) — the intercept of the serving cost
    #: model; ``plan/calibrate.py`` refits it from step-latency samples
    serve_overhead_s: float = 5e-4

    @property
    def memory_budget(self) -> float:
        return self.hbm_bytes * self.memory_fraction


def default_hardware(platform: str = "tpu") -> HardwareSpec:
    """Per-platform defaults. The ``cpu`` spec models the 8-way virtual
    test mesh: tiny compute, memcpy-grade "links" — rankings still
    exercise every term, which is all the CPU tests need."""
    if platform == "cpu":
        return HardwareSpec(name="cpu", flops=5e10, mfu=0.5,
                            hbm_bytes=4 * 2**30,
                            ici=LinkSpec(bandwidth=8e9, latency=2e-6),
                            dcn=LinkSpec(bandwidth=1e9, latency=50e-6),
                            serve_overhead_s=2e-3)
    return HardwareSpec()


# ---------------------------------------------------------------------------
# Model description
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelSpec:
    """The shapes the cost model needs, decoupled from any framework
    config class. ``from_model_config`` lifts a ``LlamaConfig``-style
    dataclass (anything with hidden_size/num_layers/... attributes)."""

    name: str
    vocab: int
    hidden: int
    intermediate: int
    layers: int
    heads: int
    kv_heads: int
    seq: int
    #: sequences per optimizer step across the whole job
    global_batch: int
    head_dim: Optional[int] = None
    num_experts: int = 0
    top_k: int = 0
    param_bytes: int = 4        # fp32 masters
    act_bytes: int = 2          # bf16 activations/compute

    def __post_init__(self) -> None:
        for f in ("vocab", "hidden", "intermediate", "layers", "heads",
                  "kv_heads", "seq", "global_batch"):
            v = getattr(self, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"ModelSpec.{f} must be a positive int, "
                                 f"got {v!r}")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden // self.heads

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.seq

    @classmethod
    def from_model_config(cls, mcfg: Any, *, seq: Optional[int] = None,
                          global_batch: int = 8,
                          name: Optional[str] = None) -> "ModelSpec":
        g = lambda attr, d=None: getattr(mcfg, attr, d)  # noqa: E731
        return cls(
            name=name or type(mcfg).__name__,
            vocab=g("vocab_size"), hidden=g("hidden_size"),
            intermediate=g("intermediate_size"), layers=g("num_layers"),
            heads=g("num_heads"), kv_heads=g("num_kv_heads", g("num_heads")),
            head_dim=g("head_dim"),
            seq=seq or g("max_seq_len", 2048), global_batch=global_batch,
            num_experts=g("num_experts", 0) or 0,
            top_k=g("num_experts_per_tok", 0) or 0)


def param_count(m: ModelSpec) -> int:
    """Dense transformer parameters (embeddings + per-layer matmuls +
    norms; MoE experts multiply the MLP block)."""
    d = m.head_dim_
    attn = m.hidden * (m.heads * d + 2 * m.kv_heads * d) + m.heads * d * m.hidden
    mlp = 3 * m.hidden * m.intermediate
    if m.num_experts > 1:
        mlp *= m.num_experts
    per_layer = attn + mlp + 2 * m.hidden
    return m.vocab * m.hidden * 2 + m.layers * per_layer + m.hidden


def step_flops(m: ModelSpec, remat: bool) -> float:
    """Training FLOPs for one optimizer step: ``6·N·T`` for the dense
    matmuls (fwd 2, bwd 4) plus the quadratic attention term; full remat
    re-runs the matmuls' forward once more (×4/3) and not the attention: a
    rematerialised layer keeps the flash kernel's output and log-sum-exp
    (``utils/remat.py``). MoE only pays for the ``top_k`` routed
    experts."""
    n_matmul = param_count(m) - m.vocab * m.hidden  # embed lookup is free
    if m.num_experts > 1 and m.top_k:
        active = 3 * m.hidden * m.intermediate * min(m.top_k, m.num_experts)
        total = 3 * m.hidden * m.intermediate * m.num_experts
        n_matmul -= m.layers * (total - active)
    flops = 6.0 * n_matmul * m.tokens_per_step
    if remat:
        flops *= 4.0 / 3.0
    # causal attention: 2 matmuls of [S, D]x[D, S] per head, halved by the
    # causal mask, fwd+bwd -> 6 * T * S * hidden
    flops += 6.0 * m.tokens_per_step * m.seq * m.heads * m.head_dim_ * 0.5
    return flops


# ---------------------------------------------------------------------------
# Candidate plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Plan:
    """One point in the search space: a mesh factorization plus the
    reduction strategy. ``dp`` is the TOTAL data-parallel degree;
    ``dcn_dp`` of it crosses DCN (1 = single slice)."""

    devices: int
    tp: int = 1
    pp: int = 1
    dp: int = 1
    cp: int = 1
    ep: int = 1
    dcn_dp: int = 1
    # reduction / overlap strategy
    zero1: bool = True
    grad_comm_dtype: str = "fp32"       # fp32 | int8 | fp8
    grad_comm_hierarchical: bool = False
    # activation-collective wire dtype (ParallelConfig.
    # tp_activation_comm_dtype): scales the TP-collective term by the
    # codec's wire_bytes_per_element
    tp_act_comm_dtype: str = "fp32"     # fp32 | int8 | fp8
    tp_overlap: bool = False
    # MoE EP-dispatch wire dtype (ParallelConfig.moe_ep_wire_dtype): scales
    # the EP token-dispatch term by the codec's wire_bytes_per_element
    ep_wire_dtype: str = "fp32"         # fp32 | int8 | fp8
    # decomposed (ppermute-ring) EP dispatch hiding hops behind per-chunk
    # expert compute (ParallelConfig.moe_overlap_dispatch)
    ep_overlap: bool = False
    sequence_parallel: bool = False
    remat: bool = True
    num_microbatches: int = 1
    # serving weight-quantization tier (ParallelConfig.weight_quant /
    # EngineConfig.weight_quant): shrinks the resident param bytes by the
    # format's storage ratio and taxes compute with the dequant overhead
    weight_quant: Optional[str] = None

    def describe(self) -> str:
        tags = [f"tp={self.tp}", f"pp={self.pp}", f"dp={self.dp}"]
        if self.cp > 1:
            tags.append(f"cp={self.cp}")
        if self.ep > 1:
            tags.append(f"ep={self.ep}")
        if self.dcn_dp > 1:
            tags.append(f"dcn={self.dcn_dp}")
        tags.append("zero1" if self.zero1 else "ddp")
        tags.append(self.grad_comm_dtype
                    + ("/hier" if self.grad_comm_hierarchical else "/flat"))
        if self.tp_act_comm_dtype != "fp32":
            tags.append(f"act:{self.tp_act_comm_dtype}")
        if self.tp_overlap:
            tags.append("overlap")
        if self.ep_wire_dtype != "fp32":
            tags.append(f"ep:{self.ep_wire_dtype}")
        if self.ep_overlap:
            tags.append("ep-overlap")
        if self.sequence_parallel:
            tags.append("sp")
        if self.weight_quant is not None:
            tags.append(f"w:{self.weight_quant}")
        return " ".join(tags)


@dataclass(frozen=True)
class ServingSpec:
    """Paged-KV pool sizing for serving plans (memory-only term)."""

    num_blocks: int = 512
    block_size: int = 16
    quantized: bool = False
    kv_bytes: int = 2


# ---------------------------------------------------------------------------
# Collective primitives (α-β ring model)
# ---------------------------------------------------------------------------

def ring_all_reduce_s(nbytes: float, n: int, link: LinkSpec) -> float:
    if n <= 1 or nbytes <= 0:
        return 0.0
    return 2.0 * nbytes * (n - 1) / n / link.bandwidth \
        + 2.0 * (n - 1) * link.latency


def ring_reduce_scatter_s(nbytes: float, n: int, link: LinkSpec) -> float:
    if n <= 1 or nbytes <= 0:
        return 0.0
    return nbytes * (n - 1) / n / link.bandwidth + (n - 1) * link.latency


def ring_all_gather_s(nbytes: float, n: int, link: LinkSpec) -> float:
    return ring_reduce_scatter_s(nbytes, n, link)


def all_to_all_s(nbytes: float, n: int, link: LinkSpec) -> float:
    if n <= 1 or nbytes <= 0:
        return 0.0
    return nbytes * (n - 1) / n / link.bandwidth + (n - 1) * link.latency


def wire_bytes_per_element(dtype: str, block_size: int = 256) -> float:
    """Bytes per fp32 element on the wire for the compressed collectives
    (gradient rings and quantized TP-activation collectives alike):
    1 quantized byte + one fp32 scale per block. Delegates to the static
    accounting exported by parallel/wire_codec.py so the model charges
    exactly what the collectives ship; the closed-form fallback keeps
    this module importable without jax (equality is regression-pinned in
    tests/test_plan.py)."""
    try:
        from ..parallel.wire_codec import (
            wire_bytes_per_element as _impl,
        )
    except ImportError:
        if dtype == "fp32":
            return 4.0
        if dtype in ("int8", "fp8"):
            return 1.0 + 4.0 / block_size
        raise ValueError(f"unknown comm dtype {dtype!r}")
    return _impl(dtype, block_size)


# ---------------------------------------------------------------------------
# Per-term costs
# ---------------------------------------------------------------------------

def tp_overlap_engagement(plan: Plan, m: ModelSpec) -> bool:
    """Would the ``tp_overlap_comm`` auto knob actually decompose at this
    plan's layer shapes? Shares ``ops.collective_matmul``'s tiling rule —
    the planner must never recommend overlap the layers would silently
    fall back from. Evaluated at the SP-MLP exit shape ``[B_mb, S, f/tp]``
    streamed over dim 1 (the strictest site: delivery needs ``S % tp``)
    and the ring-size floor the auto knob applies."""
    if plan.tp <= 1:
        return False
    from ..ops.collective_matmul import MIN_AUTO_AXIS_SIZE, shapes_tile

    b_mb = max(1, m.global_batch // max(1, plan.dp * plan.num_microbatches))
    entry = shapes_tile((b_mb, max(1, m.seq // plan.tp), m.hidden), 1,
                        plan.tp, needs_divisible=False)
    exit_ = shapes_tile((b_mb, m.seq, m.intermediate // plan.tp or 1), 1,
                        plan.tp, needs_divisible=True)
    return entry and exit_ and plan.tp >= MIN_AUTO_AXIS_SIZE


#: fraction of the tp activation collectives' time hidden behind the
#: per-shard partial matmuls when overlap engages: 66.5 ms a step exposed
#: under GSPMD, 26.3 with the rings, at tp=4 on a v5e 2x2, 2 x 4,096
#: tokens, Mistral-7B widths, no sequence parallelism (PERF.md, PR 47;
#: docs/tp_overlap.md)
TP_OVERLAP_HIDDEN_FRACTION = 0.6


def tp_comm_s(plan: Plan, m: ModelSpec, hw: HardwareSpec) -> float:
    """Activation collectives of the TP layers over one step. Per layer,
    Megatron-SP moves 2 all-gathers + 2 reduce-scatters of
    ``[tokens_local, hidden]`` forward and the duals backward. When the
    plan quantizes the activation wire (``tp_act_comm_dtype``), the
    payload shrinks by the codec's per-element accounting relative to
    the fp32 wire the collectives would otherwise ship."""
    if plan.tp <= 1:
        return 0.0
    tokens_local = m.tokens_per_step / plan.dp   # per TP group
    nbytes = (tokens_local * m.hidden * m.act_bytes
              * wire_bytes_per_element(plan.tp_act_comm_dtype) / 4.0)
    per_layer = 4 * (ring_all_gather_s(nbytes, plan.tp, hw.ici)
                     + ring_reduce_scatter_s(nbytes, plan.tp, hw.ici))
    total = m.layers * per_layer
    # vocab-parallel lm_head/embedding collectives: one AG+RS pair fwd+bwd
    total += 4 * (ring_all_gather_s(nbytes, plan.tp, hw.ici)
                  + ring_reduce_scatter_s(nbytes, plan.tp, hw.ici))
    if plan.tp_overlap and tp_overlap_engagement(plan, m):
        total *= 1.0 - TP_OVERLAP_HIDDEN_FRACTION
    return total


def grad_comm_s(plan: Plan, m: ModelSpec, hw: HardwareSpec) -> float:
    """Gradient reduction across the data axes. Flat: one ring over the
    full dp degree — over DCN links as soon as any of it crosses slices.
    Hierarchical (two-stage, PR 3): reduce-scatter + all-gather over the
    intra-slice part at ICI speed, and only ``1/n_fast`` of the payload
    all-reduced across slices. Compression scales the wire bytes; ZeRO-1
    replaces the all-reduce with an equal-volume RS + AG."""
    if plan.dp <= 1:
        return 0.0
    shard_elems = param_count(m) / (plan.tp * plan.pp)
    nbytes = shard_elems * wire_bytes_per_element(plan.grad_comm_dtype)
    n, dcn = plan.dp, plan.dcn_dp
    if dcn <= 1:
        return ring_all_reduce_s(nbytes, n, hw.ici)
    if not plan.grad_comm_hierarchical:
        # the ring interleaves slices: every step is paced by DCN
        return ring_all_reduce_s(nbytes, n, hw.dcn)
    n_fast = n // dcn
    fast = (ring_reduce_scatter_s(nbytes, n_fast, hw.ici)
            + ring_all_gather_s(nbytes, n_fast, hw.ici))
    slow = ring_all_reduce_s(nbytes / max(1, n_fast), dcn, hw.dcn)
    return fast + slow


def pp_comm_s(plan: Plan, m: ModelSpec, hw: HardwareSpec) -> float:
    """Stage-boundary activation sends: each microbatch crosses ``pp-1``
    boundaries forward and backward."""
    if plan.pp <= 1:
        return 0.0
    tokens_local = m.tokens_per_step / plan.dp
    nbytes = tokens_local * m.hidden * m.act_bytes
    if plan.sequence_parallel and plan.tp > 1:
        nbytes /= plan.tp
    return 2.0 * (plan.pp - 1) * (nbytes / hw.ici.bandwidth
                                  + plan.num_microbatches * hw.ici.latency)


#: fraction of the decomposed EP-ring transfer hidden behind the per-chunk
#: expert matmuls when ep_overlap engages (a guess: no chip run has
#: measured it; docs/moe.md)
EP_OVERLAP_HIDDEN_FRACTION = 0.6


def ep_overlap_engagement(plan: Plan) -> bool:
    """Would the ``moe_overlap_dispatch`` auto knob actually run the
    ppermute-ring dispatch at this plan's ep degree? Shares
    ``parallel.ep_dispatch``'s axis-size floor — the planner must never
    recommend an overlap the layer would silently fall back from."""
    if plan.ep <= 1:
        return False
    from ..parallel.ep_dispatch import MIN_AUTO_AXIS_SIZE

    return plan.ep >= MIN_AUTO_AXIS_SIZE


def ep_comm_s(plan: Plan, m: ModelSpec, hw: HardwareSpec) -> float:
    """MoE token dispatch: all-to-all of the routed tokens into the expert
    groups and back, forward and backward (4 per layer). A quantized EP
    wire (``ep_wire_dtype``) shrinks the payload by the codec's
    per-element accounting; an engaged ring overlap hides
    ``EP_OVERLAP_HIDDEN_FRACTION`` of the transfer behind the per-chunk
    expert compute."""
    if plan.ep <= 1 or m.num_experts <= 1:
        return 0.0
    tokens_local = m.tokens_per_step / plan.dp
    nbytes = (tokens_local * m.hidden * m.act_bytes * max(1, m.top_k)
              * wire_bytes_per_element(plan.ep_wire_dtype) / 4.0)
    total = m.layers * 4.0 * all_to_all_s(nbytes, plan.ep, hw.ici)
    if plan.ep_overlap and ep_overlap_engagement(plan):
        total *= 1.0 - EP_OVERLAP_HIDDEN_FRACTION
    return total


def compute_s(plan: Plan, m: ModelSpec, hw: HardwareSpec) -> float:
    return step_flops(m, plan.remat) / (plan.devices * hw.flops * hw.mfu)


def bubble_fraction(plan: Plan) -> float:
    """1F1B pipeline bubble: ``(pp-1)/mb`` extra idle time per step."""
    if plan.pp <= 1:
        return 0.0
    return (plan.pp - 1) / max(1, plan.num_microbatches)


# ---------------------------------------------------------------------------
# Memory model
# ---------------------------------------------------------------------------

def memory_bytes(plan: Plan, m: ModelSpec, hw: HardwareSpec,
                 serving: Optional[ServingSpec] = None) -> dict:
    """Per-device bytes: fp32 masters + bf16 compute copy + fp32 grads +
    Adam moments (ZeRO-1 shards the moments over the dp group), layer
    activations under remat/SP (a rematerialised layer holds its boundary
    and the flash kernel's output and log-sum-exp), and the paged-KV pool
    for serving.

    A serving plan carries *inference* state: one compute-dtype weight
    copy and the paged pool (÷ cp for the long-context tier) — no
    grads, no optimizer moments, and no training-length activations
    (the packed step's activations are token_budget-wide, noise next
    to the pool)."""
    shard = param_count(m) / (plan.tp * plan.pp)
    if serving is not None:
        params = shard * weight_storage_bytes_per_param(
            plan.weight_quant, m.act_bytes)
        kv = _kv_pool_bytes(m, serving, plan.tp, cp=plan.cp)
        return dict(params=params, grads=0.0, opt=0.0, acts=0.0, kv=kv,
                    total=params + kv)
    params = shard * (m.param_bytes + m.act_bytes)   # master + compute copy
    grads = shard * 4.0
    opt = shard * 8.0 / (plan.dp if plan.zero1 else 1)

    seqs_replica = max(1, m.global_batch // max(1, plan.dp))
    tokens_mb = seqs_replica * m.seq / max(1, plan.num_microbatches)
    layers_here = max(1, m.layers // plan.pp)
    tp_eff = plan.tp if (plan.sequence_parallel and plan.tp > 1) else 1
    if plan.remat:
        per_layer = tokens_mb * m.hidden * m.act_bytes * 2 / tp_eff
        # what the layer keeps beside its boundary: the flash kernel's
        # output [tokens, heads/tp, head_dim] and its float32 log-sum-exp
        per_layer += tokens_mb * m.heads * (m.head_dim_ * m.act_bytes
                                            + 4.0) / plan.tp
    else:
        per_layer = tokens_mb * (18 * m.hidden + 4 * m.intermediate) \
            * m.act_bytes / tp_eff
    inflight = min(plan.num_microbatches, plan.pp) if plan.pp > 1 else 1
    acts = layers_here * per_layer * inflight

    kv = 0.0
    if serving is not None:
        kv = _kv_pool_bytes(m, serving, plan.tp, cp=plan.cp)
    total = params + grads + opt + acts + kv
    return dict(params=params, grads=grads, opt=opt, acts=acts, kv=kv,
                total=total)


def _kv_pool_bytes(m: ModelSpec, s: ServingSpec, tp: int,
                   cp: int = 1) -> float:
    """Paged-pool bytes per device; delegates to the pool's own accounting
    (``inference.paging.pool_accounting``) so planner numbers track the
    arrays the engine actually allocates — including the long-context
    tier's pool-blocks-over-cp sharding. Falls back to the closed form
    when jax isn't importable (pure-math contexts)."""
    try:
        from ..inference.paging import pool_accounting

        return pool_accounting(
            num_layers=m.layers, num_blocks=s.num_blocks,
            block_size=s.block_size, num_kv_heads=m.kv_heads,
            head_dim=m.head_dim_, kv_bytes=s.kv_bytes,
            quantized=s.quantized, tp_size=tp, cp_size=cp)
    except ImportError:  # pragma: no cover - jax-free fallback
        per_elem = (1 + 4.0 / m.head_dim_) if s.quantized else s.kv_bytes
        return (2.0 * m.layers * s.num_blocks * s.block_size
                * m.kv_heads * m.head_dim_ * per_elem) / (tp * max(1, cp))


# ---------------------------------------------------------------------------
# Assembled breakdown
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CostBreakdown:
    """Per-term step time (seconds) and per-device memory (bytes)."""

    compute_s: float
    bubble_s: float
    tp_comm_s: float
    pp_comm_s: float
    ep_comm_s: float
    grad_comm_s: float
    memory: dict

    @property
    def total_s(self) -> float:
        return (self.compute_s + self.bubble_s + self.tp_comm_s
                + self.pp_comm_s + self.ep_comm_s + self.grad_comm_s)

    def to_dict(self) -> dict:
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self) if f.name != "memory"}
        d["total_s"] = self.total_s
        d["memory"] = dict(self.memory)
        return d


def step_cost(plan: Plan, m: ModelSpec, hw: HardwareSpec,
              serving: Optional[ServingSpec] = None) -> CostBreakdown:
    """One training step of ``plan`` on ``hw``: per-term times + memory.

    Comm terms are summed, not overlapped (except the modeled TP-overlap
    discount) — a deliberately pessimistic serialization that preserves
    ranking monotonicity: more bytes over a slower tier never gets
    cheaper (asserted in tests/test_plan.py).
    """
    comp = compute_s(plan, m, hw)
    tp = tp_comm_s(plan, m, hw)
    return CostBreakdown(
        compute_s=comp,
        bubble_s=(comp + tp) * bubble_fraction(plan),
        tp_comm_s=tp,
        pp_comm_s=pp_comm_s(plan, m, hw),
        ep_comm_s=ep_comm_s(plan, m, hw),
        grad_comm_s=grad_comm_s(plan, m, hw),
        memory=memory_bytes(plan, m, hw, serving))


# ---------------------------------------------------------------------------
# Replica cold start (serving elasticity)
# ---------------------------------------------------------------------------

#: XLA compile-time model for one serving step program: a flat front-end
#: cost plus a per-layer slope. Absolute numbers are calibrated loosely to
#: observed neuron/XLA compiles; like the step terms, only the *ratios*
#: drive decisions (cached vs uncached, deeper vs shallower stages).
COMPILE_BASE_S = 18.0
COMPILE_PER_LAYER_S = 3.0
#: AOT path: flat deserialize/link overhead for a cached executable.
AOT_LOAD_BASE_S = 0.4
#: serialized-executable size per stage-layer (constants folded out —
#: the bundle ships program text, not weights).
AOT_BYTES_PER_LAYER = 4 * 2**20


def cold_start_s(plan: Plan, m: ModelSpec, hw: HardwareSpec,
                 aot_cached: bool = True) -> float:
    """Seconds to bring one serving replica from process start to its
    first schedulable step (``docs/serving.md`` "Elastic fleet").

    Two regimes:

    * **uncached** — XLA compiles the stage program from scratch: a flat
      front-end cost plus a per-layer slope over this stage's
      ``num_layers / pp`` layers (TP shards the tensors, not the program
      node count, so it does not shrink compile time).
    * **aot_cached** — the replica *loads* a serialized executable from
      the fleet's AOT cache: a flat deserialize cost plus the bundle's
      bytes over the DCN tier (cache reads cross hosts).

    Either way the weight shard must arrive over DCN. The autoscaler uses
    the ratio to decide how far ahead of a load spike it must act; a
    cache hit turns minutes into (milli)seconds, which is why the router
    refuses to build engines outside the cache (nxdlint ``elasticity``).
    """
    stage_layers = max(1, math.ceil(m.layers / plan.pp))
    weight_shard = param_count(m) * m.act_bytes / (plan.tp * plan.pp)
    fetch_s = weight_shard / hw.dcn.bandwidth
    if aot_cached:
        bundle = AOT_BYTES_PER_LAYER * stage_layers
        return AOT_LOAD_BASE_S + bundle / hw.dcn.bandwidth + fetch_s
    return COMPILE_BASE_S + COMPILE_PER_LAYER_S * stage_layers + fetch_s


# ---------------------------------------------------------------------------
# Serving cost model (request-level)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrafficSpec:
    """Offered serving load: Poisson arrivals at ``request_rate`` req/s,
    each with ``prompt_tokens`` of context (of which
    ``shared_prefix_tokens`` are trie-shareable across requests) and
    ``new_tokens`` generated tokens. Means, not maxima — the queueing
    terms below supply the tail."""

    request_rate: float
    prompt_tokens: float = 64.0
    new_tokens: float = 16.0
    shared_prefix_tokens: float = 0.0

    def __post_init__(self) -> None:
        if self.request_rate < 0:
            raise ValueError("request_rate must be >= 0")
        if self.shared_prefix_tokens > self.prompt_tokens:
            raise ValueError("shared_prefix_tokens exceeds prompt_tokens")

    @property
    def unique_prompt_tokens(self) -> float:
        """Prompt tokens that must actually be prefilled per request when
        prefix sharing absorbs the shared head."""
        return max(0.0, self.prompt_tokens - self.shared_prefix_tokens)


@dataclass(frozen=True)
class SpeculationSpec:
    """Accept-rate-parameterized speculation term (jax-free mirror of
    ``inference.speculative.SpeculationConfig``): a speculating slot
    burns ``branches * (length + 1)`` verify rows per round to land
    ``accept_rate * length + 1`` tokens, and the draft model's chained
    forwards stretch the step wall by ``draft_cost_ratio``. Calibrate
    ``accept_rate`` from measured walls — the engine reports
    ``spec_accept_mean`` (mean accepted tokens per round) in
    ``EngineStats.report()`` / ``ReplicaRouter.engine_aggregate()``;
    divide by ``length`` to get the rate."""

    length: int = 4                 # draft chain depth k
    branches: int = 1               # tree branches B
    accept_rate: float = 0.6        # accepted fraction of the k drafts
    draft_cost_ratio: float = 0.15  # draft wall relative to target step

    def __post_init__(self) -> None:
        if self.length < 1:
            raise ValueError("length must be >= 1")
        if self.branches < 1:
            raise ValueError("branches must be >= 1")
        if not 0.0 <= self.accept_rate <= 1.0:
            raise ValueError("accept_rate must be in [0, 1]")
        if self.draft_cost_ratio < 0:
            raise ValueError("draft_cost_ratio must be >= 0")

    @classmethod
    def from_accept_mean(cls, length: int, accept_mean: float,
                         branches: int = 1,
                         draft_cost_ratio: float = 0.15
                         ) -> "SpeculationSpec":
        """Build from the engine's measured ``spec_accept_mean``."""
        return cls(length=length, branches=branches,
                   accept_rate=min(1.0, max(0.0, accept_mean / length)),
                   draft_cost_ratio=draft_cost_ratio)

    @property
    def accept_mean(self) -> float:
        return self.accept_rate * self.length

    @property
    def tokens_per_round(self) -> float:
        """Landed tokens per verify round: accepted drafts + the bonus
        token the target emits even on full rejection."""
        return self.accept_mean + 1.0

    @property
    def rows_per_round(self) -> int:
        """Packed verify rows one speculating slot occupies."""
        return self.branches * (self.length + 1)

    @property
    def row_efficiency(self) -> float:
        """Landed tokens per verify row — the factor by which
        speculation discounts (or taxes, when < plain decode's 1.0)
        the engine's row capacity."""
        return self.tokens_per_round / self.rows_per_round


#: dequant tax on a quantized KV pool: the packed step spends extra
#: element-wise work unpacking int8 KV before attention.
QUANTIZED_COMPUTE_OVERHEAD = 1.1
#: stored bytes per weight element under each weight_quant tier:
#: int8/fp8 carry one byte plus a per-out-channel fp32 scale (amortized
#: to ~0 over the contraction dim); MX packs 2 fp4 codes per byte (0.5)
#: or 1 fp8 code (1.0) plus one fp32 scale per 32-element block (4/32)
WEIGHT_QUANT_STORAGE_BYTES = {"int8": 1.0, "fp8": 1.0,
                              "mxfp4": 0.625, "mxfp8": 1.125}
#: dequant tax on weight-quantized projections: every matmul first
#: expands the packed kernel to the compute dtype (element-wise work
#: proportional to the weight bytes read, mostly hidden under the DMA
#: it shrinks — the residual tax is what the drills measure)
WEIGHT_QUANT_COMPUTE_OVERHEAD = 1.15


def weight_storage_bytes_per_param(weight_quant: Optional[str],
                                   act_bytes: float) -> float:
    """Resident bytes per weight element: the serving copy is stored in
    the compute dtype (``act_bytes``) unless a ``weight_quant`` tier
    packs it."""
    if weight_quant is None:
        return act_bytes
    try:
        return WEIGHT_QUANT_STORAGE_BYTES[weight_quant]
    except KeyError:
        raise ValueError(
            f"unknown weight_quant {weight_quant!r}; expected one of "
            f"{sorted(WEIGHT_QUANT_STORAGE_BYTES)}")
#: p99/mean inflation applied when checking a modeled mean against a p99
#: SLO target. TTFT inherits the arrival process's queueing variance
#: (M/G/1-ish); TPOT is step-paced and much tighter.
TTFT_P99_OVER_MEAN = 3.0
TPOT_P99_OVER_MEAN = 1.5
#: per-request length cap headroom: TrafficSpec states *mean* prompt/new
#: tokens, but the emitted ``max_blocks_per_seq`` is a hard admission cap
#: — size it for the tail so the engine never rejects a legitimately
#: long request as never_fits.
REQUEST_TOKENS_MAX_OVER_MEAN = 2.0


def serving_token_s(m: ModelSpec, hw: HardwareSpec, *, context: float = 0.0,
                    tp: int = 1, quantized: bool = False,
                    weight_quant: Optional[str] = None) -> float:
    """Marginal wall time of one extra row in a packed serving step:
    forward matmul FLOPs for one token plus its attention reads over
    ``context`` cached KV entries, at the hardware's dense efficiency.
    The step's fixed overhead lives in ``hw.serve_overhead_s``."""
    n_matmul = param_count(m) - m.vocab * m.hidden
    flops = 2.0 * n_matmul
    flops += 4.0 * context * m.heads * m.head_dim_ * m.layers
    if quantized:
        flops *= QUANTIZED_COMPUTE_OVERHEAD
    if weight_quant is not None:
        flops *= WEIGHT_QUANT_COMPUTE_OVERHEAD
    return flops / (max(1, tp) * hw.flops * hw.mfu)


def dcn_handoff_bytes(m: ModelSpec, traffic: TrafficSpec, *,
                      wire_block: int = 256) -> float:
    """Wire bytes of one request's prefix KV streamed prefill→decode by
    ``inference.transport.KVStreamTransport``: 2 (K and V) x layers x
    kv_heads x head_dim elements per cached token, shipped int8 with
    per-block fp32 scales (the ``wire_codec`` blockwise layout — the
    ~4x-below-fp32 "wire ratio")."""
    elems = (2.0 * m.layers * m.kv_heads * m.head_dim_
             * traffic.prompt_tokens)
    return elems * wire_bytes_per_element("int8", wire_block)


def dcn_handoff_s(m: ModelSpec, hw: HardwareSpec,
                  traffic: TrafficSpec, *,
                  wire_block: int = 256) -> float:
    """Mean wall time of one cross-host KV handoff over the DCN link:
    compressed payload over bandwidth plus one ``hw.dcn.latency`` hop
    per chunk (a K and a V chunk per layer, plus the ticket header)."""
    n_chunks = 2 * m.layers + 1
    return (dcn_handoff_bytes(m, traffic, wire_block=wire_block)
            / hw.dcn.bandwidth + n_chunks * hw.dcn.latency)


@dataclass(frozen=True)
class ServingCost:
    """Modeled steady-state serving behavior for one engine config under
    one traffic mix. All figures are per-replica means; compare p99 SLO
    targets against ``*_P99_OVER_MEAN`` times these."""

    ttft_s: float            # arrival -> first token (queue + prefill)
    tpot_s: float            # per generated token after the first
    tokens_per_s: float      # generated-token goodput actually served
    step_s: float            # modeled packed-step wall time
    utilization: float       # max of token-capacity and slot pressure
    concurrency: float       # mean live decode slots (Little's law)
    saturated: bool          # offered load exceeds capacity
    handoff_s: float = 0.0   # cross-host KV transfer (0 = colocated)
    handoff_exposed_s: float = 0.0  # transfer not hidden under prefill

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


def serving_cost(m: ModelSpec, hw: HardwareSpec, traffic: TrafficSpec, *,
                 token_budget: int, max_slots: int,
                 prefill_budget: Optional[int] = None,
                 quantized: bool = False, tp: int = 1,
                 cross_host: bool = False,
                 speculation: Optional[SpeculationSpec] = None,
                 cp: int = 1, cp_wire_dtype: str = "int8",
                 weight_quant: Optional[str] = None
                 ) -> ServingCost:
    """Steady-state TTFT / TPOT / goodput of one continuous-batching
    engine (``inference.engine.ServingEngine``) under Poisson load.

    The packed step is padded to a fixed ``token_budget`` width (that is
    what keeps it one executable), so every step costs
    ``step_s = serve_overhead_s + token_budget * token_s`` *regardless
    of occupancy* — oversizing the budget buys capacity at the price of
    every step's latency. Decode concurrency follows from Little's law
    (rate x residence), TPOT stretches when live slots outnumber the
    decode rows a step can carry, and TTFT stacks an M/G/1-style
    queueing wait ``rho/(1-rho) * step_s`` on top of the prefill
    slicing delay. Saturation (``rho >= 1``) caps goodput at capacity
    instead of diverging, so search ranking stays total.

    With ``cross_host`` the prefill and decode tiers live on different
    hosts and the KV prefix rides :func:`dcn_handoff_s` over the DCN
    link; the stream is layer-ordered and overlaps the prefill steps
    that produce it, so only the *exposed* remainder (transfer beyond
    the prefill wall time) lands in TTFT.

    With ``speculation`` each decode slot lands
    ``spec.tokens_per_round`` tokens per step (mean accepted drafts +
    the bonus token) but occupies ``spec.rows_per_round`` verify rows,
    and the chained draft forwards stretch the step wall by
    ``draft_cost_ratio`` — the same row-pricing the router's admission
    surcharge applies, so the planner and the admission controller
    agree on what a speculated token costs.

    With ``cp > 1`` the engine is the long-context tier: ``cp`` ranks
    ring-prefill the prompt together (each takes a sequence slice, so
    the prefill wall divides by ``cp``), and each ring hop ships the
    slice's KV quantized at ``cp_wire_dtype``
    (``ops.ring_attention`` wire hops) — the ``cp - 1`` hops' wire
    time lands in TTFT. Decode cost is unchanged: per-rank paged
    attention over resident blocks with a flash-decoding combine is
    one collective the overhead intercept already absorbs."""
    t = traffic
    token_s = serving_token_s(
        m, hw, context=t.prompt_tokens + t.new_tokens / 2.0,
        tp=tp, quantized=quantized, weight_quant=weight_quant)
    prompt_eff = t.unique_prompt_tokens
    tokens_per_req = prompt_eff + t.new_tokens
    # speculation: tokens landed per slot-step and verify rows burned
    # per landed decode token (plain decode: 1 and 1)
    spec_tok = speculation.tokens_per_round if speculation else 1.0
    row_tax = (1.0 / speculation.row_efficiency) if speculation else 1.0
    demand_tps = t.request_rate * (prompt_eff + t.new_tokens * row_tax)

    # padded width: a step pays for the whole budget, occupied or not
    step_s = hw.serve_overhead_s + token_s * token_budget
    if speculation is not None:
        step_s *= 1.0 + speculation.draft_cost_ratio
    capacity_tps = token_budget / step_s

    decode_rows = float(min(max_slots, token_budget))
    if speculation is not None:
        # a speculating slot needs rows_per_round rows of verify width
        decode_rows = float(min(
            max_slots,
            max(1, token_budget // speculation.rows_per_round)))
    # Little's law on the decode phase: a slot holds
    # new_tokens / spec_tok steps. slot_demand <= decode_rows -> every
    # live request advances each step (tpot = step_s / spec_tok);
    # beyond that slots queue and TPOT stretches.
    slot_demand = t.request_rate * (t.new_tokens / spec_tok) * step_s
    conc = min(slot_demand, decode_rows)
    tpot = step_s / spec_tok * max(1.0, slot_demand / decode_rows)
    rho = max(demand_tps / capacity_tps, slot_demand / decode_rows)
    saturated = rho >= 1.0

    if prefill_budget is not None:
        prefill_rows = float(max(1, prefill_budget))
    else:
        prefill_rows = max(1.0, token_budget - conc)
    # context parallelism slices the prompt over cp ranks: each rank
    # prefills prompt/cp tokens, so the wall divides by cp
    cp = max(1, cp)
    prefill_steps = (math.ceil(prompt_eff / (prefill_rows * cp))
                     if prompt_eff > 0 else 0)
    rho_q = min(rho, 0.99)
    wait = rho_q / (1.0 - rho_q) * step_s
    ttft = wait + (prefill_steps + 1) * step_s
    if cp > 1 and prompt_eff > 0:
        # ring-attention KV hops: over a full ring pass each rank ships
        # its (prompt/cp)-token KV slice to cp-1 neighbors, quantized at
        # cp_wire_dtype, once per layer (latency per hop per layer)
        elems = 2.0 * m.layers * m.kv_heads * m.head_dim_ * prompt_eff
        hop_bytes = (elems * wire_bytes_per_element(cp_wire_dtype)
                     * (cp - 1) / cp)
        ttft += (hop_bytes / hw.ici.bandwidth
                 + (cp - 1) * m.layers * hw.ici.latency)

    handoff = exposed = 0.0
    if cross_host:
        handoff = dcn_handoff_s(m, hw, traffic)
        exposed = max(0.0, handoff - prefill_steps * step_s)
        ttft += exposed

    if saturated:
        # capacity in *landed* tokens: row capacity discounted by the
        # decode row tax, and the slot ceiling credits spec_tok landed
        # tokens per slot-step
        row_demand = prompt_eff + t.new_tokens * row_tax
        goodput = min(capacity_tps * (t.new_tokens * row_tax
                                      / max(1e-9, row_demand)) / row_tax,
                      decode_rows * spec_tok / step_s)
    else:
        goodput = t.request_rate * t.new_tokens
    return ServingCost(ttft_s=ttft, tpot_s=tpot, tokens_per_s=goodput,
                       step_s=step_s, utilization=rho, concurrency=conc,
                       saturated=saturated, handoff_s=handoff,
                       handoff_exposed_s=exposed)


def serving_pool_blocks(m: ModelSpec, traffic: TrafficSpec, *,
                        block_size: int, max_slots: int,
                        slack: float = 1.25) -> int:
    """Paged-pool blocks the stated mix needs: every concurrent slot at
    full sequence length plus the shared prefix held once, with
    fragmentation slack. Conservative — prefix sharing only shrinks the
    footprint further."""
    per_seq = math.ceil((traffic.prompt_tokens + traffic.new_tokens)
                        / block_size)
    shared = math.ceil(traffic.shared_prefix_tokens / block_size)
    return int(math.ceil((max_slots * per_seq + shared) * slack))


@dataclass(frozen=True)
class ServingPlan:
    """One serving candidate: plain-dict ``EngineConfig`` /
    ``RouterConfig`` kwargs (this module stays jax-free; callers build
    the real config objects) plus its modeled cost and SLO verdict."""

    engine: dict
    router: dict
    cost: ServingCost
    meets_slo: bool
    slo: dict

    def describe(self) -> str:
        e = self.engine
        tags = [f"budget={e['token_budget']}", f"slots={e['max_slots']}",
                f"blocks={e['num_blocks']}x{e['block_size']}"]
        if e.get("cp", 1) > 1:
            tags.append(f"cp={e['cp']}/{e.get('cp_wire_dtype', 'int8')}")
        if e.get("disaggregated"):
            tags.append(f"disagg/pf={e['prefill_budget']}")
        if self.router.get("fabric"):
            tags.append("dcn")
        if e.get("prefix_sharing"):
            tags.append("prefix")
        if e.get("quantized"):
            tags.append("q8kv")
        if e.get("weight_quant"):
            tags.append(f"w:{e['weight_quant']}")
        if e.get("speculation"):
            sp = e["speculation"]
            tags.append(f"spec=k{sp['speculation_length']}"
                        f"b{sp['num_branches']}")
        return " ".join(tags)

    def to_dict(self) -> dict:
        return dict(engine=dict(self.engine), router=dict(self.router),
                    cost=self.cost.to_dict(), meets_slo=self.meets_slo,
                    slo=dict(self.slo))


def serving_search(m: ModelSpec, hw: HardwareSpec, traffic: TrafficSpec, *,
                   slo_ttft_p99_s: float = math.inf,
                   slo_tpot_p99_s: float = math.inf,
                   tp: int = 1, quantized: bool = False,
                   block_size: int = 8,
                   budgets: tuple = (4, 8, 16, 32, 64, 128, 256),
                   slots: tuple = (1, 2, 4, 8, 12, 16, 24, 32),
                   disaggregated: bool = False,
                   cross_host: bool = False,
                   speculation: Optional[SpeculationSpec] = None,
                   cps: tuple = (1,),
                   weight_quants: tuple = (None,),
                   quality: Optional[dict] = None,
                   quality_bar: Optional[float] = None,
                   top_k: int = 5) -> list:
    """Enumerate (token_budget, max_slots[, prefill_budget]) engine
    configs for the stated traffic and SLO, score each with
    :func:`serving_cost`, and return the top candidates.

    ``cps`` adds a context-parallel axis: each ``cp > 1`` candidate
    models the long-context tier — the paged pool is sharded over the
    cp group (per-rank ``num_blocks`` divides by cp, which is what the
    per-device memory check sees), prefill wall time divides by cp, and
    the ring's quantized KV hops land in TTFT. A long-context traffic
    mix whose pool cannot fit one device therefore surfaces a ``cp>1``
    plan, while short mixes keep ranking ``cp=1`` first (the ring wire
    buys them nothing). CP candidates skip the engine features the
    runtime rejects alongside cp (prefix sharing, speculation,
    quantized KV, disaggregated prefill).

    ``cross_host`` enumerates *both* colocated and two-tier fabric
    candidates; fabric candidates pay the :func:`dcn_handoff_s` term
    (exposed remainder only — the stream overlaps prefill) and carry a
    ``router["fabric"]`` hint, so the ranking itself answers
    disagg-vs-colocated for the stated traffic mix.

    ``weight_quants`` adds the low-precision tier axis: each non-None
    entry ("int8" | "fp8" | "mxfp4" | "mxfp8") models serving with the
    weights packed at that format — resident param bytes shrink by the
    format's storage ratio (which is what frees HBM for pool blocks at
    an equal budget) and the marginal token cost carries the dequant
    tax. Quantized tiers are **quality-gated**: with ``quality_bar``
    set, a tier is only proposed when ``quality`` (a mapping from
    format to its *recorded* greedy match-rate vs fp32 — either the
    rate itself or a dict with a ``"greedy_match"`` key) attests a
    match-rate >= the bar.
    A tier with no recorded quality is refused outright (fail-closed):
    the planner does not guess what quantization does to a model.

    Ranking: SLO-feasible before infeasible, unsaturated before
    saturated, then highest goodput; among configs within 2% of the best
    goodput, the lowest modeled TTFT wins (burst absorption), then the
    smallest ``token_budget`` / ``max_slots`` — headroom you don't need
    is compile width and pool memory you pay for. Candidates whose KV
    pool plus resident weight bytes would not fit ``hw.memory_budget``
    are dropped."""
    seq_cap = m.seq
    need = traffic.prompt_tokens + traffic.new_tokens
    tiers = []
    for wq in weight_quants:
        wq = wq or None
        if wq is not None:
            if wq not in WEIGHT_QUANT_STORAGE_BYTES:
                raise ValueError(
                    f"unknown weight_quant tier {wq!r}; expected one of "
                    f"{sorted(WEIGHT_QUANT_STORAGE_BYTES)} or None")
            if quality_bar is not None:
                rec = (quality or {}).get(wq)
                if isinstance(rec, dict):
                    rec = rec.get("greedy_match")
                if rec is None or rec < quality_bar:
                    # refused: no recorded quality, or recorded quality
                    # below the stated bar — the tier never enters the
                    # ranking, so the emitted config cannot pick it
                    continue
        if wq not in tiers:
            tiers.append(wq)
    cands = []
    for cp in sorted({max(1, int(c)) for c in cps}):
        if cp > 1 and (quantized or speculation is not None):
            continue    # the engine rejects these next to cp > 1
        cp_tiers = [w for w in tiers if w is None] if cp > 1 else tiers
        # the CP group holds the pool together: each rank carries 1/cp
        # of the blocks, so memory feasibility is judged per rank
        t_eff = traffic
        if cp > 1 and traffic.shared_prefix_tokens > 0:
            t_eff = dataclasses.replace(traffic, shared_prefix_tokens=0.0)
        for wq in cp_tiers:
            # resident weights compete with the pool for HBM: a packed
            # tier frees (act_bytes - storage) per param, which is what
            # buys it extra blocks at an equal budget
            w_bytes = (param_count(m) / max(1, tp)
                       * weight_storage_bytes_per_param(wq, m.act_bytes))
            for budget in budgets:
                for ms in slots:
                    if ms > budget * 2:
                        continue
                    nb_total = serving_pool_blocks(m, t_eff,
                                                   block_size=block_size,
                                                   max_slots=ms)
                    nblocks = math.ceil(nb_total / cp)
                    spec = ServingSpec(num_blocks=nblocks,
                                       block_size=block_size,
                                       quantized=quantized,
                                       kv_bytes=1 if quantized else 2)
                    if (w_bytes + _kv_pool_bytes(m, spec, tp)
                            > hw.memory_budget):
                        continue
                    if cp > 1:
                        pf_opts = [None]   # cp+disaggregated is rejected
                    elif cross_host:
                        # both topologies compete in one ranking
                        pf_opts = [None, max(ms, budget // 4)]
                    elif disaggregated:
                        pf_opts = [max(ms, budget // 4)]
                    else:
                        pf_opts = [None]
                    for pf in pf_opts:
                        fabric = cross_host and pf is not None
                        cost = serving_cost(m, hw, t_eff,
                                            token_budget=budget,
                                            max_slots=ms,
                                            prefill_budget=pf,
                                            quantized=quantized, tp=tp,
                                            cross_host=fabric,
                                            speculation=speculation,
                                            cp=cp, weight_quant=wq)
                        meets = (cost.ttft_s * TTFT_P99_OVER_MEAN
                                 <= slo_ttft_p99_s
                                 and cost.tpot_s * TPOT_P99_OVER_MEAN
                                 <= slo_tpot_p99_s
                                 and not cost.saturated)
                        mbps = max(1, math.ceil(
                            min(need * REQUEST_TOKENS_MAX_OVER_MEAN,
                                seq_cap) / block_size))
                        # the CP prefill width must tile over the cp ranks
                        mbps = cp * math.ceil(mbps / cp)
                        engine = dict(block_size=block_size,
                                      num_blocks=nblocks,
                                      max_slots=ms,
                                      max_blocks_per_seq=mbps,
                                      token_budget=budget)
                        if cp > 1:
                            engine["cp"] = cp
                            engine["cp_wire_dtype"] = "int8"
                        if quantized:
                            engine["quantized"] = True
                        if wq is not None:
                            engine["weight_quant"] = wq
                        if t_eff.shared_prefix_tokens > 0:
                            engine["prefix_sharing"] = True
                        if pf is not None:
                            engine["disaggregated"] = True
                            engine["prefill_budget"] = pf
                        if speculation is not None:
                            engine["speculation"] = dict(
                                speculation_length=speculation.length,
                                num_branches=speculation.branches)
                        slo = dict(ttft_p99_s=slo_ttft_p99_s,
                                   tpot_p99_s=slo_tpot_p99_s)
                        router = {}
                        if math.isfinite(slo_ttft_p99_s) \
                                or math.isfinite(slo_tpot_p99_s):
                            router["slo"] = {k: v for k, v in slo.items()
                                             if math.isfinite(v)}
                        if fabric:
                            router["fabric"] = {"prefill_replicas": 1,
                                                "decode_replicas": 1}
                        cands.append(ServingPlan(engine=engine,
                                                 router=router,
                                                 cost=cost, meets_slo=meets,
                                                 slo=slo))
    # rank on per-mesh goodput: a cp-degree replica occupies cp meshes,
    # so its goodput must beat cp plain replicas' — CP is for prompts
    # one mesh cannot hold, not a free TTFT tie-break
    def _eff(p):
        return p.cost.tokens_per_s / p.engine.get("cp", 1)

    cands.sort(key=lambda p: (not p.meets_slo, p.cost.saturated,
                              -_eff(p),
                              p.engine["token_budget"],
                              p.engine["max_slots"],
                              p.engine.get("cp", 1)))
    if cands:
        best = cands[0]
        peers = [p for p in cands
                 if p.meets_slo == best.meets_slo
                 and p.cost.saturated == best.cost.saturated
                 and _eff(p) >= 0.98 * _eff(best)]
        peers.sort(key=lambda p: (round(p.cost.ttft_s, 4),
                                  p.engine["token_budget"],
                                  p.engine["max_slots"],
                                  p.engine.get("cp", 1)))
        rest = [p for p in cands if p not in peers]
        cands = peers + rest
    return cands[:top_k]
