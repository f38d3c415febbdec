"""plan/ — placement auto-tuner: cost model, search/prune, emit, refine.

Covers the subsystem's contract surface:

* cost-model monotonicity — more traffic over a slower tier never models
  cheaper (the property the flat-vs-hierarchical ranking rests on);
* the memory model rejects OOM layouts with both numbers in the reason;
* search accounting — every enumerated candidate is either ranked or
  rejected with a machine-readable prune reason, and ranked plans are
  exactly the valid factorizations;
* emitted configs pass validation, round-trip through the YAML
  converter, and initialize the real (virtual-8-CPU) mesh;
* strategy preferences — hierarchical+compressed when dcn>1, TP overlap
  only when shapes tile (shared predicate with the runtime op);
* CLI smoke + deterministic measured refinement;
* regression pins against the runtime: wire-bytes vs CompressionConfig,
  shapes_tile vs will_decompose, pool_accounting vs the real pool.
"""

import dataclasses
import json

import jax
import pytest

from neuronx_distributed_tpu import plan as planner
from neuronx_distributed_tpu.plan import (
    ModelSpec, Plan, PRUNE_DOMINATED, PRUNE_INDIVISIBLE, PRUNE_OOM,
    ServingSpec, default_hardware, handpicked_plan, memory_bytes,
    plan_to_config, plan_to_config_kwargs, plan_to_yaml_dict, refine,
    search, step_cost, tp_overlap_engagement, wire_bytes_per_element)
from neuronx_distributed_tpu.plan.__main__ import main as plan_cli

TINY = ModelSpec(name="tiny", vocab=1024, hidden=256, intermediate=704,
                 layers=4, heads=8, kv_heads=8, seq=512, global_batch=8)
MID = ModelSpec(name="mid", vocab=32000, hidden=2048, intermediate=5504,
                layers=32, heads=32, kv_heads=32, seq=4096, global_batch=64)
HW = default_hardware("tpu")


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_dcn_traffic_never_cheaper():
    """Monotonicity: at a fixed layout, pushing more of the dp axis across
    DCN can only increase the gradient-comm term — for the flat ring
    (paced by DCN as soon as any hop crosses) AND the hierarchical
    two-stage (the slow-stage ring grows with dcn_dp)."""
    for hier in (False, True):
        base = Plan(devices=32, tp=2, dp=16, grad_comm_hierarchical=hier)
        costs = []
        for dcn in (1, 2, 4, 8, 16):
            p = dataclasses.replace(base, dcn_dp=dcn)
            costs.append(step_cost(p, MID, HW).grad_comm_s)
        assert costs == sorted(costs), (hier, costs)
        assert costs[-1] > costs[0]


def test_cold_start_term():
    """Replica spin-up (docs/serving.md "Elastic fleet"): an AOT-cached
    load is an order of magnitude cheaper than a from-scratch compile,
    compile time shrinks with pipeline sharding (fewer layers per stage
    program) but not with TP (same program node count), and both regimes
    still pay the weight-shard fetch."""
    from neuronx_distributed_tpu.plan import cold_start_s

    p = Plan(devices=8, tp=8, pp=1, dp=1)
    warm = cold_start_s(p, MID, HW, aot_cached=True)
    cold = cold_start_s(p, MID, HW, aot_cached=False)
    assert cold > 10 * warm
    deeper = Plan(devices=8, tp=2, pp=4, dp=1)
    assert cold_start_s(deeper, MID, HW, aot_cached=False) < cold
    wider = Plan(devices=16, tp=16, pp=1, dp=1)
    cold_wide = cold_start_s(wider, MID, HW, aot_cached=False)
    # TP halves the fetch, not the compile: the drop is far smaller
    # than pp sharding's
    assert cold - cold_wide < cold * 0.5
    assert cold_start_s(p, MID, HW, aot_cached=True) > 0.0


def test_slower_tier_never_cheaper():
    """Same bytes, slower link, higher cost — the α-β primitives are
    monotone in both bandwidth and latency."""
    from neuronx_distributed_tpu.plan.cost import (LinkSpec,
                                                   ring_all_reduce_s)

    fast = LinkSpec(bandwidth=9e10, latency=1e-6)
    slow = LinkSpec(bandwidth=3e9, latency=25e-6)
    for n in (2, 4, 8):
        assert ring_all_reduce_s(1 << 30, n, slow) \
            > ring_all_reduce_s(1 << 30, n, fast)


def test_compression_and_hierarchy_reduce_modeled_cost():
    flat32 = Plan(devices=32, tp=2, dp=16, dcn_dp=4)
    flat8 = dataclasses.replace(flat32, grad_comm_dtype="int8")
    hier8 = dataclasses.replace(flat8, grad_comm_hierarchical=True)
    c32 = step_cost(flat32, MID, HW).grad_comm_s
    c8 = step_cost(flat8, MID, HW).grad_comm_s
    ch8 = step_cost(hier8, MID, HW).grad_comm_s
    assert c8 < c32
    assert ch8 < c8


def test_breakdown_totals_and_dict():
    cost = step_cost(Plan(devices=8, tp=2, dp=4), TINY, HW)
    d = cost.to_dict()
    assert d["total_s"] == pytest.approx(
        d["compute_s"] + d["bubble_s"] + d["tp_comm_s"] + d["pp_comm_s"]
        + d["ep_comm_s"] + d["grad_comm_s"])
    assert d["memory"]["total"] > 0


def test_wire_bytes_matches_compression_config():
    """The planner's local wire-byte accounting must track the runtime's
    CompressionConfig exactly — if this pin breaks, fix plan/cost.py, not
    the test."""
    from neuronx_distributed_tpu.parallel.comm_compressed import (
        CompressionConfig)

    assert wire_bytes_per_element("fp32") == 4.0
    for dtype in ("int8", "fp8"):
        for bs in (64, 128, 256, 512):
            cfg = CompressionConfig(dtype=dtype, block_size=bs)
            assert wire_bytes_per_element(dtype, bs) \
                == pytest.approx(cfg.wire_bytes_per_element)


def test_pool_accounting_matches_real_pool():
    """pool_accounting must equal the bytes of the arrays the paging init
    functions actually allocate (K+V, + scales when quantized)."""
    from neuronx_distributed_tpu.inference.paging import (
        init_paged_kv_cache, init_quantized_paged_kv_cache,
        pool_accounting)

    kw = dict(num_layers=2, num_blocks=16, block_size=4, num_kv_heads=2,
              head_dim=8)
    fp = init_paged_kv_cache(**kw, max_slots=2, max_blocks_per_seq=4)
    got = pool_accounting(**kw, kv_bytes=2)
    assert got == fp.k.nbytes + fp.v.nbytes
    q = init_quantized_paged_kv_cache(**kw, max_slots=2,
                                      max_blocks_per_seq=4)
    gotq = pool_accounting(**kw, quantized=True)
    assert gotq == (q.k.nbytes + q.v.nbytes
                    + q.k_scale.nbytes + q.v_scale.nbytes)


# ---------------------------------------------------------------------------
# memory model / OOM pruning
# ---------------------------------------------------------------------------

def test_memory_model_rejects_oom_layouts():
    """A 7B-class model on one 32 GiB device cannot hold fp32 masters +
    Adam: the search must prune it with code=oom and carry both sides of
    the comparison in the detail."""
    big = ModelSpec(name="7b", vocab=32000, hidden=4096,
                    intermediate=11008, layers=32, heads=32, kv_heads=32,
                    seq=2048, global_batch=8)
    result = search(big, HW, 1)
    assert result.ranked == []
    ooms = result.rejected_with(PRUNE_OOM)
    assert ooms
    for p in ooms:
        assert "GiB/device" in p.detail and "budget" in p.detail
        assert memory_bytes(p.plan, big, HW)["total"] > HW.memory_budget


def test_zero1_shards_optimizer_memory():
    dp8 = Plan(devices=8, dp=8, zero1=True)
    ddp = dataclasses.replace(dp8, zero1=False)
    m_z = memory_bytes(dp8, MID, HW)
    m_d = memory_bytes(ddp, MID, HW)
    assert m_z["opt"] == pytest.approx(m_d["opt"] / 8)
    assert m_z["total"] < m_d["total"]


# the train cell's slice of Mistral-7B: 2 x 4,096 tokens at tp=4
MISTRAL = ModelSpec(name="mistral-7b", vocab=32768, hidden=4096,
                    intermediate=14336, layers=11, heads=32, kv_heads=8,
                    seq=4096, global_batch=2)


def test_remat_reruns_the_matmuls_and_not_the_attention():
    """A rematerialised layer keeps the flash kernel's output and
    log-sum-exp (``utils/remat.py``): the x4/3 is the matmuls'."""
    attention = 6.0 * MISTRAL.tokens_per_step * 4096 * 32 * 128 * 0.5
    plain = planner.step_flops(MISTRAL, remat=False)
    assert planner.step_flops(MISTRAL, remat=True) == pytest.approx(
        (plain - attention) * 4.0 / 3.0 + attention)


@pytest.mark.parametrize("tp,kept_mib", [(4, 16.25), (1, 65.0)])
def test_remat_holds_the_flash_output_and_log_sum_exp(tp, kept_mib):
    """Beside its boundary a layer holds B*S*N*D/tp bf16 and B*N*S/tp
    float32: 16.25 MiB a chip at the cell's tp=4 (what its compiled step
    carries, ``tests/test_chip_compile.py``), 65 at tp=1."""
    acts = memory_bytes(Plan(devices=tp, tp=tp, remat=True), MISTRAL,
                        HW)["acts"]
    boundary = 2 * 4096 * 4096 * 2 * 2
    assert acts / MISTRAL.layers - boundary == kept_mib * 2 ** 20


def test_serving_charges_kv_pool():
    # serving memory is inference state: one compute-dtype weight copy
    # plus the paged pool — no grads/opt/training activations
    p = Plan(devices=8, tp=8, dp=1)
    with_kv = memory_bytes(p, TINY, HW, ServingSpec(num_blocks=64,
                                                    block_size=16))
    without = memory_bytes(p, TINY, HW)
    assert with_kv["kv"] > 0
    assert with_kv["grads"] == with_kv["opt"] == with_kv["acts"] == 0.0
    assert with_kv["params"] < without["params"]  # no fp32 master copy
    assert with_kv["total"] == pytest.approx(with_kv["params"]
                                             + with_kv["kv"])


def test_serving_kv_pool_divides_by_cp():
    # the long-context tier shards the pool over the cp group: per-rank
    # bytes divide by cp (same total blocks, cp ranks)
    s = ServingSpec(num_blocks=64, block_size=16)
    cp1 = memory_bytes(Plan(devices=8, tp=1, dp=8), TINY, HW, s)
    cp4 = memory_bytes(Plan(devices=8, tp=1, dp=2, cp=4), TINY, HW, s)
    assert cp4["kv"] == pytest.approx(cp1["kv"] / 4)


# ---------------------------------------------------------------------------
# search accounting
# ---------------------------------------------------------------------------

def test_every_candidate_ranked_or_rejected_with_reason():
    result = search(TINY, HW, 8, top_k=3)
    assert result.n_enumerated == len(result.ranked) + len(result.rejected)
    assert result.n_enumerated > 0
    codes = {p.code for p in result.rejected}
    assert codes <= {PRUNE_INDIVISIBLE, PRUNE_OOM, PRUNE_DOMINATED}
    for p in result.rejected:
        assert p.detail
        if p.code == PRUNE_DOMINATED:
            assert p.by == result.best.plan


def test_ranked_plans_are_valid_factorizations():
    from neuronx_distributed_tpu.config import mesh_factorization

    result = search(TINY, HW, 8)
    assert result.ranked
    for r in result.ranked:
        p = r.plan
        assert p.tp * p.pp * p.dp * p.cp == 8
        # the same validation the mesh initializer runs must accept it
        sizes = mesh_factorization(
            p.devices, tensor_parallel_size=p.tp,
            pipeline_parallel_size=p.pp, context_parallel_size=p.cp,
            expert_parallel_size=p.ep, data_parallel_size=p.dp,
            dcn_data_parallel_size=p.dcn_dp)
        assert sizes["dp"] == p.dp
        assert TINY.heads % p.tp == 0
        assert TINY.layers % p.pp == 0
        assert TINY.global_batch % p.dp == 0


def test_indivisible_prunes_carry_mesh_error_messages():
    # heads=8, so tp=16 never divides on 16 devices at batch 8 -> the
    # rejected pool must name the violated constraint
    result = search(TINY, HW, 16)
    details = [p.detail for p in result.rejected_with(PRUNE_INDIVISIBLE)]
    assert any("num_heads 8 not divisible by tp 16" in d for d in details)
    assert any("not divisible by dp" in d for d in details)


def test_search_is_deterministic():
    a = search(TINY, HW, 8)
    b = search(TINY, HW, 8)
    assert [r.plan for r in a.ranked] == [r.plan for r in b.ranked]


def test_prefers_hierarchical_compressed_when_dcn():
    """With 4 slices over DCN, flat fp32 rings are paced by the slow
    tier: the winner must stage hierarchically AND compress the wire."""
    result = search(MID, HW, 64, dcn_dp=4)
    best = result.best.plan
    assert best.dcn_dp == 4
    assert best.grad_comm_hierarchical
    assert best.grad_comm_dtype == "int8"
    # and it strictly beats its own flat-fp32 twin
    twin = dataclasses.replace(best, grad_comm_dtype="fp32",
                               grad_comm_hierarchical=False)
    assert step_cost(best, MID, HW).total_s \
        < step_cost(twin, MID, HW).total_s


def test_activation_compression_strategies_ranked():
    """The strategy grid proposes int8 activation wires wherever tp > 1,
    the cost model charges them at the codec's wire-bytes accounting
    (strictly cheaper TP-comm than the fp32 twin), and the prune
    accounting invariant survives the extra grid dimension."""
    result = search(MID, HW, 64, dcn_dp=4, top_k=10)
    assert result.n_enumerated == len(result.ranked) + len(result.rejected)
    acts = {r.plan.tp_act_comm_dtype for r in result.ranked
            if r.plan.tp > 1}
    assert "int8" in acts
    # tp=1 layouts never grow the pointless dimension
    for r in result.ranked:
        if r.plan.tp <= 1:
            assert r.plan.tp_act_comm_dtype == "fp32"
    best = result.best.plan
    if best.tp > 1:
        assert best.tp_act_comm_dtype == "int8"
        assert "act:int8" in best.describe()
        twin = dataclasses.replace(best, tp_act_comm_dtype="fp32")
        assert step_cost(best, MID, HW).tp_comm_s \
            < step_cost(twin, MID, HW).tp_comm_s
    # the cost scaling is exactly the codec ratio
    p8 = Plan(devices=8, tp=8, dp=1, tp_act_comm_dtype="int8")
    p32 = dataclasses.replace(p8, tp_act_comm_dtype="fp32")
    assert step_cost(p8, MID, HW).tp_comm_s > 0
    # bandwidth term scales by exactly the codec ratio; only the ring
    # latency term (~0.1% here) is payload-independent
    ratio = wire_bytes_per_element("int8") / 4.0
    assert step_cost(p8, MID, HW).tp_comm_s == pytest.approx(
        step_cost(p32, MID, HW).tp_comm_s * ratio, rel=1e-2)


def test_emit_activation_dtype_round_trips():
    from neuronx_distributed_tpu import neuronx_distributed_config
    from neuronx_distributed_tpu.scripts.yaml_converter import (
        dict_to_config_kwargs)

    plan = Plan(devices=8, tp=4, dp=2, tp_act_comm_dtype="int8")
    kwargs = plan_to_config_kwargs(plan)
    assert kwargs["tp_activation_comm_dtype"] == "int8"
    doc = plan_to_yaml_dict(plan)
    assert doc["tp_activation_comm_dtype"] == "int8"
    cfg = neuronx_distributed_config(init_mesh=False,
                                     **dict_to_config_kwargs(doc))
    assert cfg == plan_to_config(plan)
    assert cfg.parallel.tp_activation_comm_dtype == "int8"


# ---------------------------------------------------------------------------
# TP overlap engagement (shared predicate with ops.collective_matmul)
# ---------------------------------------------------------------------------

def test_overlap_only_when_shapes_tile():
    # tp=4, seq 512: S % tp == 0 -> engages
    assert tp_overlap_engagement(
        Plan(devices=8, tp=4, dp=2, sequence_parallel=True), TINY)
    # tp=2 < MIN_AUTO_AXIS_SIZE -> auto knob would not engage
    assert not tp_overlap_engagement(Plan(devices=8, tp=2, dp=4), TINY)
    # seq not divisible by tp -> the RS exit cannot tile
    odd = dataclasses.replace(TINY, seq=510)
    assert not tp_overlap_engagement(Plan(devices=8, tp=4, dp=2), odd)


def test_search_never_proposes_non_engaging_overlap():
    odd = dataclasses.replace(TINY, seq=510)
    for result in (search(TINY, HW, 8), search(odd, HW, 8)):
        for r in result.ranked:
            if r.plan.tp_overlap:
                assert tp_overlap_engagement(r.plan, TINY)
    assert all(not r.plan.tp_overlap
               for r in search(odd, HW, 8).ranked)


# ---------------------------------------------------------------------------
# EP dispatch strategy dimension (shared predicate with parallel.ep_dispatch)
# ---------------------------------------------------------------------------

def test_ep_overlap_engagement_matches_runtime_floor():
    from neuronx_distributed_tpu.parallel.ep_dispatch import (
        MIN_AUTO_AXIS_SIZE)
    from neuronx_distributed_tpu.plan.cost import ep_overlap_engagement

    assert not ep_overlap_engagement(Plan(devices=8, dp=8, ep=1))
    assert not ep_overlap_engagement(Plan(devices=8, dp=8, ep=2))
    assert ep_overlap_engagement(
        Plan(devices=8, dp=8, ep=MIN_AUTO_AXIS_SIZE))


def test_ep_dispatch_strategies_ranked():
    """MoE specs grow the EP dispatch strategy dimension: int8 wire
    wherever ep > 1, ring overlap only where the runtime auto knob would
    engage (never a silently-ignored recommendation), and ep=1 layouts
    never grow the pointless dimension."""
    from neuronx_distributed_tpu.plan.cost import ep_overlap_engagement

    moe = dataclasses.replace(TINY, name="tiny-moe", num_experts=8,
                              top_k=2)
    result = search(moe, HW, 8, top_k=20)
    assert result.n_enumerated == len(result.ranked) + len(result.rejected)
    for r in result.ranked:
        if r.plan.ep <= 1:
            assert r.plan.ep_wire_dtype == "fp32"
            assert not r.plan.ep_overlap
        if r.plan.ep_overlap:
            assert ep_overlap_engagement(r.plan)
    assert any(r.plan.ep > 1 and r.plan.ep_wire_dtype == "int8"
               for r in result.ranked)


def test_ep_wire_and_overlap_cost_model():
    from neuronx_distributed_tpu.plan.cost import (
        EP_OVERLAP_HIDDEN_FRACTION, ep_comm_s)

    moe = dataclasses.replace(MID, name="mid-moe", num_experts=8, top_k=2)
    p32 = Plan(devices=8, dp=8, ep=4)
    p8 = dataclasses.replace(p32, ep_wire_dtype="int8")
    assert ep_comm_s(p32, moe, HW) > 0
    # bandwidth term scales by exactly the codec ratio (latency term is
    # payload-independent and negligible at MID's shapes)
    ratio = wire_bytes_per_element("int8") / 4.0
    assert ep_comm_s(p8, moe, HW) == pytest.approx(
        ep_comm_s(p32, moe, HW) * ratio, rel=1e-2)
    # engaged ring hides exactly EP_OVERLAP_HIDDEN_FRACTION
    ring = dataclasses.replace(p8, ep_overlap=True)
    assert ep_comm_s(ring, moe, HW) == pytest.approx(
        ep_comm_s(p8, moe, HW) * (1.0 - EP_OVERLAP_HIDDEN_FRACTION))
    # below the runtime floor the discount never applies
    small = dataclasses.replace(p8, ep=2, ep_overlap=True)
    assert ep_comm_s(small, moe, HW) == pytest.approx(
        ep_comm_s(dataclasses.replace(small, ep_overlap=False), moe, HW))
    # dense specs charge nothing
    assert ep_comm_s(p8, MID, HW) == 0.0


def test_emit_ep_dispatch_round_trips():
    from neuronx_distributed_tpu import neuronx_distributed_config
    from neuronx_distributed_tpu.scripts.yaml_converter import (
        dict_to_config_kwargs)

    plan = Plan(devices=8, dp=8, ep=4, ep_wire_dtype="int8",
                ep_overlap=True)
    kwargs = plan_to_config_kwargs(plan)
    assert kwargs["moe_ep_wire_dtype"] == "int8"
    assert kwargs["moe_overlap_dispatch"] is True
    doc = plan_to_yaml_dict(plan)
    assert doc["moe_ep_wire_dtype"] == "int8"
    assert doc["moe_overlap_dispatch"] is True
    cfg = neuronx_distributed_config(init_mesh=False,
                                     **dict_to_config_kwargs(doc))
    assert cfg == plan_to_config(plan)
    assert cfg.parallel.moe_ep_wire_dtype == "int8"
    assert cfg.parallel.moe_overlap_dispatch is True
    assert "ep:int8" in plan.describe() and "ep-overlap" in plan.describe()


def test_shapes_tile_matches_will_decompose(monkeypatch):
    """shapes_tile is the public pure form of will_decompose's shape
    gate: with the axis size bound, the two must agree on every shape.
    (The axis env only binds inside a shard_map trace, so the size lookup
    is stubbed — the delegation itself is what's under test.)"""
    from neuronx_distributed_tpu.ops import collective_matmul as cm
    from neuronx_distributed_tpu.parallel import comm

    monkeypatch.setattr(comm, "_axis_size", lambda axis: 4)
    for shape in ((2, 512, 256), (2, 510, 256), (1, 4, 8), (8,)):
        for dim in range(-1, len(shape)):
            for nd in (False, True):
                assert cm.will_decompose("decomposed", "tp", shape, dim,
                                         needs_divisible=nd) \
                    == cm.shapes_tile(shape, dim, 4, needs_divisible=nd)
    # monolithic never decomposes regardless of tiling
    assert not cm.will_decompose("monolithic", "tp", (2, 512, 256), 1,
                                 needs_divisible=False)
    # unbound axis (GSPMD path / outside any trace): both say no
    monkeypatch.setattr(comm, "_axis_size", lambda axis: None)
    assert not cm.will_decompose("decomposed", "tp", (2, 512, 256), 1,
                                 needs_divisible=False)
    assert not cm.shapes_tile((2, 512, 256), 1, None,
                              needs_divisible=False)


# ---------------------------------------------------------------------------
# emission / config round-trips
# ---------------------------------------------------------------------------

def test_emitted_config_validates_and_initializes_mesh():
    from neuronx_distributed_tpu.parallel import mesh as ps

    result = search(TINY, HW, 8)
    cfg = plan_to_config(result.best.plan)     # validation happens here
    assert cfg.optimizer.zero_one_enabled == result.best.plan.zero1
    plan_to_config(result.best.plan, init_mesh=True)
    shape = dict(ps.get_mesh().shape)
    assert shape["tp"] == result.best.plan.tp
    assert shape["pp"] == result.best.plan.pp
    assert shape["dp"] * shape["cp"] == result.best.plan.dp


def test_emitted_yaml_round_trips_through_converter():
    from neuronx_distributed_tpu import neuronx_distributed_config
    from neuronx_distributed_tpu.scripts.yaml_converter import (
        dict_to_config_kwargs)

    plan = Plan(devices=32, tp=4, pp=2, dp=4, dcn_dp=2, zero1=True,
                grad_comm_dtype="int8", grad_comm_hierarchical=True,
                tp_overlap=True, sequence_parallel=True,
                num_microbatches=4)
    doc = plan_to_yaml_dict(plan)
    json.dumps(doc)     # YAML-able == JSON-able for our scalar types
    cfg = neuronx_distributed_config(init_mesh=False,
                                     **dict_to_config_kwargs(doc))
    assert cfg == plan_to_config(plan)


def test_to_config_kwargs_full_round_trip():
    """config -> kwargs -> config is the identity, including every
    PR-3/PR-5 knob the converter used to drop (tp_overlap_comm and the
    grad_comm_* family)."""
    from neuronx_distributed_tpu import (OptimizerConfig,
                                         neuronx_distributed_config)
    from neuronx_distributed_tpu.scripts.yaml_converter import (
        config_to_dict, dict_to_config_kwargs)

    cfg = neuronx_distributed_config(
        tensor_parallel_size=4, pipeline_parallel_size=2,
        dcn_data_parallel_size=2, tp_overlap_comm=True,
        sequence_parallel=True, seed=7,
        optimizer_config=OptimizerConfig(
            zero_one_enabled=True, grad_comm_dtype="int8",
            grad_comm_hierarchical=True, grad_comm_block_size=128,
            grad_comm_error_feedback=False),
        init_mesh=False)
    assert neuronx_distributed_config(
        init_mesh=False, **cfg.to_config_kwargs()) == cfg
    # and through the YAML document form
    doc = config_to_dict(cfg)
    assert doc["tp_overlap_comm"] is True
    assert doc["optimizer"]["grad_comm_dtype"] == "int8"
    assert doc["optimizer"]["grad_comm_hierarchical"] is True
    assert doc["optimizer"]["grad_comm_block_size"] == 128
    assert neuronx_distributed_config(
        init_mesh=False, **dict_to_config_kwargs(doc)) == cfg


def test_emit_omits_defaults():
    kwargs = plan_to_config_kwargs(Plan(devices=8, dp=8, zero1=False,
                                        remat=False))
    assert kwargs == {}


# ---------------------------------------------------------------------------
# refinement
# ---------------------------------------------------------------------------

def test_refine_deterministic_under_fixed_seed():
    result = search(TINY, HW, 8, top_k=4)

    def fake_measure(plan, spec):
        # deterministic closed form that intentionally inverts the
        # analytic order so re-ranking is observable
        return 1.0 / (1 + plan.tp) + 0.01 * plan.num_microbatches

    a = refine(result.ranked, TINY, HW, measure=fake_measure, top_k=4)
    b = refine(result.ranked, TINY, HW, measure=fake_measure, top_k=4)
    assert [(r.plan, r.measured_s) for r in a] \
        == [(r.plan, r.measured_s) for r in b]
    # re-ranked: highest-tp plan wins under the fake measurement
    assert a[0].plan.tp == max(r.plan.tp for r in result.ranked[:4])
    assert a[0].measured_s == min(r.measured_s for r in a)


def test_refine_real_proxy_runs_on_cpu():
    result = search(TINY, HW, 8, top_k=2)
    out = refine(result.ranked, TINY, HW, top_k=1, seed=0)
    assert len(out) == 1 and out[0].measured_s > 0


# ---------------------------------------------------------------------------
# CLI + bench integration
# ---------------------------------------------------------------------------

def test_cli_smoke(capsys):
    rc = plan_cli(["--model", "bench-cpu", "--devices", "8",
                   "--platform", "cpu", "--batch", "8", "--yaml",
                   "--show-pruned", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "candidates" in out and "total ms" in out
    assert "handpicked baseline" in out
    assert "emitted YAML config" in out
    assert "pruned[" in out
    # 8 == the virtual device count -> the emitted config proved itself
    # by initializing the real mesh
    assert "mesh initialized" in out


def test_cli_planner_beats_or_matches_handpicked(capsys):
    """Acceptance: on the bench llama config the emitted plan's modeled
    cost is <= the hand-picked bench layout's."""
    rc = plan_cli(["--model", "bench-cpu", "--devices", "8",
                   "--platform", "cpu", "--batch", "8"])
    assert rc == 0
    spec = ModelSpec(name="bench", vocab=1024, hidden=256,
                     intermediate=704, layers=4, heads=8, kv_heads=8,
                     seq=512, global_batch=8)
    cpu = default_hardware("cpu")
    best = search(spec, cpu, 8).best
    hand = handpicked_plan(8, platform="cpu")
    assert best.total_s <= step_cost(hand, spec, cpu).total_s


def test_cli_unknown_model_errors():
    with pytest.raises(SystemExit):
        plan_cli(["--model", "nope", "--devices", "8"])


# ---------------------------------------------------------------------------
# serving plans
# ---------------------------------------------------------------------------

def test_serving_search_single_stage_with_pool():
    result = search(TINY, HW, 8, serving=ServingSpec())
    assert result.ranked
    for r in result.ranked:
        assert r.plan.pp == 1
        assert r.cost.memory["kv"] > 0


def test_handpicked_plan_matches_bench_layout():
    p = handpicked_plan(8, platform="cpu")
    assert (p.tp, p.pp, p.dp, p.zero1) == (2, 1, 4, True)
    assert not p.remat
    t = handpicked_plan(8, platform="tpu")
    assert t.tp == 8 and t.remat
