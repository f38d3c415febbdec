"""Prefix-sharing paged KV (copy-on-write) + disaggregated prefill/decode.

Covers the PR's acceptance surface: refcounted allocator round trips,
the prefix trie (match/insert/LRU leaf eviction), COW isolation with
bit-exact greedy outputs for concurrent sharers (fp32 and int8 pools),
compile-once under prefix-hit-rate swings, disaggregated worker parity
and per-worker compile counts, shared-table invariance of the attention
kernel, router prefix-locality placement + failover, submit-time budget
crediting of shared blocks, the new stats plumbing, and the AOT worker
registration helpers.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      RequestRejected,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.inference.model_builder import (
    ModelBuilder, register_serving_workers, serving_state_spec)
from neuronx_distributed_tpu.inference.paging import (BlockAllocator,
                                                      PrefixCache)
from neuronx_distributed_tpu.inference.router import (ReplicaRouter,
                                                      RouterConfig)
from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                  tiny_config)
from neuronx_distributed_tpu.ops.paged_attention import (column_live,
                                                          paged_attention,
                                                          tile_walk)
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.resilience.chaos import FaultPlan
from walk_checks import check_paged_runs, check_tile_walk


@pytest.fixture
def tiny_model():
    ps.initialize_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, params


def _ecfg(**kw):
    base = dict(block_size=4, num_blocks=32, max_slots=4,
                max_blocks_per_seq=12, token_budget=16,
                kv_dtype=jnp.float32)
    base.update(kw)
    return EngineConfig(**base)


SYS = list(range(1, 13))                 # 12 tokens = 3 full blocks


def _solo_tokens(tiny_model, reqs, **ecfg_kw):
    """Reference greedy tokens: each request through a no-sharing engine."""
    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, _ecfg(**ecfg_kw))
    for i, (p, n) in enumerate(reqs):
        eng.submit(p, n, uid=f"solo{i}")
    res = eng.run()
    return [res[f"solo{i}"].tokens for i in range(len(reqs))]


# ---------------------------------------------------------------------------
# refcounted allocator
# ---------------------------------------------------------------------------

def test_allocator_refcount_round_trip():
    a = BlockAllocator(4)
    b1, b2 = a.alloc(2)
    assert a.refcount(b1) == 1 and a.num_shared == 0
    a.ref(b1)
    assert a.refcount(b1) == 2 and a.num_shared == 1
    # first unref keeps the block allocated and frees nothing
    assert a.free([b1]) == []
    assert a.num_allocated == 2
    # second unref actually frees it (and reports it for pos hygiene)
    assert a.free([b1]) == [b1]
    assert a.num_allocated == 1 and a.refcount(b1) == 0
    with pytest.raises(ValueError):
        a.free([b1])                      # double free
    with pytest.raises(ValueError):
        a.ref(b1)                         # ref of unallocated block
    assert a.free([b2]) == [b2]
    assert a.num_free == 4


# ---------------------------------------------------------------------------
# prefix trie
# ---------------------------------------------------------------------------

def test_prefix_cache_match_insert_partial():
    a = BlockAllocator(8)
    pc = PrefixCache(a, block_size=4)
    blocks = a.alloc(3)
    chain = None
    for i, b in enumerate(blocks):
        chain, inserted = pc.insert(chain, SYS[i * 4:(i + 1) * 4], b)
        assert inserted
    assert pc.size == 3
    # inserts took one ref each on top of the caller's
    assert all(a.refcount(b) == 2 for b in blocks)
    # full match over the cached prefix
    full, matched, partial, _ = pc.match(SYS + [99, 98], max_tokens=13)
    assert full == blocks and matched == 12 and partial is None
    # partial tail: a prompt diverging mid-block matches the common head
    full, matched, partial, _ = pc.match(SYS[:8] + [9, 10, 77, 78],
                                         max_tokens=11)
    assert full == blocks[:2] and matched == 8
    assert partial == (blocks[2], 2)      # tokens 9,10 of the cached block
    # idempotent re-insert: chain advances, nothing new is created
    chain2, inserted = pc.insert(None, SYS[:4], 99)
    assert not inserted and pc.size == 3
    assert pc._nodes[chain2].block == blocks[0]
    # insert under an evicted parent is refused
    pc.evict(want_free=3)                 # caller refs keep blocks alive...
    a.free(blocks)                        # ...until the caller unrefs too
    chain3, inserted = pc.insert(chain, [50, 51, 52, 53], 0)
    assert chain3 is None and not inserted


def test_prefix_cache_evicts_lru_leaves():
    a = BlockAllocator(8)
    pc = PrefixCache(a, block_size=2)
    chain = None
    blocks = a.alloc(3)
    for i, b in enumerate(blocks):
        chain, _ = pc.insert(chain, [10 + 2 * i, 11 + 2 * i], b)
    a.free(blocks)                        # trie now holds the only refs
    # matching the first block makes the deeper chain the LRU side, but
    # eviction must still take leaves (deepest-first), never a parent a
    # surviving child still chains through
    pc.match([10, 11], max_tokens=2)
    freed = pc.evict(want_free=2)
    assert freed == [blocks[2], blocks[1]]
    assert pc.size == 1 and a.num_allocated == 1
    assert pc.lookup([10, 11, 12], max_tokens=3) == 2


# ---------------------------------------------------------------------------
# engine: prefix hits, COW isolation, compile stability
# ---------------------------------------------------------------------------

def test_prefix_hit_bit_identical_compiles_once(tiny_model):
    cfg, params = tiny_model
    hit = SYS + [20, 21, 22]
    miss = [77, 78, 79, 80, 81]
    reqs = [(SYS, 3), (hit, 4), (miss, 4)]
    ref = _solo_tokens(tiny_model, reqs)
    eng = ServingEngine(cfg, params, _ecfg(prefix_sharing=True))
    got = []
    for i, (p, n) in enumerate(reqs):     # sequential: each later request
        eng.submit(p, n, uid=f"r{i}")     # sees the earlier one's trie
        eng.run()
        got.append(eng.results[f"r{i}"].tokens)
    assert got == ref
    rep = eng.stats.report()
    assert rep["prefix_hit_rate"] > 0 and eng.stats.prefix_hit_tokens == 12
    # hit-rate swings (0% -> 100% -> 0%) never retrace the step
    assert eng.compile_count() == 1
    assert eng.prefix_lookup(hit) == 12


@pytest.mark.parametrize("quantized", [False, True])
def test_cow_isolation_concurrent_sharers(tiny_model, quantized):
    """Two live requests share blocks, one diverges mid-block: the COW
    clone keeps both bit-identical to their solo runs."""
    cfg, params = tiny_model
    kw = (dict(quantized=True, kv_dtype=None) if quantized else {})
    a = SYS + [20, 21, 22, 23, 24]        # seeds blocks incl. [20,21,22,23]
    b = SYS + [20, 21, 40, 41]            # diverges inside that block
    ref_a, ref_a2, ref_b = _solo_tokens(
        tiny_model, [(a, 4), (a, 4), (b, 4)], **kw)
    eng = ServingEngine(cfg, params, _ecfg(prefix_sharing=True, **kw))
    eng.submit(a, 4, uid="a")
    eng.run()
    eng.submit(a, 4, uid="a2")            # full hit on a's blocks
    eng.submit(b, 4, uid="b")             # partial hit -> COW mid-block
    res = eng.run()                       # both decode concurrently
    assert res["a"].tokens == ref_a
    assert res["a2"].tokens == ref_a2
    assert res["b"].tokens == ref_b
    assert eng.stats.cow_copies >= 1
    assert eng.compile_count() == 1


def test_refcount_round_trip_preempt_evict_release(tiny_model):
    """Alloc/free/preempt/evict/teardown: every path unrefs exactly once,
    so after the trie is released the pool is empty."""
    cfg, params = tiny_model
    sys8 = SYS[:8]
    eng = ServingEngine(cfg, params, _ecfg(
        num_blocks=8, max_slots=2, token_budget=8, prefix_sharing=True))
    eng.submit(sys8, 1, uid="seed")
    eng.run()
    assert eng.prefix_cache.size == 2     # sys8 cached, held by the trie
    trie_only = eng.allocator.num_allocated
    assert trie_only == 2
    # pool pressure: two sharers whose growth exceeds the free list makes
    # the engine evict trie leaves / preempt rather than deadlock
    eng.submit(sys8 + [30, 31], 6, uid="p0")
    eng.submit(sys8 + [40, 41], 6, uid="p1")
    eng.submit(sys8 + [50, 51], 6, uid="p2")
    res = eng.run()
    assert all(res[f"p{i}"].status == "completed" for i in range(3))
    # one of the sharers evicted mid-flight hands its blocks back exactly
    # once (the resubmitter owns its fate from here)
    eng.submit(sys8 + [60, 61], 6, uid="gone")
    prompt, generated = eng.evict("gone")
    assert prompt == sys8 + [60, 61] and generated == []
    eng.run()
    eng.release_prefix_cache()
    assert eng.allocator.num_allocated == 0
    assert eng.allocator.num_free == 8
    # and the sharers still decoded greedily like their solo runs
    ref = _solo_tokens(tiny_model, [(sys8 + [30, 31], 6)],
                       num_blocks=8, max_slots=2, token_budget=8)
    assert res["p0"].tokens == ref[0]


# ---------------------------------------------------------------------------
# disaggregated prefill/decode workers
# ---------------------------------------------------------------------------

def test_disagg_parity_and_worker_compile_counts(tiny_model):
    cfg, params = tiny_model
    reqs = [(SYS + [20 + i], 4) for i in range(4)]
    ref = _solo_tokens(tiny_model, reqs)
    eng = ServingEngine(cfg, params, _ecfg(
        disaggregated=True, prefix_sharing=True, prefill_budget=8))
    eng.submit(*reqs[0], uid="d0")        # seeds the trie...
    eng.run()
    for i, (p, n) in enumerate(reqs[1:], start=1):
        eng.submit(p, n, uid=f"d{i}")     # ...the rest share its blocks
    res = eng.run()
    assert [res[f"d{i}"].tokens for i in range(4)] == ref
    # one compiled program per worker, no matter the prefix-hit mix
    assert eng.worker_compile_counts() == {"prefill": 1, "decode": 1}
    assert eng.compile_count() == 1
    assert eng.stats.report()["prefix_hit_rate"] > 0


# ---------------------------------------------------------------------------
# kernel invariance under shared tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("force_pallas", [False, True])
def test_paged_attention_invariant_under_shared_tables(force_pallas):
    """The kernel is read-only over the pool: a table that aliases another
    sequence's block id attends identically to one pointing at a private
    copy of the same rows."""
    rng = np.random.RandomState(3)
    T, N, D, NB, BS = 2, 4, 16, 8, 4
    q = jnp.asarray(rng.randn(T, N, D).astype(np.float32))
    k = jnp.asarray(rng.randn(NB, BS, 2, D).astype(np.float32))
    v = jnp.asarray(rng.randn(NB, BS, 2, D).astype(np.float32))
    pos = jnp.tile(jnp.arange(BS, dtype=jnp.int32)[None, :], (NB, 1))
    pos = pos.at[2].set(jnp.arange(BS, 2 * BS, dtype=jnp.int32))
    q_pos = jnp.asarray([7, 7], jnp.int32)
    # block 7 := copy of block 2 (same rows, same stored positions)
    k, v = k.at[7].set(k[2]), v.at[7].set(v[2])
    pos = pos.at[7].set(pos[2])
    shared = jnp.asarray([[0, 2, -1], [1, 2, -1]], jnp.int32)
    private = jnp.asarray([[0, 2, -1], [1, 7, -1]], jnp.int32)
    out_shared = paged_attention(q, k[None], v[None], pos, shared, q_pos, 0,
                                 force_pallas=force_pallas)
    out_private = paged_attention(q, k[None], v[None], pos, private, q_pos,
                                  0, force_pallas=force_pallas)
    np.testing.assert_array_equal(np.asarray(out_shared),
                                  np.asarray(out_private))
    # membership in a tile's pair is by the row's own table entry, not by
    # its slot: the two rows' tile fetches the block they share once, and
    # the private copies once each
    live = column_live(np.asarray(shared), np.arange(3),
                       np.asarray(q_pos)[:, None], BS)
    for tables, blocks in ((shared, [0, 1, 2]), (private, [0, 1, 2, 7])):
        walk = jax.tree_util.tree_map(
            np.asarray, tile_walk(tables, q_pos, BS, NB, N // 2))
        assert walk.count.tolist() == [len(blocks)]
        assert sorted(walk.blocks[:len(blocks)].tolist()) == blocks
        check_tile_walk(walk, live, np.asarray(tables), 8, N // 2)


@pytest.mark.parametrize("run", [2, 4])
@pytest.mark.parametrize("tables", ["shared", "private"])
def test_a_shared_prefix_block_rides_in_its_groups_run(tables, run):
    """Two decode rows whose heads one narrow group holds: the block they
    share is one pair of that group's run, named by both rows, and the
    private copies one each (``tests/walk_checks.py``)."""
    q_pos = np.asarray([7, 7])
    tables = np.asarray({"shared": [[0, 2, -1], [1, 2, -1]],
                         "private": [[0, 2, -1], [1, 7, -1]]}[tables])
    live = column_live(tables, np.arange(3), q_pos[:, None], 4)
    kinds = check_paged_runs(tables, q_pos, live, 4, 8, 2, run)
    pairs = 3 if tables[1, 1] == 2 else 4
    alone = int(pairs % run == 1)
    assert kinds.tolist() == [pairs - alone, alone, 0]


# ---------------------------------------------------------------------------
# router: prefix-locality placement, failover, budget crediting, stats
# ---------------------------------------------------------------------------

def test_router_prefix_placement_failover_bit_identical(tiny_model):
    """placement="prefix" routes sharers to the replica holding their
    prefix; killing it mid-decode still completes everything with greedy
    tokens matching the fault-free reference."""
    cfg, params = tiny_model
    reqs = [(SYS + [20 + i], 4) for i in range(5)]
    ref = _solo_tokens(tiny_model, reqs, prefix_sharing=True)
    rcfg = RouterConfig(num_replicas=2, placement="prefix")
    router = ReplicaRouter(
        cfg, params, _ecfg(prefix_sharing=True), rcfg,
        chaos=FaultPlan.parse("step|r0 : crash, after=4, times=1"))
    for i, (p, n) in enumerate(reqs):
        router.submit(p, n, uid=f"req{i}")
    res = router.run()
    assert all(r.status == "completed" for r in res.values())
    assert router.stats.availability() == 1.0
    assert [res[f"req{i}"].tokens for i in range(5)] == ref
    assert router.stats.failovers >= 1


def test_router_prefix_placement_prefers_warm_replica(tiny_model):
    cfg, params = tiny_model
    rcfg = RouterConfig(num_replicas=2, placement="prefix")
    router = ReplicaRouter(cfg, params, _ecfg(prefix_sharing=True), rcfg)
    router.submit(SYS + [20], 3, uid="warm")
    router.run()
    warm_on = router.results["warm"].replica
    # later sharers all land on the replica already holding the prefix
    for i in range(3):
        router.submit(SYS + [30 + i], 3, uid=f"s{i}")
    res = router.run()
    assert {res[f"s{i}"].replica for i in range(3)} == {warm_on}
    with pytest.raises(ValueError):
        RouterConfig(num_replicas=2, placement="wat")
        ReplicaRouter(cfg, params, _ecfg(),
                      RouterConfig(num_replicas=2, placement="wat"))


def test_router_credits_prefix_shared_blocks_in_budget(tiny_model):
    """A burst whose raw token total exceeds the global budget is admitted
    when the trie already covers most of each prompt; without sharing the
    same burst trips over_budget (the typed reason stays accurate)."""
    cfg, params = tiny_model

    def drive(sharing):
        ecfg = _ecfg(prefix_sharing=sharing)
        rcfg = RouterConfig(num_replicas=1, global_token_budget=24)
        router = ReplicaRouter(cfg, params, ecfg, rcfg)
        router.submit(SYS + [20, 21], 2, uid="seed")  # raw 16 <= 24
        router.run()
        for i in range(2):                # raw 2 * 16 = 32 > 24
            router.submit(SYS + [30 + i, 40 + i], 2, uid=f"b{i}")
        return router

    router = drive(sharing=True)          # credit 12/prompt: 2 * 4 fits
    res = router.run()
    assert all(res[f"b{i}"].status == "completed" for i in range(2))
    with pytest.raises(RequestRejected) as exc:
        drive(sharing=False)
    assert exc.value.reason == "over_budget"


def test_prefix_stats_surface_engine_and_router(tiny_model):
    cfg, params = tiny_model
    router = ReplicaRouter(cfg, params, _ecfg(prefix_sharing=True),
                           RouterConfig(num_replicas=2,
                                        placement="prefix"))
    router.submit(SYS + [20], 3, uid="r0")
    router.run()
    router.submit(SYS + [21], 3, uid="r1")
    router.run()
    eng_rep = router.replicas[0].engine.stats.report()
    for key in ("prefix_hit_rate", "shared_block_fraction", "cow_copies"):
        assert key in eng_rep
        assert key in router.replicas[0].engine.stats.to_dict()
    agg = router.engine_aggregate()
    assert agg["prefix_hit_rate"] > 0
    assert 0.0 <= agg["shared_block_fraction"] <= 1.0
    assert agg["cow_copies"] >= 0
    d = router.stats_dict()
    assert d["prefix_hit_rate"] == agg["prefix_hit_rate"]
    assert "availability" in d


# ---------------------------------------------------------------------------
# AOT worker registration
# ---------------------------------------------------------------------------

def test_register_serving_workers_trace_compile_forward(tiny_model):
    cfg, params = tiny_model
    ecfg = _ecfg(disaggregated=True, prefill_budget=8)
    nxd = register_serving_workers(
        ModelBuilder(), cfg, ecfg, params).trace().compile()
    assert nxd.keys() == ["chunked_prefill", "token_decode"]
    nxd.state_spec = serving_state_spec(cfg, ecfg)
    cache = nxd.init_state()
    assert cache.block_tables.shape == (ecfg.max_slots,
                                        ecfg.max_blocks_per_seq)
    assert cache.k.shape[1] == ecfg.num_blocks
    for key, width in (("chunked_prefill", 8),
                       ("token_decode", ecfg.max_slots)):
        tokens = jnp.zeros((1, width), jnp.int32)
        positions = jnp.full((1, width), PAD_POSITION, jnp.int32)
        slot_ids = jnp.full((width,), ecfg.max_slots, jnp.int32)
        logits, cache = nxd.forward(key, params, cache, tokens,
                                    positions, slot_ids)
        assert logits.shape == (1, width, cfg.vocab_size)
