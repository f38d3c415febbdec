"""Long-context serving tier: context-parallel prefill + flash-decoding
decode (docs/serving.md "Long-context tier").

Covers the tier's contract surface end to end:

* greedy parity — a cp=4 engine reproduces the cp=1 engine's tokens
  bit-for-bit with the fp32 wire fallback AND with the default int8
  quantized KV hops, compiling each worker exactly once;
* capacity — a prompt that busts one mesh's pool is rejected
  (``never_fits``) at cp=1 with the allocator raising
  :class:`CacheExhaustedError`, and serves at cp=4 (global pool =
  ``cp * num_blocks``);
* the compile_count()==1 invariant across mixed session lengths;
* config guard rails — every engine feature the tier rejects raises a
  pointed ValueError at construction, not three steps into a session;
* the CP-sharded :class:`BlockAllocator` rank-slice math and
  :func:`pool_accounting`'s pool-over-cp memory term;
* :func:`pick_bucket`'s cp-scaled bucket boundaries;
* fabric mode — a CP prefill engine streams per-rank block shards
  (``StreamConfig.cp_shards``) to a plain decode worker, bit-identical
  and all-shards-or-nothing atomic under a torn stream;
* the router's long-context replica class routing by prompt length
  (explicit threshold and capacity-implicit);
* the planner surfacing ``cp>1`` for long-context mixes whose pool no
  single mesh holds, while short mixes keep ranking cp=1 first.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      RequestRejected,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.generation import (DECODE_BUCKETS,
                                                          pick_bucket)
from neuronx_distributed_tpu.inference.paging import (BlockAllocator,
                                                      CacheExhaustedError,
                                                      pool_accounting)
from neuronx_distributed_tpu.inference.router import (ReplicaRouter,
                                                      RouterConfig)
from neuronx_distributed_tpu.inference.speculative import SpeculationConfig
from neuronx_distributed_tpu.inference.transport import (DcnLink,
                                                         KVStreamTransport,
                                                         StreamConfig)
from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                  tiny_config)
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.plan import (ModelSpec, TrafficSpec,
                                          default_hardware, serving_search)
from neuronx_distributed_tpu.plan.cost import param_count, serving_pool_blocks
from neuronx_distributed_tpu.resilience import FaultPlan


@pytest.fixture(scope="module")
def tiny_model():
    # params are built MESH-FREE on purpose: arrays committed to a live
    # mesh re-key the jit cache once that mesh is destroyed and rebuilt,
    # and the tests below bring up a fresh (plain or cp=4) mesh each —
    # uncommitted params survive every swap without recompiles
    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, params


def _tokens(n, seed=7, vocab=256):
    return np.random.RandomState(seed).randint(1, vocab - 1, (n,)).tolist()


_PROMPT = _tokens(13)


def _plain(tiny_model, **kw):
    cfg, params = tiny_model
    base = dict(block_size=4, num_blocks=32, max_slots=2,
                max_blocks_per_seq=16, token_budget=16,
                kv_dtype=jnp.float32)
    base.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**base))


def _cp(tiny_model, cp=4, **kw):
    cfg, params = tiny_model
    base = dict(block_size=4, num_blocks=8, max_slots=2,
                max_blocks_per_seq=16, token_budget=16,
                kv_dtype=jnp.float32, cp=cp, cp_prefill_width=32)
    base.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**base))


@pytest.fixture(scope="module")
def ref_tokens(tiny_model):
    """Greedy reference: the same prompt on a plain cp=1 engine."""
    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    eng = _plain(tiny_model)
    uid = eng.submit(_PROMPT, 8)
    toks = eng.run()[uid].tokens
    ps.destroy_model_parallel()
    return toks


# ---------------------------------------------------------------------------
# engine: parity, capacity, compile-once, guard rails
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_cp_greedy_parity_and_compile_once(tiny_model, ref_tokens, wire):
    """cp=4 reproduces the cp=1 greedy tokens bitwise — with the fp32
    wire fallback (bitwise by construction) and with the default int8
    quantized ring hops — and each CP worker compiles exactly once."""
    ps.initialize_model_parallel(context_parallel_size=4)
    eng = _cp(tiny_model, cp_wire_dtype=wire)
    uid = eng.submit(_PROMPT, 8)
    assert eng.run()[uid].tokens == ref_tokens
    assert eng.worker_compile_counts() == {"packed": 1, "cp_prefill": 1}


def test_cp_mixed_session_lengths_compile_once(tiny_model):
    ps.initialize_model_parallel(context_parallel_size=4)
    eng = _cp(tiny_model)
    for n, new in ((5, 4), (13, 8), (29, 5)):
        uid = eng.submit(_tokens(n, seed=n), new)
        res = eng.run()[uid]
        assert res.tokens, (n, res)
    assert eng.compile_count() == 1, eng.worker_compile_counts()
    assert eng.worker_compile_counts() == {"packed": 1, "cp_prefill": 1}


def test_long_prompt_oom_at_cp1_serves_at_cp4(tiny_model):
    """The tier's reason to exist: a prompt over one mesh's pool is a
    pointed never_fits rejection at cp=1 (the allocator agrees) and a
    served request at cp=4, where the global pool is cp * num_blocks."""
    ps.initialize_model_parallel()
    eng1 = _plain(tiny_model, num_blocks=8)     # 8 blocks * 4 = 32 tokens
    long_prompt = _tokens(40, seed=3)
    with pytest.raises(RequestRejected) as ei:
        eng1.submit(long_prompt, 8)
    assert ei.value.reason == "never_fits"
    with pytest.raises(CacheExhaustedError):
        eng1.allocator.alloc(12)                # ceil(48 / block_size)
    ps.destroy_model_parallel()

    ps.initialize_model_parallel(context_parallel_size=4)
    eng4 = _cp(tiny_model, cp_prefill_width=64)  # same 8 blocks PER RANK
    uid = eng4.submit(long_prompt, 8)
    res = eng4.run()[uid]
    assert len(res.tokens) == 8
    assert eng4.max_model_len() >= 48 > eng1.max_model_len()


@pytest.mark.parametrize("kw,msg", [
    (dict(prefix_sharing=True), "CP-sharded"),
    (dict(speculation=SpeculationConfig()), "lane clones"),
    (dict(disaggregated=True, prefill_budget=8), "prefill/decode split"),
    (dict(quantized=True), "quantized pools"),
])
def test_cp_guard_rails_reject_incompatible_features(tiny_model, kw, msg):
    with pytest.raises(ValueError, match=msg):
        _cp(tiny_model, **kw)


def test_cp_refuses_a_family_that_names_it_and_says_why():
    """The cp tier asks the family, not the identity of its forward: a
    MixtralConfig is refused at construction (params never read), with
    the family's own reason."""
    from neuronx_distributed_tpu.models.mixtral import tiny_moe_config

    with pytest.raises(ValueError, match="MixtralConfig cannot be served "
                                         "with cp: .*expert capacity"):
        _cp((tiny_moe_config(), None))


def test_cp_requires_matching_mesh(tiny_model):
    ps.initialize_model_parallel()      # plain mesh, no cp axis
    with pytest.raises(ValueError, match="context_parallel_size"):
        _cp(tiny_model)


def test_cp_prefill_width_must_tile_over_ranks(tiny_model):
    ps.initialize_model_parallel(context_parallel_size=4)
    with pytest.raises(ValueError, match="must split into"):
        _cp(tiny_model, cp_prefill_width=30)    # not cp*block_size-aligned


# ---------------------------------------------------------------------------
# CP-sharded pool: allocator rank slices + memory accounting
# ---------------------------------------------------------------------------

def test_allocator_pool_must_divide_over_cp():
    with pytest.raises(ValueError, match="divide evenly"):
        BlockAllocator(10, cp_size=4)


def test_allocator_rank_slices_strict_and_spill():
    a = BlockAllocator(16, cp_size=4)
    assert a.blocks_per_rank == 4
    assert [a.rank_of(b) for b in (0, 5, 15)] == [0, 1, 3]
    assert a.free_per_rank() == [4, 4, 4, 4]

    # strict placement: rank-pinned blocks come from that rank's slice
    got = a.alloc(2, rank=1)
    assert all(4 <= b < 8 for b in got)
    assert a.free_per_rank() == [4, 2, 4, 4]
    with pytest.raises(CacheExhaustedError, match="on cp rank 1"):
        a.alloc(3, rank=1)

    # spill: unpinned allocation balances onto the most-free slice
    spill = a.alloc(1)
    assert a.rank_of(spill[0]) != 1
    # ...and fails only when the WHOLE pool is short
    a.alloc(a.num_free)
    with pytest.raises(CacheExhaustedError):
        a.alloc(1)

    # freed blocks return to their owning rank's slice
    a.free(got)
    assert a.free_per_rank() == [0, 2, 0, 0]
    back = a.alloc(2, rank=1)
    assert sorted(back) == sorted(got)


def test_pool_accounting_divides_by_cp():
    kw = dict(num_layers=4, num_blocks=64, block_size=8,
              num_kv_heads=8, head_dim=32)
    base = pool_accounting(**kw)
    assert pool_accounting(cp_size=4, **kw) == pytest.approx(base / 4)
    assert pool_accounting(cp_size=4, tp_size=2, **kw) == \
        pytest.approx(base / 8)
    with pytest.raises(ValueError, match="cp_size"):
        pool_accounting(cp_size=0, **kw)


def test_pick_bucket_scales_boundaries_by_cp():
    assert pick_bucket(100, DECODE_BUCKETS) == 256
    # the cp group holds cp single-mesh slices: every boundary scales
    assert pick_bucket(100, DECODE_BUCKETS, cp=4) == 256
    assert pick_bucket(1500, DECODE_BUCKETS, cp=4) == 4096
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        pick_bucket(5000, DECODE_BUCKETS)
    assert pick_bucket(5000, DECODE_BUCKETS, cp=8) == 8192


# ---------------------------------------------------------------------------
# fabric mode: CP prefill tier streams per-rank shards to plain decoders
# ---------------------------------------------------------------------------

_STREAM = StreamConfig(bandwidth=50e3, latency_s=1e-3, wire_dtype="fp32",
                       cp_shards=4)


def _drive(tr, link, t=0.0, t_max=30.0):
    while tr.state == "streaming" and t < t_max:
        nxts = [x for x in (link.next_deliver(), tr.next_timer())
                if x is not None]
        if not nxts:
            break
        t = max(t, min(nxts))
        for _route, data in link.deliver(t):
            tr.on_wire(data, t)
        tr.pump(t)
    return t


def _finish(eng, uid, t_max=200):
    for _ in range(t_max):
        if uid in eng.results:
            return eng.results[uid]
        eng.step()
    raise AssertionError("request never completed")


def _cp_ticket(tiny_model, n_decode=2):
    """A KV-bearing ticket exported from a CP prefill engine: 16-token
    prompt -> >= 4 pool blocks, so every slab splits over cp_shards."""
    src = _cp(tiny_model)
    uid = src.submit(_tokens(16, seed=11), 6, uid="req0")
    for _ in range(1 + n_decode):
        src.step()
    assert src.handoff_ready(uid)
    return src, src.export_session(uid)


def test_cp_prefill_streams_shards_to_plain_decoder(tiny_model):
    ps.initialize_model_parallel(context_parallel_size=4)
    # reference: the whole request prefills AND decodes on a plain engine
    ref = _plain(tiny_model)
    ref.submit(_tokens(16, seed=11), 6, uid="req0")
    ref_tokens = _finish(ref, "req0").tokens

    src, ticket = _cp_ticket(tiny_model)
    dst = _plain(tiny_model)        # plain decode worker, same mesh
    link = DcnLink(bandwidth=_STREAM.bandwidth, latency_s=_STREAM.latency_s)
    tr = KVStreamTransport(ticket, dst, link, "cp->d0/req0", _STREAM)
    tr.start(0.0)
    _drive(tr, link)
    assert tr.state == "committed"
    # the per-layer K/V slabs (2 layers x k,v) each split into cp_shards
    # disjoint block-subset chunks riding the wire concurrently
    assert tr.stats.chunks >= _STREAM.cp_shards * 4
    tokens = _finish(dst, "req0").tokens
    assert tokens == ref_tokens
    assert dst.compile_count() == 1


def test_cp_sharded_torn_stream_is_all_or_nothing(tiny_model):
    ps.initialize_model_parallel(context_parallel_size=4)
    src, ticket = _cp_ticket(tiny_model)
    dst = _plain(tiny_model)
    base_free = dst.pool_free_blocks()
    plan = FaultPlan.parse("seed=3; link|* : link_partition, times=1")
    link = DcnLink(bandwidth=_STREAM.bandwidth,
                   latency_s=_STREAM.latency_s, chaos=plan)
    tr = KVStreamTransport(ticket, dst, link, "cp->d0/req0", _STREAM)
    tr.start(0.0)
    _drive(tr, link)
    assert tr.state == "aborted"
    # all-shards-or-nothing: no partial shard landed, no block leaked
    assert dst.pool_free_blocks() == base_free
    assert not dst.handoff_ready("req0")
    assert "req0" not in dst.results


def test_stream_config_rejects_bad_cp_shards():
    with pytest.raises(ValueError, match="cp_shards"):
        StreamConfig(cp_shards=0)


# ---------------------------------------------------------------------------
# router: the long-context replica class
# ---------------------------------------------------------------------------

def _lc_cfg(**kw):
    base = dict(block_size=4, num_blocks=8, max_slots=2,
                max_blocks_per_seq=16, token_budget=16,
                kv_dtype=jnp.float32, cp=4, cp_prefill_width=48)
    base.update(kw)
    return EngineConfig(**base)


def test_router_routes_long_prompts_by_threshold(tiny_model):
    cfg, params = tiny_model
    ps.initialize_model_parallel(context_parallel_size=4)
    rcfg = RouterConfig(num_replicas=1, long_context_replicas=1,
                        long_context_engine=_lc_cfg(),
                        long_context_threshold=16)
    router = ReplicaRouter(cfg, params, EngineConfig(
        block_size=4, num_blocks=16, max_slots=2, max_blocks_per_seq=8,
        token_budget=8, kv_dtype=jnp.float32), rcfg)
    u_short = router.submit(_tokens(6, seed=1), 4)
    u_long = router.submit(_tokens(20, seed=2), 4)
    res = router.run()
    assert res[u_short].status == "completed"
    assert res[u_long].status == "completed"
    assert res[u_short].replica == "r0"     # under threshold: plain class
    assert res[u_long].replica == "l0"      # at threshold: CP class


def test_router_capacity_implicit_long_context_routing(tiny_model):
    """No threshold set: capacity IS the threshold — a prompt no plain
    replica could hold routes to the CP class instead of never_fits."""
    cfg, params = tiny_model
    ps.initialize_model_parallel(context_parallel_size=4)
    rcfg = RouterConfig(num_replicas=1, long_context_replicas=1,
                        long_context_engine=_lc_cfg())
    router = ReplicaRouter(cfg, params, EngineConfig(
        block_size=4, num_blocks=16, max_slots=2, max_blocks_per_seq=8,
        token_budget=8, kv_dtype=jnp.float32), rcfg)
    # 36 + 4 tokens > the plain replica's 32-token per-seq ceiling
    u_long = router.submit(_tokens(36, seed=5), 4)
    res = router.run()
    assert res[u_long].status == "completed"
    assert res[u_long].replica == "l0"


def test_router_long_context_config_errors(tiny_model):
    cfg, params = tiny_model
    ps.initialize_model_parallel()
    ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=8, token_budget=8,
                        kv_dtype=jnp.float32)
    with pytest.raises(ValueError, match="cp > 1"):
        ReplicaRouter(cfg, params, ecfg, RouterConfig(
            num_replicas=1, long_context_replicas=1,
            long_context_engine=dataclasses.replace(ecfg)))
    with pytest.raises(ValueError, match="long_context_engine"):
        ReplicaRouter(cfg, params, ecfg, RouterConfig(
            num_replicas=1, long_context_replicas=1))


# ---------------------------------------------------------------------------
# planner: the cp axis in serving_search
# ---------------------------------------------------------------------------

_TINY_MS = ModelSpec(name="tiny", vocab=1024, hidden=256,
                     intermediate=704, layers=4, heads=8, kv_heads=8,
                     seq=65536, global_batch=8)
_HW = default_hardware("tpu")


def test_serving_search_long_mix_surfaces_cp_tier():
    """A long-context mix whose KV pool no single device holds ranks a
    cp>1 plan (per-rank pool = total / cp fits), int8 wire and a
    cp-tiled block-table width on the emitted engine dict."""
    long_mix = TrafficSpec(request_rate=0.05, prompt_tokens=16384.0,
                           new_tokens=64.0)
    nb1 = serving_pool_blocks(_TINY_MS, long_mix, block_size=8,
                              max_slots=1)
    rank_bytes = pool_accounting(num_layers=4, num_blocks=nb1,
                                 block_size=8, num_kv_heads=8, head_dim=32)
    hw = dataclasses.replace(_HW, hbm_bytes=rank_bytes / 2,
                             memory_fraction=1.0)
    plans = serving_search(_TINY_MS, hw, long_mix, cps=(1, 4))
    assert plans
    assert all(p.engine.get("cp", 1) == 4 for p in plans)
    best = plans[0]
    assert best.engine["cp_wire_dtype"] == "int8"
    assert best.engine["max_blocks_per_seq"] % 4 == 0


def test_serving_search_cp_plan_constructs_and_runs(tiny_model):
    """The emitted cp>1 engine dict is directly constructible: build the
    EngineConfig it names on a cp mesh and serve a request through it.
    Modest scale (seq=512 reference model) keeps the ring-prefill width
    compile-friendly; the memory squeeze still forces the CP tier."""
    cfg, params = tiny_model
    m = dataclasses.replace(_TINY_MS, seq=512)
    mix = TrafficSpec(request_rate=0.05, prompt_tokens=400.0,
                      new_tokens=16.0)
    nb1 = serving_pool_blocks(m, mix, block_size=8, max_slots=1)
    rank_bytes = pool_accounting(num_layers=4, num_blocks=nb1,
                                 block_size=8, num_kv_heads=8, head_dim=32)
    # resident weights are charged against the budget too, so the
    # squeeze is weights + half the single-rank pool: cp=1 can't fit
    # its pool, cp=4's quarter-pool shard fits
    w_bytes = param_count(m) * m.act_bytes
    hw = dataclasses.replace(_HW, hbm_bytes=w_bytes + rank_bytes / 2,
                             memory_fraction=1.0)
    plans = serving_search(m, hw, mix, cps=(1, 4))
    assert plans
    best = plans[0]
    cp = best.engine.get("cp", 1)
    assert cp == 4
    ps.initialize_model_parallel(context_parallel_size=cp)
    eng = ServingEngine(cfg, params, EngineConfig(**best.engine))
    uid = eng.submit(_tokens(13), 4)
    res = eng.run()[uid]
    assert len(res.tokens) == 4
    assert eng.compile_count() == 1


def test_serving_search_short_mix_keeps_cp1():
    """Per-mesh goodput ranking: a cp-degree replica occupies cp meshes,
    so short mixes (which fit one mesh) keep ranking cp=1 first."""
    plans = serving_search(_TINY_MS, _HW,
                           TrafficSpec(request_rate=1.0), cps=(1, 4))
    assert plans
    assert plans[0].engine.get("cp", 1) == 1
