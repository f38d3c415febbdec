"""ServingEngine tests: continuous batching produces the same greedy
tokens as solo ``generate()``, the step compiles once regardless of the
live-request mix, and blocks are reclaimed/rejected/preempted correctly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      RequestRejected,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.generation import generate
from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                  tiny_config)
from neuronx_distributed_tpu.parallel import mesh as ps


@pytest.fixture
def tiny_model():
    ps.initialize_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, params


def _ecfg(**kw):
    base = dict(block_size=4, num_blocks=16, max_slots=2,
                max_blocks_per_seq=8, token_budget=8,
                kv_dtype=jnp.float32)
    base.update(kw)
    return EngineConfig(**base)


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    return ServingEngine(cfg, params, _ecfg(**kw))


def _prompt(seed, n, vocab):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).tolist()


def test_solo_request_matches_generate(tiny_model):
    cfg, params = tiny_model
    prompt = _prompt(0, 7, cfg.vocab_size)
    ref = np.asarray(generate(cfg, params, jnp.asarray([prompt]),
                              jnp.array([7], jnp.int32), 8))[0].tolist()
    eng = _engine(tiny_model)
    eng.submit(prompt, max_new_tokens=8, uid="a")
    res = eng.run()["a"]
    assert res.status == "completed"
    assert res.tokens == ref  # greedy: bit-identical to the static path
    assert res.ttft_s is not None and res.ttft_s >= 0


def test_late_arrival_is_bit_identical_to_solo(tiny_model):
    """A request admitted mid-flight (while another decodes) finishes
    with exactly the tokens it would get alone — paged attention keeps
    slots independent and greedy sampling is rng-free."""
    cfg, params = tiny_model
    pa = _prompt(3, 9, cfg.vocab_size)
    pb = _prompt(4, 5, cfg.vocab_size)

    def solo(prompt):
        e = _engine(tiny_model)
        e.submit(prompt, max_new_tokens=6, uid="x")
        return e.run()["x"].tokens

    ra, rb = solo(pa), solo(pb)
    eng = _engine(tiny_model)
    eng.submit(pa, max_new_tokens=6, uid="a")
    for _ in range(3):
        eng.step()
    eng.submit(pb, max_new_tokens=6, uid="b")
    res = eng.run()
    assert res["a"].tokens == ra
    assert res["b"].tokens == rb


def test_step_compiles_once_across_load_changes(tiny_model):
    """The no-recompile invariant: 1, then 2, then 0, then 1 live
    requests — every step runs the same compiled program."""
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    eng.submit(_prompt(5, 6, cfg.vocab_size), 4, uid="a")
    eng.step()
    eng.submit(_prompt(6, 3, cfg.vocab_size), 4, uid="b")  # 2 live
    eng.run()                                              # drain to 0
    eng.submit(_prompt(7, 11, cfg.vocab_size), 3, uid="c")
    res = eng.run()
    assert {r.status for r in res.values()} == {"completed"}
    assert eng.compile_count() == 1


def test_retired_requests_free_their_blocks(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    eng.submit(_prompt(8, 6, cfg.vocab_size), 4)
    eng.run()
    assert eng.allocator.num_allocated == 0
    assert (eng._tables == -1).all()


def test_oversize_request_rejected_at_submit(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    # needs more blocks than max_blocks_per_seq can ever map
    with pytest.raises(RequestRejected) as exc:
        eng.submit(_prompt(9, 30, cfg.vocab_size), 10, uid="big")
    assert exc.value.reason == "never_fits"
    assert eng.results["big"].status == "rejected"
    assert eng.stats.rejected == 1
    assert not eng.has_work()
    with pytest.raises(RequestRejected) as exc:
        eng.submit([], 4, uid="empty")
    assert exc.value.reason == "never_fits"
    assert eng.results["empty"].status == "rejected"


def test_preemption_restarts_and_completes(tiny_model):
    """A pool sized so two requests can't both finish forces the
    youngest to be preempted; it restarts from its prompt and still
    produces its solo tokens."""
    cfg, params = tiny_model
    pa = _prompt(10, 8, cfg.vocab_size)
    pb = _prompt(11, 8, cfg.vocab_size)

    def solo(prompt):
        e = _engine(tiny_model)
        e.submit(prompt, max_new_tokens=6, uid="x")
        return e.run()["x"].tokens

    ra, rb = solo(pa), solo(pb)
    # 5 blocks of 4 = 20 KV slots; each request needs 14 -> can't coexist
    eng = _engine(tiny_model, num_blocks=5, max_blocks_per_seq=4)
    eng.submit(pa, max_new_tokens=6, uid="a")
    eng.submit(pb, max_new_tokens=6, uid="b")
    res = eng.run()
    assert eng.stats.preempted >= 1
    assert res["a"].tokens == ra
    assert res["b"].tokens == rb
    assert eng.allocator.num_allocated == 0


def test_eos_retires_early(tiny_model):
    cfg, params = tiny_model
    prompt = _prompt(12, 6, cfg.vocab_size)
    probe = _engine(tiny_model)
    probe.submit(prompt, max_new_tokens=8, uid="x")
    toks = probe.run()["x"].tokens
    eos = toks[2]  # pretend the 3rd sampled token is the eos
    eng = _engine(tiny_model, eos_id=eos)
    eng.submit(prompt, max_new_tokens=8, uid="a")
    res = eng.run()["a"]
    # retires at the FIRST eos (the tiny model may emit it even earlier)
    assert res.tokens == toks[:toks.index(eos) + 1]
    assert res.tokens[-1] == eos
    assert len(res.tokens) < 8


def test_quantized_engine_smoke(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model, quantized=True, kv_dtype=None)
    eng.submit(_prompt(13, 6, cfg.vocab_size), 4, uid="a")
    res = eng.run()["a"]
    assert res.status == "completed" and len(res.tokens) == 4
    assert eng.cache.k.dtype == jnp.int8


def test_stats_report_fields(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    eng.submit(_prompt(14, 5, cfg.vocab_size), 4)
    eng.run()
    rep = eng.stats.report()
    assert rep["completed"] == 1 and rep["tokens_generated"] == 4
    for key in ("tokens_per_s", "ttft_p50_ms", "ttft_p99_ms",
                "step_latency_p50_ms", "step_latency_p99_ms",
                "pool_occupancy_mean"):
        assert key in rep and rep[key] >= 0


def test_decode_buckets_share_one_compile(tiny_model):
    """Satellite: two different max_new_tokens within one decode bucket
    reuse a single compiled scan."""
    from neuronx_distributed_tpu.inference.generation import (
        _jit_decode_scan)

    cfg, params = tiny_model
    ids = jnp.asarray(_prompt(15, 8, cfg.vocab_size))[None]
    plen = jnp.array([8], jnp.int32)
    a = generate(cfg, params, ids, plen, 5, buckets=(8,),
                 decode_buckets=(16,))
    b = generate(cfg, params, ids, plen, 9, buckets=(8,),
                 decode_buckets=(16,))
    assert a.shape == (1, 5) and b.shape == (1, 9)
    # both lengths bucket to 16 steps -> one scan compile
    assert _jit_decode_scan(cfg, 16)._cache_size() == 1
    # the shorter run is a prefix of the longer (greedy, same prompt)
    assert np.asarray(a)[0].tolist() == np.asarray(b)[0, :5].tolist()


def test_router_hooks_gauges_and_stats_to_dict(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    assert eng.queue_depth() == 0
    assert eng.pool_free_blocks() == eng.allocator.num_blocks
    eng.submit(_prompt(16, 6, cfg.vocab_size), 4, uid="a")
    assert eng.queue_depth() == 1
    eng.step()
    assert eng.pool_free_blocks() < eng.allocator.num_blocks
    eng.run()
    assert eng.queue_depth() == 0
    d = eng.stats.to_dict()
    for key in ("rejected", "resubmitted", "queue_depth", "completed",
                "ttft_p99_ms"):
        assert key in d
    assert d["queue_depth"] == 0 and d["resubmitted"] == 0


def test_drain_mode_rejects_but_keeps_stepping(tiny_model):
    cfg, params = tiny_model
    eng = _engine(tiny_model)
    eng.submit(_prompt(17, 6, cfg.vocab_size), 4, uid="a")
    eng.step()
    eng.drain()
    assert eng.draining
    with pytest.raises(RequestRejected) as exc:
        eng.submit(_prompt(18, 4, cfg.vocab_size), 4, uid="late")
    assert exc.value.reason == "draining"
    res = eng.run()  # in-flight work still finishes
    assert res["a"].status == "completed" and len(res["a"].tokens) == 4


def test_evict_returns_progress_and_frees_blocks(tiny_model):
    cfg, params = tiny_model
    prompt = _prompt(19, 6, cfg.vocab_size)
    eng = _engine(tiny_model)
    eng.submit(prompt, max_new_tokens=6, uid="a")
    for _ in range(3):
        eng.step()
    assert eng.allocator.num_allocated > 0
    got_prompt, got_gen = eng.evict("a")
    assert got_prompt == prompt and len(got_gen) >= 1
    assert eng.allocator.num_allocated == 0
    assert eng.stats.resubmitted == 1
    assert not eng.has_work() and "a" not in eng.results
    with pytest.raises(KeyError):
        eng.evict("a")
    # a queued (never-admitted) request evicts with no generated tokens
    eng2 = _engine(tiny_model)
    eng2.submit(prompt, max_new_tokens=2, uid="q",
                arrival_time=1e9)  # far future: stays queued
    qp, qg = eng2.evict("q")
    assert qp == prompt and qg == []
