"""The packed step runs one deep in flight: ``step()`` enqueues step n+1
before it reads step n, a decode row whose token is still on the device
takes it there, and what the host knows of a step at its enqueue is kept
apart from what needs its values. Every request's tokens are those of the
engine's serial modes and of a plain greedy loop over the model."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from neuronx_distributed_tpu import obs
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      RequestRejected,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.inference.sampling import SamplingConfig
from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                  llama_forward_with_cache,
                                                  tiny_config)
from neuronx_distributed_tpu.parallel import mesh as ps

MAX_LEN = 32


@pytest.fixture(autouse=True)
def _fresh_obs():
    was = obs.enabled()
    obs.reset()
    yield
    obs.reset()
    obs.enable() if was else obs.disable()


@pytest.fixture
def tiny_model():
    ps.initialize_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, params


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    base = dict(block_size=4, num_blocks=24, max_slots=3,
                max_blocks_per_seq=8, token_budget=8, kv_dtype=jnp.float32)
    base.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**base))


def _prompt(seed, n, vocab):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).tolist()


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(cfg, params, ids, last):
    return LlamaForCausalLM(cfg).apply(params, ids)[0, last]


def _greedy_loop(tiny_model, prompt, new, eos=None):
    """A plain greedy loop over the model's own forward, no cache, no
    engine: one token a call, the sequence padded to one length (causal:
    what follows a position does not reach it)."""
    seq, out = list(prompt), []
    for _ in range(new):
        ids = np.zeros((1, MAX_LEN), np.int32)
        ids[0, :len(seq)] = seq
        tok = int(jnp.argmax(_logits_at(*tiny_model, jnp.asarray(ids),
                                        len(seq) - 1)))
        out.append(tok)
        seq.append(tok)
        if tok == eos:
            break
    return out


#: what each scenario serves and what it must have exercised
SCENARIOS = {
    # five requests over three slots: chunks of prefill beside decode rows
    "mixed": dict(
        requests=[(0, 13, 6), (1, 5, 8), (2, 9, 4), (3, 21, 5), (4, 3, 7)],
        engine={}, saw=lambda st: st.steps > 8),
    # a pool too small for its slots: the youngest is preempted
    "preemption": dict(
        requests=[(5, 9, 12), (6, 10, 12), (7, 7, 12), (8, 6, 9)],
        engine=dict(num_blocks=9), saw=lambda st: st.preempted > 0),
    # the fourth request's prompt begins with the first's, which the trie
    # holds when a slot comes free
    "prefix_hit": dict(
        requests=[(9, 12, 4), (10, 6, 9), (11, 7, 9), (9, 12, 6, 5)],
        engine=dict(prefix_sharing=True),
        saw=lambda st: st.prefix_hit_tokens > 0),
}


def _requests(spec, vocab):
    out = []
    for i, (seed, n, new, *more) in enumerate(spec):
        prompt = _prompt(seed, n, vocab)
        if more:        # the same prompt and then some
            prompt = prompt + _prompt(seed + 100, more[0], vocab)
        out.append((f"r{i}", prompt, new))
    return out


def _serve(eng, requests):
    for uid, prompt, new in requests:
        eng.submit(prompt, new, uid=uid)
    while eng.has_work():
        eng.step()
    return {u: r.tokens for u, r in eng.results.items()}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_tokens_equal_the_serial_modes_and_a_plain_loop(tiny_model, name):
    sc = SCENARIOS[name]
    requests = _requests(sc["requests"], tiny_model[0].vocab_size)
    eng = _engine(tiny_model, **sc["engine"])
    got = _serve(eng, requests)
    assert sc["saw"](eng.stats), eng.stats
    assert eng._depth == 1 and eng.compile_count() == 1
    serial = _engine(tiny_model, disaggregated=True, **sc["engine"])
    assert serial._depth == 0
    assert got == _serve(serial, requests)
    assert got == {uid: _greedy_loop(tiny_model, prompt, new)
                   for uid, prompt, new in requests}
    assert eng.stats.tokens_generated >= sum(len(t) for t in got.values())


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_seeded_sampling_draws_the_tokens_of_the_step_read_at_once(
        tiny_model, name):
    """The same engine at depth 0 reads every step before it schedules
    the next, as the engine did before a step could be in flight: same
    schedule, same rows, same split of the key, so the same draws."""
    sc = SCENARIOS[name]
    requests = _requests(sc["requests"], tiny_model[0].vocab_size)
    kw = dict(sampling=SamplingConfig(temperature=0.8, top_k=20),
              **sc["engine"])
    eng = _engine(tiny_model, **kw)
    got = _serve(eng, requests)
    at_once = _engine(tiny_model, **kw)
    at_once._depth = 0
    want = _serve(at_once, requests)
    assert got == want
    assert got != {uid: _greedy_loop(tiny_model, prompt, new)
                   for uid, prompt, new in requests}
    for field in ("steps", "preempted", "tokens_generated",
                  "prefix_hit_tokens", "prefill_tokens", "cow_copies"):
        assert getattr(eng.stats, field) == getattr(at_once.stats, field)


def test_an_eos_mid_batch_ends_its_request_and_drops_the_extra_row(
        tiny_model):
    """``eos_id`` sampled in step n is known when step n lands, after step
    n+1 was enqueued with one more row for the request: that row's token
    is dropped, the request ends on EOS, the others run on."""
    requests = _requests(SCENARIOS["mixed"]["requests"],
                         tiny_model[0].vocab_size)
    free = {uid: _greedy_loop(tiny_model, prompt, new)
            for uid, prompt, new in requests}
    # a token that some request samples in the middle of its answer
    uid = max(free, key=lambda u: len(free[u]))
    eos = free[uid][len(free[uid]) // 2]
    want = {u: _greedy_loop(tiny_model, prompt, new, eos=eos)
            for u, prompt, new in requests}
    assert len(want[uid]) < len(free[uid]) and want[uid][-1] == eos
    eng = _engine(tiny_model, eos_id=eos)
    assert _serve(eng, requests) == want
    serial = _engine(tiny_model, eos_id=eos, disaggregated=True)
    assert _serve(serial, requests) == want
    # the wasted rows are no tokens
    assert eng.stats.tokens_generated == sum(len(t) for t in want.values())


def test_a_step_is_enqueued_before_the_one_before_it_is_fetched(tiny_model):
    obs.enable()
    eng = _engine(tiny_model)
    _serve(eng, _requests(SCENARIOS["mixed"]["requests"],
                          tiny_model[0].vocab_size))
    steps = {c.labels["kind"]: c.value for c in obs.get_registry().get(
        "nxd_engine_steps_total").children()}
    assert steps["serial"] == 1                 # the first: nothing before it
    assert steps["overlapped"] == eng.stats.steps - 1 > 8
    events = sorted(
        (ev for ev in obs.get_tracer().chrome_trace()["traceEvents"]
         if ev["name"] in ("engine/packed/dispatch", "engine/packed/fetch")),
        key=lambda ev: ev["ts"])
    order = [ev["name"].rsplit("/", 1)[1] for ev in events]
    # two enqueues before the first read, then one read an enqueue, and
    # the last call has nothing to enqueue and reads the last step
    assert order[:3] == ["dispatch", "dispatch", "fetch"]
    assert order == (["dispatch"] + ["dispatch", "fetch"]
                     * (eng.stats.steps - 1) + ["fetch"])
    # the serial modes read each step at once
    obs.reset()
    obs.enable()
    serial = _engine(tiny_model, disaggregated=True)
    _serve(serial, _requests(SCENARIOS["mixed"]["requests"],
                             tiny_model[0].vocab_size))
    steps = {c.labels["kind"]: c.value for c in obs.get_registry().get(
        "nxd_engine_steps_total").children()}
    assert steps == {"overlapped": 0, "serial": serial.stats.steps}


def test_has_work_holds_while_a_step_is_in_flight(tiny_model):
    cfg, _ = tiny_model
    eng = _engine(tiny_model)
    eng.submit(_prompt(0, 5, cfg.vocab_size), 1, uid="a")
    assert eng.step() == 5
    # the one token is sampled and not read: the slot is free already,
    # the result is not there yet
    assert eng._inflight is not None and eng.has_work()
    assert eng.queue_depth() == 0 and "a" not in eng.results
    assert eng.stats.tokens_generated == 0 and not eng.stats.ttft_s
    assert eng.step() == 5              # nothing to pack: the step lands
    assert not eng.has_work() and eng._inflight is None
    assert len(eng.results["a"].tokens) == 1 and len(eng.stats.ttft_s) == 1
    assert eng.stats.steps == 1 and eng.step() == 0


def test_run_drain_and_export_land_the_step_in_flight_first(tiny_model):
    cfg, _ = tiny_model
    prompt = _prompt(1, 6, cfg.vocab_size)
    want = _greedy_loop(tiny_model, prompt, 5)

    def started(**kw):
        eng = _engine(tiny_model, **kw)
        eng.submit(prompt, 5, uid="a")
        eng.step()
        eng.step()
        assert eng._inflight is not None
        return eng

    eng = started()
    assert eng.run()["a"].tokens == want and not eng.has_work()

    eng = started()
    eng.drain()
    assert eng._inflight is None and eng._slots[0].generated == want[:2]
    with pytest.raises(RequestRejected):
        eng.submit(prompt, 1)
    assert eng.run()["a"].tokens == want

    eng = started()
    ticket = eng.export_session("a")
    assert eng._inflight is None and not eng.has_work()
    assert ticket.generated == want[:2] and ticket.n_cached == 6 + 1
    other = _engine(tiny_model)
    other.import_session(ticket)
    assert other.run()["a"].tokens == want

    eng = started()
    assert eng.evict("a") == (prompt, want[:2]) and not eng.has_work()


def test_results_are_complete_when_has_work_turns_false(tiny_model):
    requests = _requests(SCENARIOS["preemption"]["requests"],
                         tiny_model[0].vocab_size)
    eng = _engine(tiny_model, **SCENARIOS["preemption"]["engine"])
    for uid, prompt, new in requests:
        eng.submit(prompt, new, uid=uid)
    returned = []
    while eng.has_work():
        assert set(eng.results) != {u for u, _, _ in requests}
        returned.append(eng.step())
        assert returned[-1] > 0         # never 0 while work remains
    assert {u: len(r.tokens) for u, r in eng.results.items()} == {
        u: new for u, _, new in requests}
    assert all(r.status == "completed" and r.ttft_s is not None
               for r in eng.results.values())
    assert eng.allocator.num_allocated == 0 and eng.step() == 0


def test_many_steps_compile_once_and_the_tables_are_the_hosts(tiny_model):
    eng = _engine(tiny_model)
    for round_ in range(3):
        _serve(eng, [(f"{round_}-{u}", p, n) for u, p, n in _requests(
            SCENARIOS["mixed"]["requests"], tiny_model[0].vocab_size)])
    assert eng.stats.steps > 30 and eng.compile_count() == 1
    # what a reader of the cache finds there is what the host wrote for
    # the last step it enqueued, whether or not that step has been read
    eng.submit(_prompt(2, 9, tiny_model[0].vocab_size), 4, uid="t")
    eng.step()
    assert eng._inflight is not None
    np.testing.assert_array_equal(np.asarray(eng.cache.block_tables),
                                  eng._tables)
    assert np.asarray(eng.cache.lengths).tolist() == [8, 0, 0]


@pytest.mark.parametrize("quantized", [False, True])
def test_the_forward_returns_tables_and_lengths_as_it_got_them(
        tiny_model, quantized):
    """Why the engine may keep the arrays it uploaded in place of the
    step's outputs: the paged forward hands both back untouched."""
    cfg, params = tiny_model
    cache = paging.init_serving_cache(
        cfg, num_blocks=12, block_size=4, table_rows=3,
        max_blocks_per_seq=8, dtype=jnp.float32, quantized=quantized)
    table = np.full((3, 8), -1, np.int32)
    table[1, :2] = [7, 3]
    cache = cache.replace(block_tables=jnp.asarray(table),
                          lengths=jnp.asarray([0, 5, 0], jnp.int32))
    tok = np.zeros((1, 8), np.int32)
    pos = np.full((1, 8), PAD_POSITION, np.int32)
    slot = np.full((8,), 3, np.int32)
    tok[0, :5], pos[0, :5], slot[:5] = [3, 1, 4, 1, 5], np.arange(5), 1
    _, out = llama_forward_with_cache(
        cfg, params, jnp.asarray(tok), jnp.asarray(pos), cache,
        slot_ids=jnp.asarray(slot))
    np.testing.assert_array_equal(np.asarray(out.block_tables), table)
    assert np.asarray(out.lengths).tolist() == [0, 5, 0]
    assert not np.array_equal(np.asarray(out.pos), np.asarray(cache.pos))
