"""The packed step runs one deep in flight: ``step()`` enqueues step n+1
before it reads step n, a decode row whose token is still on the device
takes it there, and what the host knows of a step at its enqueue is kept
apart from what needs its values. Every request's tokens are those of the
engine's serial modes and of a plain greedy loop over the model."""

import dataclasses
import functools
import gc
import threading
import time

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
from neuronx_distributed_tpu.obs.tracing import STOPPED_SPAN, WITNESS_THREAD
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


# ---------------------------------------------------------------------------
# a stalled step says what it waited for (obs on)
# ---------------------------------------------------------------------------

PAUSE_S = 0.12


class _SlowToBeReady:
    """What a step returned, on a device that takes ``PAUSE_S`` longer."""

    def __init__(self, array, how=""):
        self.array, self.how = array, how

    def block_until_ready(self):
        _pause(self.how, PAUSE_S)
        self.array.block_until_ready()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.array)


def _pause(how, seconds):
    """Lose ``seconds`` where this is called: asleep (``how`` ""), asleep
    while the witness finds the process ``stopped`` (its late wake-up is
    put where the witness puts it), awake and ``busy`` with the
    interpreter's lock held while the witness starves on it (late as well),
    or asleep with the thread on the ``runq`` all the while."""
    tracer = obs.get_tracer()
    t0 = time.perf_counter_ns()
    if how == "busy":
        while time.perf_counter_ns() - t0 < seconds * 1e9:
            pass
    else:
        time.sleep(seconds)
    if how in ("stopped", "busy"):
        tracer._pending.append((
            STOPPED_SPAN, t0, time.perf_counter_ns(), tracer._witness[0].ident,
            {"throttled": 2.0, "pressure_us": seconds * 1e6}))
    elif how == "runq":
        _pause.runq_ns += int(seconds * 1e9)


_pause.runq_ns = 0


def _pause_in(eng, where, monkeypatch):
    """Arrange for the next ``step()`` of ``eng`` to lose ``PAUSE_S`` in
    ``where`` (``<how>_<where>``: the manner of :func:`_pause`); returns
    the thunk that takes the pause out again."""
    how, _, where = where.rpartition("_")
    if how == "runq":
        reading = obs.get_tracer().thread_reading
        monkeypatch.setattr(obs.get_tracer(), "thread_reading", lambda: (
            lambda r: r[:5] + ((r[5] or 0) + _pause.runq_ns,))(reading()))
    if where == "pack":                 # inside engine/packed/pack
        count = eng._count_step
        monkeypatch.setattr(eng, "_count_step", lambda *a: (
            _pause(how, PAUSE_S), count(*a))[1])
    elif where == "gc":                 # a collection, inside the same span
        count = eng._count_step
        # a generation-2 pass over some hundred thousand containers
        held = [[i] for i in range(400_000)]
        monkeypatch.setattr(eng, "_count_step", lambda *a: (
            gc.collect(), count(*a))[1])
        return lambda: (monkeypatch.undo(), held.clear())
    elif where == "ready":              # the fetch's wait for the device
        fetch = eng._fetch

        def slow_fetch(flight, span, **attrs):
            flight.sampled = _SlowToBeReady(flight.sampled, how)
            return fetch(flight, span, **attrs)

        monkeypatch.setattr(eng, "_fetch", slow_fetch)
    elif where == "tables":             # an upload that does not leave
        put = jax.device_put
        monkeypatch.setattr(jax, "device_put", lambda *a, **kw: (
            _pause(how, PAUSE_S / 2), put(*a, **kw))[1])
    return monkeypatch.undo


def _wall_children():
    return {c.labels["where"]: c.value for c in obs.get_registry().get(
        "nxd_engine_step_wall_seconds_total").children()}


def _cause_children():
    return {c.labels["cause"]: c.value for c in obs.get_registry().get(
        "nxd_engine_stall_cause_seconds_total").children()}


@pytest.mark.parametrize("where,cause,why", [
    # the witness on time: a wait is a wait, wherever it was
    ("pack", "host", "other"), ("ready", "device", "other"),
    ("tables", "transfer", "other"), ("gc", "host_pause", "other"),
    # the process stopped: the same places, another cause
    ("stopped_ready", "device", "process_stopped"),
    ("stopped_tables", "transfer", "process_stopped"),
    ("stopped_pack", "host", "process_stopped"),
    # the witness late because the stepping thread held the lock: it ran
    ("busy_pack", "host", "other"),
    # runnable all the while, and no core
    ("runq_pack", "host", "cpu_wait")])
def test_a_slow_call_puts_its_excess_under_its_cause(tiny_model, where,
                                                     cause, why, monkeypatch):
    obs.enable()
    _pause.runq_ns = 0
    events = []
    unsubscribe = obs.subscribe(
        lambda name, fields: events.append((name, fields)))
    eng = _engine(tiny_model)
    for i in range(3):
        eng.submit(_prompt(i, 5, tiny_model[0].vocab_size), 24, uid=f"r{i}")
    for _ in range(12):                 # the rule has its median at 8
        eng.step()
    before, why_before = _wall_children(), _cause_children()
    undo = _pause_in(eng, where, monkeypatch)
    eng.step()
    undo()
    slow_call = eng._calls
    after, why_after = _wall_children(), _cause_children()
    while eng.has_work():
        eng.step()
    unsubscribe()

    gained = {k: after[k] - before.get(k, 0.0) for k in after}
    why_gained = {k: why_after[k] - why_before.get(k, 0.0)
                  for k in why_after}
    median_s = eng._stall.median * 1e-6
    # the median to steady, the rest by cause: nearly all under this one
    assert gained["steady"] == pytest.approx(median_s, rel=0.5)
    excess = sum(gained.values()) - gained["steady"]
    assert excess > 0.8 * PAUSE_S / (2 if where == "gc" else 1) \
        or where == "gc" and excess > 10 * median_s
    assert gained[cause] > 0.8 * excess, gained
    # the same seconds by cause: nine tenths of them under this one
    assert why_gained["steady"] == pytest.approx(gained["steady"], abs=1e-9)
    assert sum(why_gained.values()) - why_gained["steady"] == pytest.approx(
        excess, abs=1e-9)
    # (of the pause that was injected: a machine that other tests load
    # adds waits of its own to the call, keeps a runnable thread off its
    # core, which is cpu_wait by right, the witness too, whose stop is
    # then real and says so, and a thread that would burn CPU off it for
    # part of the time, so that one is held to half)
    [fields] = [f for name, f in events
                if name == "slow_step" and f["step"] == slow_call]
    real = [stop for stop in fields["stops"] if stop.get("throttled") != 2.0]
    mine = why_gained[why]
    if why == "other":
        mine += why_gained["cpu_wait"] + (
            why_gained["process_stopped"] if real else 0)
    assert mine > min(excess, PAUSE_S) * (
        0.5 if where in ("gc", "busy_pack") else 0.9), why_gained
    # every call's wall is in each counter, once
    children, whys = _wall_children(), _cause_children()
    assert set(children) == {"steady", "host_pause", "device", "transfer",
                             "compile", "host"}
    assert set(whys) == {"steady", "process_stopped", "cpu_wait", "other"}
    for counted in (children, whys):
        assert sum(counted.values()) == pytest.approx(
            sum(eng._stall.walls) * 1e-6, abs=1e-6)
    assert sum(children.values()) - children["steady"] == pytest.approx(
        sum(whys.values()) - whys["steady"], abs=1e-6)
    assert eng._stall.calls == eng.stats.steps == len(eng._stall.walls)

    # one event for the slow call, with both calls' facts
    assert fields["wall_ms"] > 3 * fields["median_ms"] > 0
    assert sum(fields["split_ms"].values()) == pytest.approx(
        fields["wall_ms"] - fields["median_ms"], abs=0.01)
    assert max(fields["split_ms"], key=fields["split_ms"].get) == cause
    assert sum(fields["cause_ms"].values()) == pytest.approx(
        sum(fields["split_ms"].values()), abs=0.01)
    assert max(fields["cause_ms"], key=fields["cause_ms"].get) == why or real
    assert 0 <= fields["cpu_ms"] <= fields["wall_ms"]
    # (a collection of 100 ms holds the interpreter's lock: the witness is
    # late by right, and the thread's own CPU time says that it ran)
    late = where.startswith(("stopped", "busy"))
    ran = where.startswith("busy") or where == "gc"
    assert (fields["stopped_ms"] > 0.9 * PAUSE_S * 1e3) == late or ran \
        or real
    assert (fields["cpu_ms"] > 0.5 * fields["wall_ms"]) == ran
    assert fields["cause_ms"]["process_stopped"] <= max(
        0.0, fields["wall_ms"] - fields["cpu_ms"]) + 0.01
    injected = [stop for stop in fields["stops"] if stop not in real]
    assert [set(stop) for stop in injected] == [
        {"at_ms", "ms", "throttled", "pressure_us"}] * (
            (2 if where.endswith("tables") else 1) if late else 0)
    assert all(0 <= stop["at_ms"] <= fields["wall_ms"] for stop in injected)
    assert (fields["cpu_wait_ms"] or 0) >= (
        PAUSE_S * 1e3 if where.startswith("runq") else 0)
    assert fields["core"] is None or fields["core"] >= 0
    assert {"switches_voluntary", "switches_involuntary", "faults_minor",
            "faults_major"} <= set(fields)
    assert fields["switches_voluntary"] >= (0 if where in (
        "gc", "busy_pack") else 1)      # a sleep gives the core up
    # (a young collection may fall into any call; the forced one is old)
    assert (fields["gc_generation"] == 2) == (where == "gc")
    assert (fields["gc_ms"] > 0.5 * fields["wall_ms"]) == (where == "gc")
    assert fields["ready_ms"] > 0 and fields["copy_ms"] >= 0
    assert {"engine/packed/pack", "engine/packed/fetch", "engine/tables",
            "engine/admission"} <= set(fields["spans_ms"])
    this, prev = fields["call"], fields["call_before"]
    assert this["decode_rows"] == 3 and this["kind"] == "overlapped"
    assert prev["step"] == slow_call - 1 and prev["decode_rows"] == 3
    for facts in (this, prev):
        assert {"decode_rows", "prefill_rows", "pad_rows", "kind",
                "admitted", "retired", "preempted", "cleared", "cow_copies",
                "rolled", "compiled", "cpu_us"} <= set(facts)
    assert "engine/packed/dispatch" in prev["spans_ms"]
    assert "memory" not in fields       # nothing read it (PR 69)
    # every call, slow or not, says what CPU time its thread took
    packed = [r for r in obs.get_tracer().step_records().values()
              if "kind" in r["attrs"]["engine/publish"]]
    assert len(packed) == eng.stats.steps and all(
        0 <= r["attrs"]["engine/publish"]["cpu_us"]
        <= r["return_us"] - r["entry_us"] + 1e3 for r in packed)
    counted = {c.labels["event"]: c.value for c in obs.get_registry().get(
        "nxd_events_total").children()}
    assert counted["slow_step"] == sum(
        1 for name, _ in events if name == "slow_step")
    # the tokens are those of an engine nobody paused
    assert all(len(r.tokens) == 24 for r in eng.results.values())


def test_a_call_in_which_a_worker_compiled_is_compile(tiny_model):
    obs.enable()
    events = []
    unsubscribe = obs.subscribe(
        lambda name, fields: events.append((name, fields)))
    eng = _engine(tiny_model)
    for i in range(3):
        eng.submit(_prompt(i, 5, tiny_model[0].vocab_size), 24, uid=f"r{i}")
    for _ in range(12):
        eng.step()
    before = _wall_children()
    eng._rng = jax.random.key(0, impl="rbg")    # a key of another type:
    eng.step()                          # the packed step compiles again
    unsubscribe()
    gained = {k: v - before.get(k, 0.0)
              for k, v in _wall_children().items()}
    assert gained["compile"] > 0
    assert all(gained[k] == 0 for k in ("host", "device", "transfer",
                                        "host_pause"))
    [fields] = [f for name, f in events if name == "slow_step"]
    assert fields["call"]["compiled"] and set(fields["split_ms"]) == {
        "compile"}


@pytest.mark.parametrize("where", ["events", "state", "stats_fields",
                                   "hook", "spans", "witness"])
def test_with_obs_off_a_paused_step_leaves_nothing(tiny_model, where,
                                                   monkeypatch):
    assert not obs.enabled()
    if where == "witness":              # nor does a step read its thread
        def read(*_):
            raise AssertionError("a reading of the thread with obs off")

        monkeypatch.setattr(time, "thread_time_ns", read)
        monkeypatch.setattr("resource.getrusage", read)
        monkeypatch.setattr(type(obs.get_tracer()), "thread_reading", read)
    events = []
    unsubscribe = obs.subscribe(
        lambda name, fields: events.append((name, fields)))
    eng = _engine(tiny_model)
    for i in range(3):
        eng.submit(_prompt(i, 5, tiny_model[0].vocab_size), 16, uid=f"r{i}")
    for _ in range(10):
        eng.step()
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda *a, **kw: (
        time.sleep(PAUSE_S / 2), put(*a, **kw))[1])
    eng.step()
    monkeypatch.undo()
    while eng.has_work():
        eng.step()
    unsubscribe()
    if where == "events":
        assert events == []
    elif where == "state":
        assert eng._stall is None and eng._obs_cache is None
        assert obs.get_registry().get(
            "nxd_engine_step_wall_seconds_total") is None
    elif where == "stats_fields":
        assert [f.name for f in dataclasses.fields(eng.stats)] == [
            "steps", "completed", "rejected", "preempted", "resubmitted",
            "queue_depth", "tokens_generated", "cow_copies",
            "prefix_hit_tokens", "prefill_tokens", "migrated_in",
            "migrated_out", "migrated_tokens", "integrity_rejects",
            "spec_rounds", "spec_accepted_tokens", "ttft_s",
            "step_latency_s", "occupancy", "shared_fraction",
            "first_step_t", "last_step_t"]
    elif where == "hook":
        assert not any(getattr(cb, "__self__", None) is obs.get_tracer()
                       for cb in gc.callbacks)
    elif where == "witness":
        assert WITNESS_THREAD not in [t.name for t in threading.enumerate()]
        assert obs.get_tracer()._meter is None and eng._stall is None
    else:
        assert obs.get_tracer().chrome_trace()["traceEvents"] == []


@pytest.mark.parametrize("engine_kw", [{}, {"prefix_sharing": True},
                                       {"disaggregated": True}],
                         ids=["packed", "prefix_sharing", "disaggregated"])
def test_a_calls_spans_cover_its_wall(tiny_model, engine_kw):
    """Every ``engine/*`` span of a call carries its number, the fetch
    says which wait it was, and what a call leaves outside any span is
    under 1% of its wall at a step of a real length (30 ms here; the
    spans' own bookkeeping is some tens of microseconds a call)."""
    obs.enable()
    eng = _engine(tiny_model, **engine_kw)
    for worker in ("_step_fn", "_prefill_fn", "_decode_fn"):
        fn = getattr(eng, worker)
        if fn is not None:
            setattr(eng, worker, lambda *a, fn=fn: (
                time.sleep(0.03), fn(*a))[1])
    requests = _requests(SCENARIOS["prefix_hit"]["requests"],
                         tiny_model[0].vocab_size)
    eng.step()                          # a call with nothing to do
    _serve(eng, requests)
    tracer = obs.get_tracer()
    spans = [ev for ev in tracer.chrome_trace()["traceEvents"]
             if ev["name"].startswith("engine/")]
    assert spans and all("step" in ev["args"] for ev in spans)
    # held flat: the collector does not see the events a run piles up
    assert not any(gc.is_tracked(ev) for ev in tracer._events
                   if ev["name"].startswith("engine/"))
    fetches = [ev for ev in spans if ev["name"].endswith("/fetch")]
    assert fetches and all(
        0 <= ev["args"]["ready_us"] and 0 <= ev["args"]["copy_us"]
        and ev["args"]["ready_us"] + ev["args"]["copy_us"] <= ev["dur"]
        for ev in fetches)
    records = tracer.step_records()
    packed = [r for r in records.values()
              if "kind" in r["attrs"].get("engine/publish", {})]
    assert len(packed) == eng.stats.steps and len(records) > len(packed)
    uncovered = [1.0 - sum(r["self_us"].values())
                 / (r["return_us"] - r["entry_us"]) for r in packed]
    assert float(np.median(uncovered)) < 0.01, sorted(uncovered)[-5:]
    names = set().union(*(r["self_us"] for r in packed))
    assert "engine/slices" in names
    assert ("engine/prefix_insert" in names) == bool(
        engine_kw.get("prefix_sharing"))
    # the numbers are the engine's own calls, in order, one a call
    assert sorted(records) == list(range(eng._calls - len(records) + 1,
                                         eng._calls + 1))
