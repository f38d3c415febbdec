"""What every family's serving tests share, whatever the family: the
seeded draw of a tiny model's weights, a model kept for the module's life,
the driver that pushes seeded sequences through a family's paged forward
by steps of rows, three requests through one engine with the youngest
preempted, and the bodies of the tests that read the same in every
``tests/test_<family>.py``. A module of functions, called directly, as
``walk_checks.py`` is: a family's file keeps its published keys, its
draw's rule, its shapes' assertions and the tests that are its own."""

import collections
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from counter_checks import check_registered_counters
from neuronx_distributed_tpu import obs
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.inference.speculative import SpeculationConfig
from neuronx_distributed_tpu.parallel import mesh as ps

#: what a module keeps until its teardown (``forget``): the models of
#: :func:`once_a_module` and the jitted paged steps of :func:`paged_logits`
_KEPT = {}

#: the features no family of a state, a ring or a latent row serves, as
#: ``EngineConfig`` switches them on (``[1:]`` where prefixes are shared)
REFUSED_FEATURES = [
    ("prefix_sharing", dict(prefix_sharing=True)),
    ("speculation", dict(speculation=SpeculationConfig())),
    ("cp", dict(cp=2)),
    ("quantized", dict(quantized=True)),
]


def forget():
    """Drop what the module kept (``conftest.py``, at a module's teardown,
    beside ``jax.clear_caches()``)."""
    _KEPT.clear()


def once_a_module(build):
    """``build(*a, **kw)`` run once for each set of arguments and kept for
    the module's life: a file's ``_model``. The mesh is the caller's every
    time, since the autouse fixture destroys it after each test."""
    @functools.wraps(build)
    def kept(*a, **kw):
        ps.initialize_model_parallel()
        key = (build.__module__, build.__qualname__,
               repr((a, sorted(kw.items()))))
        if key not in _KEPT:
            _KEPT[key] = build(*a, **kw)
        return _KEPT[key]

    return kept


def seeded_weights(init, special=lambda name, noise, x, key: None):
    """``init``'s leaves drawn anew, each from a key of its own path's
    name: a norm's multiplier of order one (at the chip's initialisation a
    wrong norm would not show), 0.08 elsewhere, and what ``special(name,
    noise, x, key)`` returns where that is not ``None``."""
    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.key(5),
                                 sum(map(ord, name)) % 2 ** 31)
        noise = jax.random.normal(key, x.shape, x.dtype)
        own = special(name, noise, x, key)
        if own is not None:
            return own
        if name.endswith("['scale']"):
            return 1.0 + 0.3 * noise
        return 0.08 * noise

    return jax.tree_util.tree_map_with_path(draw, init)


def worst(got, want):
    """The largest deviation over the spread of what was wanted."""
    return float(np.abs(got - want).max() / np.std(want))


def worst_at(got, want):
    """:func:`worst` over :func:`paged_logits`' rows, ``want [n, S, V]``."""
    return float(max(np.abs(v - want[s, p]).max()
                     for (s, p), v in got.items()) / np.std(want))


def engine_config(**kw):
    """The families' ``EngineConfig``: float32 blocks and steps of 16 rows,
    three slots over 40 blocks, ``kw`` over them."""
    base = dict(block_size=16, num_blocks=40, max_slots=3,
                max_blocks_per_seq=12, token_budget=16,
                kv_dtype=jnp.float32)
    base.update(kw)
    return EngineConfig(**base)


def greedy_by_reference(reference_logits, params, prompt, tokens):
    """The reference's greedy next token after each prefix of ``prompt +
    tokens`` that ends where the engine sampled (one full forward,
    causal: equal lists mean the engine's greedy continuation is the
    reference's). ``reference_logits(params, tokens [1, S]) -> [1, S, V]``."""
    logits = np.asarray(reference_logits(
        params, np.asarray([list(prompt) + list(tokens)])))
    return np.argmax(logits[0, len(prompt) - 1:-1], -1).tolist()


# -- the paged forward, by steps of rows --------------------------------------

def schedule(length, chunks, width):
    """Sequence 0 prefills in ``chunks`` and then decodes a row a step to
    ``length``; sequence 1 prefills beside its decode rows, in chunks of
    what a step of ``width`` rows has left, unaligned to the blocks."""
    steps, done = [], [0, 0]
    for n in chunks:
        steps.append([(0, done[0] + i) for i in range(n)])
        done[0] += n
    while min(done) < length:
        rows = [(0, done[0])] if done[0] < length else []
        done[0] += len(rows)
        n = min(width - len(rows) - len(steps) % 2, length - done[1])
        rows += [(1, done[1] + i) for i in range(n)]
        done[1] += n
        steps.append(rows)
    return steps


def chunked(chunks):
    """Sequence 0's steps alone, ``chunks`` rows each from position 0 on."""
    starts = np.cumsum([0] + list(chunks))
    return [[(0, int(p)) for p in range(a, b)]
            for a, b in zip(starts, starts[1:])]


def paged_logits(cfg, params, seqs, steps, width, cache=None, slots=None,
                 fresh=False, block_size=None, **cache_kw):
    """Sequences ``seqs [n, S]`` through the family's paged forward by
    ``steps``, each a list of rows ``(sequence, position)`` padded to
    ``width`` (sequence ``s`` in slot ``slots[s]``, slot ``s`` by
    default); a row's block is mapped in order as the engine maps them,
    where ``cache``'s tables have not mapped it. ``cache`` is the family's
    serving cache of blocks of ``block_size`` (``width`` unless given;
    ``cache_kw`` over 24 blocks and 3 rows of 8 columns) unless given.
    The jitted step is kept for the module on ``(cfg, width)``; ``fresh``
    traces it anew, as a run under a patched function must. ``({(s, p):
    logits}, cache)``."""
    block = block_size or width
    if cache is None:
        cache = paging.init_serving_cache(cfg, dtype=jnp.float32, **{
            **dict(num_blocks=24, block_size=block, table_rows=3,
                   max_blocks_per_seq=8), **cache_kw})
    forward = cfg.serving_family().forward
    step = jax.jit(lambda p, c, t, pos, s: forward(cfg, p, t, pos, c,
                                                   slot_ids=s))
    if not fresh:
        step = _KEPT.setdefault(("paged step", cfg, width), step)
    table = np.array(cache.block_tables)
    mapped = int((table >= 0).sum())
    out = {}
    for rows in steps:
        tok = np.zeros((1, width), np.int32)
        pos = np.full((1, width), PAD_POSITION, np.int32)
        ids = np.full((width,), table.shape[0], np.int32)
        for i, (s, p) in enumerate(rows):
            slot = s if slots is None else slots[s]
            tok[0, i], pos[0, i], ids[i] = seqs[s][p], p, slot
            if table[slot, p // block] < 0:
                table[slot, p // block], mapped = mapped, mapped + 1
        cache = cache.replace(block_tables=jnp.asarray(table))
        with jax.default_matmul_precision("highest"):
            logits, cache = step(params, cache, *map(jnp.asarray,
                                                     (tok, pos, ids)))
        for i, row in enumerate(rows):
            out[row] = np.asarray(logits[0, i])
    return out, cache


# -- through ServingEngine ---------------------------------------------------

#: what :func:`serve_three` returns: ``seen`` is ``watch(eng)`` after each
#: step, ``spans`` the names of the host's spans
Served = collections.namedtuple(
    "Served", "cfg params eng prompts new counters seen spans")


def serve_three(cfg, params, counters, lengths, new, vocab=256, watch=None,
                before=None, **engine_kw):
    """Requests ``a``, ``b``, ``c`` of ``lengths`` seeded prompt tokens and
    ``new`` generated ones through one engine (``engine_kw`` over
    :func:`engine_config`; a pool too small for ``a`` and ``b`` preempts
    the younger on the way), with the registry's ``counters`` read back by
    kind and held to what the family declares."""
    eng = ServingEngine(cfg, params, engine_config(**engine_kw))
    rng = np.random.RandomState(11)
    prompts = {uid: rng.randint(0, vocab, (n,)).tolist()
               for uid, n in zip("abc", lengths)}
    new = dict(zip(prompts, new))
    obs.enable()
    obs.get_registry().reset()
    for uid, prompt in prompts.items():
        eng.submit(prompt, new[uid], uid=uid)
    if before is not None:
        before(eng)
    seen = []
    while eng.has_work():
        eng.step()
        if watch is not None:
            seen.append(watch(eng))
    read = {name: {c.labels.get("kind", ""): c.value
                   for c in obs.get_registry().get(name).children()}
            for name in counters}
    spans = {e["name"]
             for e in obs.get_tracer().chrome_trace()["traceEvents"]}
    check_registered_counters(obs.get_registry(), cfg.serving_family())
    obs.disable()
    ps.destroy_model_parallel()
    return Served(cfg, params, eng, prompts, new, read, seen, spans)


def check_engine_greedy_tokens_equal_the_reference(served, reference_logits):
    for uid, prompt in served.prompts.items():
        assert served.eng.results[uid].status == "completed"
        tokens = served.eng.results[uid].tokens
        assert len(tokens) == served.new[uid]
        assert tokens == greedy_by_reference(
            reference_logits, served.params, prompt, tokens), uid


def check_preempted_and_whole(eng):
    """The pool did not hold ``a`` and ``b``: one was preempted, and at the
    end every block is back and the step compiled once."""
    assert eng.stats.preempted >= 1
    assert eng.allocator.num_allocated == 0
    assert (eng._tables == -1).all()
    assert eng.compile_count() == 1


def check_refused_features(cfg, params, features, reason=False,
                           **engine_kw):
    """Each of ``features`` (name: the ``EngineConfig`` keys that switch it
    on) is refused by name when the engine is built, before anything is
    traced; with ``reason``, beside what the family says of it."""
    for feature, kw in features.items():
        with pytest.raises(ValueError, match=feature) as e:
            ServingEngine(cfg, params, engine_config(**engine_kw, **kw))
        if reason:
            said = cfg.serving_family().unsupported[feature]
            assert said[:30] in str(e.value)


def check_session_export_is_refused(cfg, params, cache_type, kind_type=None,
                                    **engine_kw):
    """A live request's session is refused by name; the engine, whose
    cache is ``cache_type`` of the family's ``kind_type``, for the file's
    own assertions on its shapes."""
    eng = ServingEngine(cfg, params, engine_config(**engine_kw))
    uid = eng.submit([1, 2, 3], 4)
    eng.step()
    with pytest.raises(ValueError, match="session_export"):
        eng.export_session(uid)
    assert isinstance(eng.cache, cache_type)
    if kind_type is not None:
        assert isinstance(cfg.serving_family().cache_kind, kind_type)
    return eng
