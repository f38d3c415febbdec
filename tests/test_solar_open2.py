"""Solar-Open2 (Kimi Delta Attention mixers with a gated NoPE GQA layer
every fourth, sigmoid-routed experts of which a share is held beside a
shared expert) through ``ops/kda.py``, the model, the paged forward over
the state-pool cache, the kernel in interpret mode and ``ServingEngine``,
against the benchmark's plain reference
``benchmarks/reference/solar_open2_f32.py``.

Tiny widths but the published KDA head (the kernel's lanes): hidden 64,
seven layers (GQA, KDA x 3, GQA, KDA x 2); 4 query heads of 16 over 2 K/V
heads; 2 KDA heads whose state is ``[128, 128]``, a convolution of 4
taps; 8 experts, top 3, of which the first 4 are held. The weights are
seeded, norm multipliers of order one, the decay's own parameters through
the family's mapping onto KDA's initialisation.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

import family_checks as fc
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import solar_open2 as so
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.ops import kda, ssd
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)

BS = 8
#: what a sound float32 run may read of the logits' deviation (it reads
#: 7e-6); each fault below reads the stated multiple of it
SOUND = 3e-5
#: every comparison against the reference runs over these positions, so
#: that its eager programs are compiled once
LENGTH = 37
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = dict(harness.read_json(os.path.join(
    BENCH, "tests", "configs", "tiny-solar-open2.json")),
    initializer_range=0.02)

_CASE = {}


def _family():
    return harness.load_plugin("families", "solar_open2")


def _reference():
    return harness.load_plugin("reference", "solar_open2_f32")


@fc.once_a_module
def _model(**kw):
    """The family's config from the published keys, its module and seeded
    weights: what ``make_weights`` would draw for the decay's leaves (the
    family reads them as KDA's), order one elsewhere."""
    cfg, model, _ = _family().build(
        PUBLISHED, **{"dtype": jnp.float32, "param_dtype": jnp.float32,
                      **kw})
    if "params" in _CASE:                  # the same draw for every config
        return cfg, model, _CASE["params"]
    init = meta.unbox(jax.eval_shape(model.init, jax.random.key(3),
                                     jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if name.endswith(("['A_log']", "['dt_bias']")):
            return 0.02 * noise
        # a router and a beta of order one; a convolution whose taps differ
        if not name.endswith("['scale']") and (
                "router" in name or "conv" in name):
            return 1.0 * noise

    _CASE["params"] = fc.seeded_weights(init, special)
    return cfg, model, _CASE["params"]


def _reference_logits(params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference().forward(
            _family().published(params, PUBLISHED), np.asarray(tokens),
            PUBLISHED)[0])


def _case():
    if "want" not in _CASE:
        _, _, params = _model()
        tokens = np.random.RandomState(2).randint(0, 256, (2, LENGTH))
        _CASE["tokens"], _CASE["want"] = tokens, _reference_logits(params,
                                                                   tokens)
    return _CASE["tokens"], _CASE["want"]


# -- (a) the two forms of the recurrence -------------------------------------

def _rows(seed, batch, length, heads=3, dk=8, dv=16):
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (batch, length, heads, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    g = -jnp.exp(jax.random.uniform(ks[3], (batch, length, heads, dk),
                                    minval=-7.0, maxval=2.0))
    return (unit(ks[0]), unit(ks[1]),
            jax.random.normal(ks[2], (batch, length, heads, dv)), g,
            2 * jax.nn.sigmoid(jax.random.normal(ks[4],
                                                 (batch, length, heads))))


@pytest.mark.parametrize("chunk", [8, 16, 37, 5, 64])
def test_kda_full_equals_the_token_by_token_recurrence(chunk):
    """37 positions: chunk boundaries inside the sequence, a length that
    is no multiple of the chunk, a chunk longer than the sequence; decays
    down to ``exp(-7.4)`` a position, so that a chunk of 37 decays a
    channel to nothing (and forms no infinity on the way)."""
    rows = _rows(1, 2, 37)
    want, got = kda.kda_scan(*rows), kda.kda_full(*rows, chunk=chunk)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_kda_full_is_differentiable_as_the_scan_is():
    rows = _rows(2, 1, 21)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3, 4))(*rows)

    for got, want in zip(grads(lambda *a: kda.kda_full(*a, chunk=8)),
                         grads(kda.kda_scan)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, atol=2e-5)


def _drive(steps, rows, impl, slots=6, width=12, state=None):
    """Sequences (``rows``: ``_rows`` of batch ``n``) through
    :func:`kda.kda_packed` by ``steps``, each a list of ``(sequence,
    slot, count)``: the sequence's next ``count`` positions as one
    segment of ``slot``. ``(outputs [n, S, H, dv], state)``; a step's pad
    rows must read zero and the slots without rows keep their state."""
    q, k, v, g, beta = (np.asarray(x) for x in rows)
    n, length, heads, dk = q.shape
    dv = v.shape[-1]
    if state is None:                       # garbage: position 0 clears it
        state = jnp.full((2, slots, heads, dk, dv), 7.0, jnp.float32)
    out = np.zeros((n, length, heads, dv), np.float32)
    done = [0] * n
    step = jax.jit(kda.kda_packed, static_argnames="force_pallas")
    for plan in steps:
        sid = np.full((width,), slots, np.int32)
        pos = np.full((width,), PAD_POSITION, np.int32)
        at = []
        for seq, slot, count in plan:
            for _ in range(count):
                sid[len(at)], pos[len(at)] = slot, done[seq]
                at.append((seq, done[seq]))
                done[seq] += 1

        def take(x):
            packed = np.zeros((width,) + x.shape[2:], np.float32)
            for i, (seq, p) in enumerate(at):
                packed[i] = x[seq, p]
            return jnp.asarray(packed)

        before = np.asarray(state)
        seg = ssd.step_segments(jnp.asarray(sid), jnp.asarray(pos), slots)
        o, state = step(take(q), take(k), take(v), take(g), take(beta),
                        state, jnp.int32(1), seg,
                        force_pallas=impl == "pallas-interpret")
        o, after = np.asarray(o), np.asarray(state)
        for i, (seq, p) in enumerate(at):
            out[seq, p] = o[i]
        assert (o[len(at):] == 0).all()
        touched = {slot for _, slot, _ in plan}
        for j in set(range(slots)) - touched:
            assert np.array_equal(before[:, j], after[:, j])
        assert np.array_equal(before[0], after[0])      # the other layer
    return out, state


def _staggered_steps(chunk):
    """Three sequences of 14 in slots 4, 1 and 3: the first prefills in
    chunks and then decodes a row a step, the second prefills beside its
    decode rows, the third beside both; steps of decode rows alone, of
    chunks alone and of both."""
    steps, left = [], [14, 14, 14]
    while max(left):
        plan = []
        for seq, slot in ((0, 4), (1, 1), (2, 3)):
            if seq and left[seq - 1] > 14 - chunk - 1:
                break                    # starts once the one before has
            n = min(chunk if left[seq] > 14 - 2 * chunk else 1, left[seq])
            if n:
                plan.append((seq, slot, n))
                left[seq] -= n
        steps.append(plan)
    return steps


def _beside_steps(chunk, length, width=16):
    """Twelve sequences, four steps of 16 rows: sequences 0 and then 1
    prefill ``chunk`` rows a step (a tail of fewer where the sequence
    ends), the ten others decode a row a step around the chunk, as many
    as the step has room for. The chunk starts at rows 3, 5, 1 and 6 (no
    multiple of the kernel's group of eight), so over the steps the
    decode rows stand at every ``t % 8``."""
    steps, left, turn = [], [length, length], 0
    for lead in (3, 5, 1, 6):
        seq = 0 if left[0] else 1
        n = min(chunk, left[seq])
        left[seq] -= n
        decoding = [2 + (turn + i) % 10 for i in range(min(10, width - n))]
        turn += len(decoding)
        steps.append([(d, d, 1) for d in decoding[:lead]] + [(seq, seq, n)]
                     + [(d, d, 1) for d in decoding[lead:]])
    return steps


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
@pytest.mark.parametrize(
    "chunk,heads,dk",
    [(c, 2, 8) for c in (1, 2, 3, 5)] + [(c, 16, 128) for c in range(1, 10)])
def test_the_packed_step_equals_kda_full_for_chunks_beside_decode_rows(
        impl, chunk, heads, dk):
    """Chunks beside decode rows with pad rows behind them. At two heads
    of 8 channels (the kernel a head a tile) three staggered sequences
    and a width of 12: the kernel pads the rows to its group of eight.
    At sixteen heads of 128 (two tiles of eight heads, the compiled
    form's shapes) every chunk length from 1 to 9 beside one-row
    segments: the one-row branch, the four-row body and every tail of
    ``rows % 4`` in one step."""
    if heads == 2:
        rows, geometry = _rows(3, 3, 14, heads, dk, 128), {}
        steps = _staggered_steps(chunk)
    else:
        # two chunks and a tail of sequence 0, then a chunk of sequence 1
        length = max(4, 2 * chunk + max(1, chunk // 2))
        rows = _rows(3, 12, length, heads, dk, 128)
        steps, geometry = _beside_steps(chunk, length), {"slots": 12,
                                                         "width": 16}
        first = [(t, seq) for plan in steps for t, (seq, _, _) in zip(
            np.cumsum([0] + [n for *_, n in plan]), plan)]
        assert all(t % 8 for t, seq in first if seq < 2)
        assert {t % 8 for t, seq in first if seq > 1} == set(range(8))
    want = np.asarray(kda.kda_full(*rows, chunk=4))
    got, _ = _drive(steps, rows, impl, **geometry)
    fed = np.zeros(len(want), int)          # a sequence's positions so far
    for plan in steps:
        for seq, _, n in plan:
            fed[seq] += n
    assert chunk == 1 or fed.all()
    done = np.arange(want.shape[1]) < fed[:, None]
    np.testing.assert_allclose(got[done], want[done], atol=1e-5)
    assert chunk == 1 or any(len(p) > 1 and {n for *_, n in p} != {1}
                             for p in steps)


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_a_slot_restarted_at_position_0_inherits_nothing(impl):
    rows = _rows(4, 2, 9, heads=2, dk=8, dv=128)
    want = np.asarray(kda.kda_full(*rows, chunk=4))
    # sequence 0 runs through slot 2; sequence 1 then takes the slot over
    got, state = _drive([[(0, 2, 5)], [(0, 2, 4)]], rows, impl)
    assert float(jnp.abs(state[1, 2]).max()) > 0
    again, _ = _drive([[(1, 2, 3)], [(1, 2, 6)]], rows, impl, state=state)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(again[1], want[1], atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_a_step_of_pad_rows_alone_writes_nothing(impl):
    rows = _rows(5, 1, 4, heads=2, dk=8, dv=128)
    state = jax.random.normal(jax.random.key(0), (2, 6, 2, 8, 128))
    _, after = _drive([[]], rows, impl, state=state)
    assert np.array_equal(np.asarray(state), np.asarray(after))


def test_kda_packed_impl_names_what_runs(monkeypatch):
    assert kda.kda_packed_impl(128, 128) == "xla"
    assert kda.kda_packed_impl(128, 128, True) == "pallas-interpret"
    assert kda.kda_packed_impl(128, 64, True) == "xla"
    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    assert kda.kda_packed_impl(128, 128) == "pallas"
    assert kda.kda_packed_impl(128, 128, False) == "xla"
    assert kda.kda_packed_impl(16, 16) == "xla"
    with pytest.raises(ValueError, match="does not tile"):
        kda.kda_packed_impl(16, 16, True)


# -- (b) the model ------------------------------------------------------------

def test_the_pattern_is_one_stack_a_kind_and_a_scan_a_run():
    cfg, _, params = _model()
    assert cfg.kinds() == ("full", "kda", "kda", "kda", "full", "kda",
                           "kda")
    assert cfg.runs() == (("full", 0, 1), ("kda", 0, 3), ("full", 1, 1),
                          ("kda", 3, 2))
    tree = params["params"]["model"]
    assert tree["layers_full"]["layer"]["attn"]["q_proj"]["kernel"].shape \
        == (2, 64, 64)
    attn = tree["layers_kda"]["layer"]["attn"]
    assert attn["qkv_proj"]["kernel"].shape == (5, 64, 3 * 256)
    assert attn["low_proj"]["kernel"].shape == (5, 64, 128 + 128 + 2)
    assert attn["conv_kernel"].shape == (5, 3 * 256, 4)
    assert attn["A_log"].shape == (5, 2) and attn["dt_bias"].shape == (5,
                                                                       256)
    moe = tree["layers_kda"]["layer"]["moe"]
    assert moe["router"]["kernel"].shape == (5, 64, 8)      # all 8 scored
    assert moe["experts"]["down"].shape == (5, 4, 32, 64)   # 4 held
    assert moe["shared"]["down"]["kernel"].shape == (5, 32, 64)
    assert so.SolarOpen2Config().kinds().count("full") == 12
    assert so.SolarOpen2Config().runs()[:2] == (("full", 0, 1),
                                                ("kda", 0, 3))
    with pytest.raises(ValueError, match="gqa_layers"):
        so.tiny_config(gqa_layers=(4, 0))
    with pytest.raises(ValueError, match="experts_held"):
        so.tiny_config(experts_held=(6, 4))


def test_every_published_key_is_read_or_refused():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Solar-Open2-250B"]
        assert set(row["config"]) == so.PUBLISHED_KEYS
        cfg = so.SolarOpen2Config.from_published(row["config"])
        assert cfg == so.SolarOpen2Config()
    assert so.PUBLISHED_KEYS <= set(PUBLISHED)
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False),
                       ("first_k_dense_replace", 1),
                       ("n_shared_experts", 2), ("norm_topk_prob", False),
                       ("tie_word_embeddings", True),
                       ("model_type", "solar_open")):
        with pytest.raises(ValueError, match=key):
            _family().build(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError, match="num_kv_heads"):
        _family().build(dict(PUBLISHED, linear_attn_config=dict(
            PUBLISHED["linear_attn_config"], num_kv_heads=1)))


def test_full_forward_matches_the_reference():
    cfg, model, params = _model()
    tokens, want = _case()
    assert np.std(want) > 0.05
    served = _family().with_kda_init(params, 0.02)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(served, jnp.asarray(tokens)))
    assert fc.worst(got, want) < SOUND


@pytest.mark.parametrize("impl,length", [("xla", LENGTH),
                                         ("pallas-interpret", 19)])
def test_paged_prefill_then_decode_matches_the_reference(impl, length):
    """State and tails carried across every step boundary: chunks of 3 to
    8 rows, then a decode row beside the second sequence's unaligned
    chunks, then both decoding among pad rows."""
    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    seqs, want = _case()
    steps = fc.schedule(length, [3, 8, 2, 1, 5], BS)
    got, cache = fc.paged_logits(cfg, params, seqs, steps, BS)
    assert len(got) == 2 * length
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=SOUND * np.std(want), err_msg=(s, p))
    assert cache.k.shape == (2, 24, BS, 2, 16) == cache.v.shape
    assert cache.states["kda"].shape == (5, 3, 2, 128, 128)
    assert cache.states["kda"].dtype == jnp.float32
    assert cache.states["conv"].shape == (5, 3, 3, 768)  # [L, W-1, J, C]
    # [kept, dropped, elsewhere] of the last step's rows, 7 layers x top 3
    counts = np.asarray(cache.moe_counts)
    assert counts.sum() == len(steps[-1]) * 7 * 3
    assert counts[1] == 0 < counts[2]


FAULTS = {
    # what each reads of the logits' deviation is over this many times
    # what a sound run may (they read 2.5 to 4.4; the state 0.0176)
    "a state left stale for a step": 1000,
    "the decay left out": 1000,
    "beta not doubled": 1000,
    "a dropped tail": 1000,
    "the output gate left out": 1000,
    "the shared expert left out": 1000,
    "a bfloat16 state": 100,
}


@pytest.mark.parametrize("fault", FAULTS)
def test_what_the_comparison_must_not_pass(fault, monkeypatch):
    """The sound paged run reads under ``SOUND``; the same run with one
    fault put in reads over its stated multiple of it."""
    cfg, _, params = _model()
    seqs, want = _case()
    steps = fc.schedule(30, [4, 5, 3, 4, 4], BS)[:12]
    packed, conv = kda.kda_packed, ssd.causal_conv_step

    def stale(q, k, v, g, beta, state, layer, seg, **kw):
        o, new = packed(q, k, v, g, beta, state, layer, seg, **kw)
        # the one step whose chunk has five rows keeps the state it found
        return o, jnp.where(seg.rows[0] == 5, state, new)

    patches = {
        "a state left stale for a step": (kda, "kda_packed", stale),
        "the decay left out": (kda, "kda_packed", lambda q, k, v, g, *a,
                               **kw: packed(q, k, v, 0 * g, *a, **kw)),
        "beta not doubled": (kda, "kda_packed", lambda q, k, v, g, beta, *a,
                             **kw: packed(q, k, v, g, beta / 2, *a, **kw)),
        "a dropped tail": (ssd, "causal_conv_step", lambda x, tails, *a:
                           (conv(x, tails, *a)[0], tails)),
        "the output gate left out": (so, "_output_gate",
                                     lambda x: jnp.ones(x.shape,
                                                        jnp.float32)),
        "a bfloat16 state": (kda, "kda_packed", lambda *a, **kw: (
            lambda o, s: (o, s.astype(jnp.bfloat16).astype(jnp.float32)))(
            *packed(*a, **kw))),
    }

    def worst(cfg, **kw):
        got, _ = fc.paged_logits(cfg, params, seqs, steps, BS, **kw)
        return fc.worst_at(got, want)

    assert worst(cfg) < SOUND
    if fault == "the shared expert left out":
        cfg = dataclasses.replace(cfg, shared_expert_intermediate_size=0)
    else:
        monkeypatch.setattr(*patches[fault])
    read = worst(cfg, fresh=True)
    print(fault, "reads", read)
    assert read > FAULTS[fault] * SOUND


def test_the_family_reads_normal_draws_as_kdas_initialisation():
    ps.initialize_model_parallel()
    family = _family()
    cfg, model, forward = family.build(PUBLISHED, dtype=jnp.float32,
                                       param_dtype=jnp.float32)
    assert isinstance(cfg, so.SolarOpen2Config)
    assert cfg.serving_family().forward is forward
    assert cfg.kind_config("kda").serving_family().forward is forward
    assert (cfg.num_experts, cfg.experts_held) == (8, (0, 4))
    shapes = meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = harness.make_weights(shapes, 2 ** 31 + 7, 0.02)
    weights = family.published(params, PUBLISHED)
    a = np.exp(np.asarray(weights("A_log", 1)))
    dt = np.log1p(np.exp(np.asarray(weights("dt_bias", 6))))
    assert ((a >= 1) & (a <= 16)).all() and np.ptp(a) > 0.5
    assert ((dt >= 1e-3 * .99) & (dt <= 1e-1 * 1.01)).all()
    assert np.ptp(np.log(dt)) > 3
    # every tensor under its published name, in its orientation
    for name, layer, shape in (
            ("q_proj", 1, (256, 64)), ("k_proj", 2, (256, 64)),
            ("v_proj", 3, (256, 64)), ("q_conv", 1, (256, 1, 4)),
            ("v_conv", 5, (256, 1, 4)), ("f_a_proj", 1, (128, 64)),
            ("f_b_proj", 1, (256, 128)), ("g_a_proj", 6, (128, 64)),
            ("g_b_proj", 6, (256, 128)), ("g_b_bias", 6, (256,)),
            ("b_proj", 2, (2, 64)), ("o_norm", 2, (128,)),
            ("o_proj", 2, (64, 256)), ("q_proj", 0, (64, 64)),
            ("k_proj", 4, (32, 64)), ("g_proj", 4, (64, 64)),
            ("o_proj", 0, (64, 64)), ("router", 3, (8, 64)),
            ("router_bias", 3, (8,)), ("shared_gate", 0, (32, 64)),
            ("shared_down", 5, (64, 32))):
        assert weights(name, layer).shape == shape, (name, layer)
    assert weights("up", 2, 3).shape == (32, 64)
    assert weights("lm_head").shape == (256, 64)
    with pytest.raises(KeyError, match="held elsewhere"):
        weights("gate", 2, 4)
    # the fused leaves come apart where they were put together
    attn = params["params"]["model"]["layers_kda"]["layer"]["attn"]
    np.testing.assert_array_equal(
        weights("k_proj", 5), np.asarray(attn["qkv_proj"]["kernel"][3],
                                         np.float32)[:, 256:512].T)
    np.testing.assert_array_equal(
        weights("b_proj", 1), np.asarray(attn["low_proj"]["kernel"][0],
                                         np.float32)[:, 256:].T)
    # the seeded forward and the reference read the same parameters
    seqs = np.random.RandomState(4).randint(0, 256, (2, 21))
    got, _ = fc.paged_logits(cfg, params, seqs,
                             fc.schedule(21, [8, 5], BS)[:9], BS)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference().forward(weights, seqs, PUBLISHED)[0])
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=5e-4 * np.std(want))


# -- (c) the shares add up to the uncut layer --------------------------------

def test_sixteen_shares_routed_sums_and_one_shared_expert_are_the_layer():
    """A layer's feed-forward as sixteen devices of twenty experts hold
    it: the shares' routed sums, added, plus the shared expert counted
    once (every share computes it whole) equal the plain reference's
    uncut layer over all 320 under the sigmoid router; ``elsewhere``
    counts what the other shares keep."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=320, hidden_size=64, intermediate_size=32,
                   top_k=8, capacity_factor=None, router_type="sigmoid",
                   shared_expert_intermediate=32, held=held,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    whole = meta.unbox(moe((0, 320)).init(jax.random.key(1), jnp.asarray(x),
                                          valid=jnp.asarray(valid)))
    tree = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)["params"]
    leaves = harness.load_plugin("families", "llama")._leaves

    def weights(name, layer=None, expert=None):
        if name == "router":
            return np.asarray(tree["router"]["kernel"]).T
        if name == "router_bias":
            return np.asarray(tree["router"]["bias"])
        if name.startswith("shared_"):
            node, name = tree["shared"], name.removeprefix("shared_")
        else:
            node = {k: v[expert] for k, v in tree["experts"].items()}
        (w,) = [v for k, v in leaves(node).items()
                if name in k.split("/")[0].split("_")]
        return np.asarray(w).T

    ref = _reference()
    config = dict(n_routed_experts=320, num_experts_per_tok=8,
                  norm_topk_prob=True, routed_scaling_factor=1,
                  n_shared_experts=1)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.feed_forward(jnp.asarray(x), weights, 0,
                                            config)[0])
        shared = np.asarray(ref.swiglu(
            jnp.asarray(x), weights("shared_gate"), weights("shared_up"),
            weights("shared_down")))
    assert np.abs(shared)[valid].max() > 0.05

    apply = jax.jit(moe((0, 20)).apply)

    def of(first):
        # one program for the sixteen (``held`` is static): the router's
        # columns are rolled so that expert ``first`` scores as expert 0
        rolled = dict(tree, router={
            "kernel": jnp.roll(tree["router"]["kernel"], -first, axis=1),
            "bias": jnp.roll(tree["router"]["bias"], -first)})
        p = {"params": dict(rolled, experts={
            k: w[first:first + 20] for k, w in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = apply(p, jnp.asarray(x), valid=jnp.asarray(valid))
        return np.asarray(y), np.asarray(aux["assignments"])

    shares = [of(first) for first in range(0, 320, 20)]
    routed = sum(y - shared for y, _ in shares)
    np.testing.assert_allclose((routed + shared)[valid], uncut[valid],
                               atol=3e-5)
    assert sum(np.abs(y - shared)[valid].max() > 0.02
               for y, _ in shares) >= 8         # the shares matter
    # [kept, dropped, elsewhere] of 13 real rows x top 8
    kept = [int(c[0]) for _, c in shares]
    assert sum(kept) == 13 * 8 and all(c[1] == 0 for _, c in shares)
    assert [int(c[2]) for _, c in shares] == [13 * 8 - n for n in kept]
    # the package's own ``held=(first, 20)`` is the same share, and the
    # reference's share the same sum
    p = {"params": dict(tree, experts={
        k: w[40:60] for k, w in tree["experts"].items()})}
    with jax.default_matmul_precision("highest"):
        y, aux = moe((40, 20)).apply(p, jnp.asarray(x),
                                     valid=jnp.asarray(valid))
        third = np.asarray(ref.feed_forward(
            jnp.asarray(x), weights, 0,
            dict(config, n_routed_experts=20, share={"first_expert": 40}))[0])
    np.testing.assert_allclose(np.asarray(y)[valid], shares[2][0][valid],
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(y)[valid], third[valid], atol=3e-5)
    assert np.array_equal(np.asarray(aux["assignments"]), shares[2][1])


# -- (d) through ServingEngine -------------------------------------------------

#: over ``family_checks.engine_config``: blocks and steps of 8 rows
ENGINE = dict(block_size=BS, num_blocks=24, token_budget=BS)


@pytest.fixture(scope="module")
def served():
    """Three requests through one engine of two slots whose pool holds
    nine blocks: the youngest is preempted on the way."""
    cfg, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_state_bytes_held_total", "nxd_state_slot_steps_total",
        "nxd_moe_held_total", "nxd_moe_assignments_total"),
        lengths=[30, 22, 5], new=[20, 10, 4], **dict(ENGINE, num_blocks=9,
                                                     max_slots=2))


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_decodes_as_a_fresh_one(served):
    """Nine blocks do not hold a and b: b is preempted and re-admitted
    into a slot whose state and tail another request left, and still
    decodes what the reference does (above)."""
    eng = served.eng
    assert eng.stats.preempted >= 1
    assert eng.allocator.num_allocated == 0
    assert eng.compile_count() == 1
    assert float(jnp.abs(eng.cache.states["kda"]).max()) > 0


def test_the_held_bytes_and_the_routed_assignments_are_counted(served):
    counters = served.counters
    held = counters["nxd_state_bytes_held_total"]
    slots = sum(counters["nxd_state_slot_steps_total"].values())
    # an occupied slot a step: five layers' float32 states of [2, 128,
    # 128] and their tails of 3 x 768 float32 values
    assert held["state"] == slots * 5 * 2 * 128 * 128 * 4
    assert held["tail"] == slots * 5 * 3 * 768 * 4
    # a mapped block a step: two layers' K and V of 8 x 2 x 16 values
    assert held["kv"] > 0 and held["kv"] % (2 * 2 * BS * 2 * 16 * 4) == 0
    moe = counters["nxd_moe_held_total"]
    assert moe["held"] > 0 and moe["elsewhere"] > 0
    assert counters["nxd_moe_assignments_total"]["dropped"] == 0
    assert (counters["nxd_moe_assignments_total"]["kept"] == moe["held"])


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES)
def test_refused_features_raise_by_name(feature, kw):
    cfg, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, **ENGINE)


def test_session_export_is_refused_and_the_cache_is_the_kinds():
    cfg, _, params = _model()
    eng = fc.check_session_export_is_refused(
        cfg, params, paging.StatePoolPagedCache, paging.StatePoolCache,
        **ENGINE)
    cache, family = eng.cache, cfg.serving_family()
    kind = family.cache_kind
    assert kind.pack == 1
    assert [(leaf.name, leaf.counted_as) for leaf in kind.leaves] == [
        ("kda", "state"), ("conv", "tail")]
    assert family.moe_counts and cache.moe_counts.shape == (3,)
    assert family.device_counts() == (paging.MOE_KEPT_DROPPED_ELSEWHERE,)
    assert set(family.unsupported) == {"prefix_sharing", "session_export",
                                       "speculation", "cp", "quantized"}
