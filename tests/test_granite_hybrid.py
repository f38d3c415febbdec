"""Granite 4.0-H (Mamba-2 layers beside NoPE attention layers) through the
scan's two forms, the convolution's tail, the model, the paged forward and
``ServingEngine``, against the benchmark's plain reference
``benchmarks/reference/granite_hybrid_f32.py`` (the token-by-token
recurrence).

Tiny widths: hidden 64, five layers in runs of 1, 1, 2, 1; four mamba
heads of 32 over a state of 16 (``d_inner`` 128: the ``ssd_state_update``
kernel's tiles exist, so it runs in interpret mode), a convolution of
width 4; four query heads of 16 over two K/V heads, two a pool row; pool
blocks of 16. The weights are seeded with norm multipliers of order one
and the scan's own parameters by the module's Mamba-2 initialisers.
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import granite_hybrid as gh
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.ops import ssd
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)

BS = 16
LAYERS = ["mamba", "attention", "mamba", "mamba", "attention"]
PUBLISHED = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    shared_intermediate_size=128, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, layer_types=LAYERS,
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    hidden_act="silu", normalization_function="rmsnorm",
    position_embedding_type="nope", num_local_experts=0,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=8, rms_norm_eps=1e-5,
    rope_theta=1e4, tie_word_embeddings=True, max_position_embeddings=4096,
    initializer_range=0.02, reduced={}, family="granite_hybrid",
    reference="granite_hybrid_f32")
H, P, N, W = 4, 32, 16, 4
HI = jax.lax.Precision.HIGHEST


@fc.once_a_module
def _model(**kw):
    """The package's own config (no seeded mapping: the tests' weights
    hold the scan's parameters as they are), its module and weights."""
    cfg = gh.GraniteHybridConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
        num_heads=4, num_kv_heads=2, max_seq_len=4096,
        layer_types=tuple(LAYERS), mamba_n_heads=H, mamba_d_head=P,
        mamba_d_state=N, mamba_d_conv=W, mamba_chunk_size=8,
        attention_multiplier=0.0625,
        **{"dtype": jnp.float32, "param_dtype": jnp.float32, **kw})
    model = gh.GraniteHybridForCausalLM(cfg)
    init = meta.unbox(model.init(jax.random.key(3),
                                 jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if not name.endswith("['scale']") and any(
                leaf in name for leaf in ("A_log", "dt_bias", "['D']",
                                          "conv_kernel")):
            return x                       # the module's Mamba-2 draws

    return cfg, model, fc.seeded_weights(init, special)


class _AsPublished:
    """The family's ``published`` without its seeded mapping: the tests'
    weights are the published parameters themselves."""

    def __init__(self, params):
        family = harness.load_plugin("families", "granite_hybrid")
        self.inner = family.Published.__new__(family.Published)
        self.inner.tree = params["params"]
        self.inner.hidden, self.inner.inter = 64, 128
        kinds = [family.KINDS[t] for t in LAYERS]
        self.inner.where = [
            (self.inner.tree["model"][f"layers_{k}"]["layer"],
             kinds[:i].count(k)) for i, k in enumerate(kinds)]

    def __call__(self, *a, **kw):
        return self.inner(*a, **kw)


def _reference_logits(params, tokens):
    ref = harness.load_plugin("reference", "granite_hybrid_f32")
    return np.asarray(ref.forward(_AsPublished(params), np.asarray(tokens),
                                  PUBLISHED)[0])


# -- (a) the scan's two forms -----------------------------------------------

def _recurrence(x, dt, a, b, c, d, state=None):
    """Token by token in NumPy float64: ``(y [S, H, P], state)``."""
    s = np.zeros((H, P, N)) if state is None else state.copy()
    ys = []
    for t in range(x.shape[0]):
        s = (np.exp(dt[t] * a)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        ys.append(s @ c[t] + d[:, None] * x[t])
    return np.stack(ys), s


def _scan_inputs(rng, length):
    return dict(x=rng.normal(size=(length, H, P)),
                dt=np.log1p(np.exp(rng.normal(size=(length, H)))),
                b=rng.normal(size=(length, N)),
                c=rng.normal(size=(length, N)))


@pytest.mark.parametrize("chunk", [8, 16, 37, 5, 256])
def test_ssd_full_equals_the_token_by_token_recurrence(chunk):
    """Chunks that divide the length (37 itself, 256 past it) and that do
    not (8, 16, 5)."""
    rng = np.random.default_rng(0)
    a, d = -np.exp(rng.normal(size=H)), rng.normal(size=H)
    v = _scan_inputs(rng, 37)
    want, _ = _recurrence(v["x"], v["dt"], a, v["b"], v["c"], d)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_full(
            *(jnp.asarray(v[k], jnp.float32)[None] for k in ("x", "dt")),
            jnp.asarray(a, jnp.float32),
            *(jnp.asarray(v[k], jnp.float32)[None] for k in ("b", "c")),
            jnp.asarray(d, jnp.float32), chunk=chunk)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=5e-5)


def test_ssd_full_is_differentiable():
    rng = np.random.default_rng(1)
    v = {k: jnp.asarray(x, jnp.float32)[None]
         for k, x in _scan_inputs(rng, 12).items()}
    a = -jnp.ones((H,))

    def loss(x):
        return jnp.sum(ssd.ssd_full(x, v["dt"], a, v["b"], v["c"],
                                    jnp.ones((H,)), chunk=4) ** 2)

    grad = jax.grad(loss)(v["x"])
    assert np.isfinite(np.asarray(grad)).all() and float(
        jnp.abs(grad).max()) > 0


def _drive(schedule, seqs, impl, slots=4, layers=2, layer=1, stale=None):
    """Packed steps of the convolution and the scan at ``layer`` of
    ``layers``: ``schedule`` is a list of steps, each a list of ``(slot,
    sequence, first position, rows)`` and ``None`` for a pad row; returns
    ``{sequence: (conv out [S, C], y [S, H, P])}`` and the leaves."""
    rng = np.random.default_rng(7)
    chans = H * P + 2 * N
    weight = jnp.asarray(rng.uniform(-.5, .5, (chans, W)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=chans) * .1, jnp.float32)
    a = jnp.asarray(-np.exp(rng.normal(size=H)), jnp.float32)
    d = jnp.asarray(rng.normal(size=H), jnp.float32)
    ssm = jnp.asarray(rng.normal(size=(layers, slots, N, H * P)),
                      jnp.float32) if stale is None else stale[0]
    conv = jnp.asarray(rng.normal(size=(layers, W - 1, slots, chans)),
                       jnp.float32) if stale is None else stale[1]
    before = (ssm, conv)
    width = max(sum(1 if r is None else r[3] for r in step)
                for step in schedule)
    out = {name: ([], []) for name in seqs}

    @jax.jit
    def step_fn(ssm, conv, xbc, dt, slot_ids, positions):
        seg = ssd.step_segments(slot_ids, positions, slots)
        act, conv = ssd.causal_conv_step(xbc, conv, layer, weight, bias, seg)
        x, b, c = jnp.split(act, (H * P, H * P + N), axis=-1)
        y, ssm = ssd.ssd_packed(x.reshape(-1, H, P), dt, a, b, c, d, ssm,
                                layer, seg,
                                force_pallas=impl == "pallas-interpret")
        return ssm, conv, act, y

    for step in schedule:
        xbc = np.zeros((width, chans), np.float32)
        dt = np.ones((width, H), np.float32)
        slot_ids = np.full((width,), slots, np.int32)
        positions = np.full((width,), PAD_POSITION, np.int32)
        at, where = 0, []
        for rows in step:
            if rows is None:
                xbc[at] = 1e3            # a pad row's values must not count
                at += 1
                continue
            slot, name, first, count = rows
            sl = slice(first, first + count)
            xbc[at:at + count] = seqs[name]["xbc"][sl]
            dt[at:at + count] = seqs[name]["dt"][sl]
            slot_ids[at:at + count] = slot
            positions[at:at + count] = np.arange(first, first + count)
            where.append((name, at, count))
            at += count
        ssm, conv, act, y = step_fn(ssm, conv, *map(jnp.asarray, (
            xbc, dt, slot_ids, positions)))
        pads = positions == PAD_POSITION
        assert (np.asarray(y)[pads] == 0).all()
        for name, at, count in where:
            out[name][0].append(np.asarray(act[at:at + count]))
            out[name][1].append(np.asarray(y[at:at + count]))
    got = {n: (np.concatenate(v[0]), np.concatenate(v[1]))
           for n, v in out.items() if v[0]}
    return got, (ssm, conv), before, (weight, bias, a, d)


def _whole(seq, params):
    """The convolution and :func:`ssd.ssd_full` over a whole sequence."""
    weight, bias, a, d = params
    xbc = jnp.asarray(seq["xbc"], jnp.float32)
    padded = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    act = jax.nn.silu(bias + sum(weight[:, k] * padded[k:k + xbc.shape[0]]
                                 for k in range(W)))
    x, b, c = jnp.split(act, (H * P, H * P + N), axis=-1)
    with jax.default_matmul_precision("highest"):
        y = ssd.ssd_full(x.reshape(1, -1, H, P),
                         jnp.asarray(seq["dt"], jnp.float32)[None], a,
                         b[None], c[None], d, chunk=8)[0]
    return np.asarray(act), np.asarray(y)


def _sequences(lengths):
    rng = np.random.default_rng(11)
    return {name: dict(
        xbc=rng.normal(size=(n, H * P + 2 * N)).astype(np.float32),
        dt=np.log1p(np.exp(rng.normal(size=(n, H)))).astype(np.float32))
        for name, n in lengths.items()}


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("chunk", [1, 2, 3, 5])
def test_the_packed_step_equals_ssd_full_for_chunks_beside_decode_rows(
        chunk, impl):
    """Sequence ``a`` prefills in chunks of 1, 2, 3 or 5 rows (the
    convolution's tail crosses the step's boundary with 3, 2, 1 and no
    row of the tail left in it), beside sequence ``b``'s decode row (after
    its own prefill in the first step) and a pad row; both slots held
    another sequence's state and tail before, which a start at position 0
    clears inside the step."""
    seqs = _sequences({"a": 15, "b": 6 + 15})
    schedule = [[(2, "b", 0, 6)]]
    for i, lo in enumerate(range(0, 15, chunk)):
        schedule.append([(2, "b", 6 + i, 1), None,
                         (0, "a", lo, min(chunk, 15 - lo))])
    got, (ssm, conv), (ssm0, conv0), params = _drive(schedule, seqs, impl)
    for name in ("a", "b"):
        act, y = _whole(seqs[name], params)
        n = got[name][0].shape[0]
        np.testing.assert_allclose(got[name][0], act[:n], atol=1e-5)
        np.testing.assert_allclose(got[name][1], y[:n], atol=2e-4)
    # the other layer and the slots without rows keep what they held
    np.testing.assert_array_equal(np.asarray(ssm[0]), np.asarray(ssm0[0]))
    np.testing.assert_array_equal(np.asarray(ssm[1, [1, 3]]),
                                  np.asarray(ssm0[1, [1, 3]]))
    np.testing.assert_array_equal(np.asarray(conv[1, :, [1, 3]]),
                                  np.asarray(conv0[1, :, [1, 3]]))
    # slot 0's tail is a's last three inputs
    np.testing.assert_array_equal(np.asarray(conv[1, :, 0]),
                                  seqs["a"]["xbc"][12:15])


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_a_slot_re_admitted_at_position_0_inherits_nothing(impl):
    """``a`` runs seven rows in slot 1 and is preempted; ``c`` takes the
    slot; ``a`` is re-admitted into slot 3, which ``d`` left, and runs from
    position 0 again: all as fresh sequences."""
    seqs = _sequences({"a": 12, "c": 9, "d": 5})
    schedule = [[(1, "a", 0, 4), (3, "d", 0, 5)], [(1, "a", 4, 3)],
                [(1, "c", 0, 9)]]
    _, stale, _, _ = _drive(schedule, seqs, impl)
    got, _, _, params = _drive([[(3, "a", 0, 2)], [(3, "a", 2, 10)]], seqs,
                               impl, stale=stale)
    act, y = _whole(seqs["a"], params)
    np.testing.assert_allclose(got["a"][0], act, atol=1e-5)
    np.testing.assert_allclose(got["a"][1], y, atol=2e-4)


def test_a_step_of_pad_rows_alone_writes_nothing():
    seqs = _sequences({"a": 3})
    for impl in ("xla", "pallas-interpret"):
        _, (ssm, conv), (ssm0, conv0), _ = _drive([[None, None, None]], seqs,
                                                  impl)
        np.testing.assert_array_equal(np.asarray(ssm), np.asarray(ssm0))
        np.testing.assert_array_equal(np.asarray(conv), np.asarray(conv0))


def test_segments_are_a_slots_neighbouring_rows():
    seg = ssd.step_segments(
        jnp.asarray([2, 0, 0, 0, 9, 4, 4, 9], jnp.int32),
        jnp.asarray([20, 0, 1, 2, PAD_POSITION, 7, 8, PAD_POSITION],
                    jnp.int32), 5)
    assert int(seg.count[0]) == 3
    assert seg.slot.tolist() == [2, 0, 4, 4, 4]        # the last repeated
    assert seg.scatter_slot.tolist() == [2, 0, 4, 5, 5]
    assert seg.start.tolist()[:3] == [0, 1, 5]
    assert seg.rows.tolist() == [1, 3, 2, 0, 0]
    assert seg.zero.tolist() == [0, 1, 0, 0, 0]
    assert seg.segment.tolist() == [0, 1, 1, 1, 5, 2, 2, 5]
    assert seg.since.tolist() == [0, 0, 1, 2, 0, 0, 1, 0]


# -- (b) heads of 64, two a pool row ------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_a_pool_row_of_two_heads_equals_dense_attention_at_scale_1_64(impl):
    """32 query heads over 8 K/V heads of 64, the pool ``[.., 4, 128]``:
    three sequences' rows (a decode row, a prefill chunk, a pad row)
    against dense causal attention with ``softmax(q k^T / 64)``."""
    rng = np.random.default_rng(2)
    n, kv, d, bs, nb = 32, 8, 64, 16, 12
    lens = {0: 37, 1: 20}
    keys = {s: rng.normal(size=(m, kv, d)).astype(np.float32)
            for s, m in lens.items()}
    vals = {s: rng.normal(size=(m, kv, d)).astype(np.float32)
            for s, m in lens.items()}
    k_pool = jnp.zeros((1, nb, bs, kv // 2, 2 * d), jnp.float32)
    v_pool, pos = k_pool, jnp.full((nb, bs), PAD_POSITION, jnp.int32)
    tables = np.full((2, 4), -1, np.int32)
    tables[0, :3], tables[1, :2] = [5, 2, 9], [7, 1]
    for s, m in lens.items():
        tbl = jnp.broadcast_to(jnp.asarray(tables[s]), (m, 4))
        idx = paging.flat_write_indices(tbl, jnp.arange(m), bs, nb * bs)
        k_pool = paging.write_pool_rows(k_pool, jnp.asarray(keys[s]), idx, 0)
        v_pool = paging.write_pool_rows(v_pool, jnp.asarray(vals[s]), idx, 0)
        pos = paging.write_pool_positions(pos, jnp.arange(m), idx)
    rows = [(0, 36)] + [(1, p) for p in range(14, 20)]
    q = rng.normal(size=(len(rows) + 1, n, d)).astype(np.float32)
    q_pos = np.asarray([p for _, p in rows] + [PAD_POSITION], np.int32)
    tok = np.stack([tables[s] for s, _ in rows] + [tables[0]])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(pa.paged_attention(
            jnp.asarray(q), k_pool, v_pool, pos, jnp.asarray(tok),
            jnp.asarray(q_pos), 0, scale=1 / 64,
            force_pallas=impl == "pallas-interpret"))
    for i, (s, p) in enumerate(rows):
        k = np.repeat(keys[s][:p + 1], n // kv, axis=1)
        v = np.repeat(vals[s][:p + 1], n // kv, axis=1)
        scores = np.einsum("nd,snd->ns", q[i], k) / 64
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        want = np.einsum("ns,snd->nd", probs / probs.sum(-1, keepdims=True),
                         v)
        np.testing.assert_allclose(got[i], want, atol=2e-5)


def test_paged_attention_impl_names_what_runs(monkeypatch):
    pa.paged_attention_impl.cache_clear()
    assert pa.paged_attention_impl(64, 128) == "xla"        # off the TPU
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    pa.paged_attention_impl.cache_clear()
    try:
        assert pa.paged_attention_impl(64, 128) == "pallas"  # two a row
        assert pa.paged_attention_impl(128, 128) == "pallas"
        assert pa.paged_attention_impl(96, 128) == "xla"
        assert pa.paged_attention_impl(64, 16) == "xla"
    finally:
        pa.paged_attention_impl.cache_clear()
    assert ssd.ssd_packed_impl(128, 4096) == "xla"
    assert ssd.ssd_packed_impl(128, 4096, True) == "pallas-interpret"
    assert ssd.ssd_packed_impl(8, 64, True) == "xla"        # no whole tile


# -- (c) the module and the paged forward against the reference -------------

def test_the_layer_pattern_is_one_stack_a_kind_and_a_scan_a_run():
    cfg, _, params = _model()
    assert cfg.runs() == (("mamba2", 0, 1), ("full", 0, 1), ("mamba2", 1, 2),
                          ("full", 1, 1))
    layers = params["params"]["model"]
    mamba = layers["layers_mamba2"]["layer"]["attn"]
    assert mamba["in_proj"]["kernel"].shape == (3, 64, 2 * 128 + 2 * 16 + 4)
    assert mamba["conv_kernel"].shape == (3, 128 + 32, 4)
    assert mamba["A_log"].shape == mamba["D"].shape == (3, 4)
    assert layers["layers_full"]["layer"]["attn"]["qkv"][
        "k_kernel"].shape == (2, 64, 32)
    assert "lm_head" not in params["params"]                  # tied
    assert gh.GraniteHybridConfig().runs()[:3] == (
        ("mamba2", 0, 5), ("full", 0, 1), ("mamba2", 5, 9))
    assert gh.GraniteHybridConfig().pool_pack == 2 == cfg.pool_pack


def test_full_forward_matches_the_reference():
    cfg, model, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 45))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    want = _reference_logits(params, tokens)
    assert np.std(want) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-4 * np.std(want))


def _in_slot_1(cfg, params, tokens, chunks, cache=None, **kw):
    """One sequence through the paged driver in ``chunks`` rows a step
    (prefill chunks, then single decode rows) beside pad rows, in slot 1,
    whose blocks are mapped out of order."""
    if cache is None:
        cache = paging.init_serving_cache(
            cfg, num_blocks=16, block_size=BS, table_rows=3,
            max_blocks_per_seq=6, dtype=jnp.float32)
    table = np.full((3, 6), -1, np.int32)
    table[1] = [3, 5, 7, 9, 11, 2]
    got, cache = fc.paged_logits(
        cfg, params, [tokens], fc.chunked(chunks), max(chunks),
        cache=cache.replace(block_tables=jnp.asarray(table)), slots=[1],
        block_size=BS, **kw)
    return np.stack([got[0, p] for p in range(sum(chunks))]), cache


CHUNKS = [7, 16, 2, 1, 3, 9] + [1] * 7          # 45 positions


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_prefill_then_decode_matches_the_reference(impl):
    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    tokens = np.random.RandomState(2).randint(0, 256, (45,))
    got, cache = _in_slot_1(cfg, params, tokens, CHUNKS)
    want = _reference_logits(params, tokens[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-4 * np.std(want))
    assert cache.k.shape == (2, 16, BS, 1, 32)       # two heads a row
    assert float(jnp.abs(cache.states["ssm"][:, 1]).max()) > 0
    assert float(jnp.abs(cache.states["ssm"][:, [0, 2]]).max()) == 0


# -- (d) the control: what the comparison must not pass ----------------------

@pytest.fixture(scope="module")
def sound():
    cfg, _, params = _model()
    tokens = np.random.RandomState(2).randint(0, 256, (45,))
    want = _reference_logits(params, tokens[None])[0]
    got, cache = _in_slot_1(cfg, params, tokens, CHUNKS)
    err = fc.worst(got, want)
    print("sound run: largest logit difference over the spread", err)
    assert err < 2e-5
    ps.destroy_model_parallel()
    return tokens, want, cache, 2e-5


def test_a_stale_state_fails_the_comparison(sound, monkeypatch):
    """The slot's leaves as the last request left them and no start from
    zero: the comparison that the sound run passes fails."""
    tokens, want, cache, limit = sound
    cfg, _, params = _model()
    real = ssd.step_segments

    def never_fresh(*a):
        return real(*a)._replace(zero=jnp.zeros_like(real(*a).zero))

    monkeypatch.setattr(ssd, "step_segments", never_fresh)
    got, _ = _in_slot_1(cfg, params, tokens, CHUNKS, cache=cache,
                        fresh=True)
    assert fc.worst(got, want) > limit


def test_a_dropped_convolution_tail_fails_the_comparison(sound, monkeypatch):
    """Tails that are never written: a chunk's first three rows and every
    decode row convolve with zeros."""
    tokens, want, _, limit = sound
    cfg, _, params = _model()
    real = ssd.causal_conv_step

    def tail_lost(x, tails, *a):
        return real(x, tails, *a)[0], tails

    monkeypatch.setattr(ssd, "causal_conv_step", tail_lost)
    got, _ = _in_slot_1(cfg, params, tokens, CHUNKS, fresh=True)
    assert fc.worst(got, want) > limit


def test_a_bfloat16_state_fails_the_comparison(sound):
    tokens, want, _, limit = sound
    cfg, _, params = _model()
    cache = paging.init_serving_cache(
        cfg, num_blocks=16, block_size=BS, table_rows=3,
        max_blocks_per_seq=6, dtype=jnp.float32)
    cache = cache.replace(states=dict(
        cache.states, ssm=cache.states["ssm"].astype(jnp.bfloat16)))
    got, _ = _in_slot_1(cfg, params, tokens, CHUNKS, cache=cache)
    assert fc.worst(got, want) > limit


# -- (e) the benchmark's family: seeded weights and checkpoint names ---------

def test_the_family_reads_normal_draws_as_mamba2s_initialisation():
    ps.initialize_model_parallel()
    family = harness.load_plugin("families", "granite_hybrid")
    cfg, model, forward = family.build(PUBLISHED, dtype=jnp.float32,
                                       param_dtype=jnp.float32)
    assert isinstance(cfg, gh.GraniteHybridConfig)
    assert cfg.serving_family().forward is forward
    assert cfg.kind_config("mamba2").serving_family().forward is forward
    shapes = meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = harness.make_weights(shapes, 2 ** 31 + 7, 0.02)
    weights = family.published(params, PUBLISHED)
    a = np.exp(np.asarray(weights("A_log", 0)))
    dt = np.log1p(np.exp(np.asarray(weights("dt_bias", 2))))
    conv = np.asarray(weights("conv_weight", 3))
    assert ((a >= 1) & (a <= 16)).all() and np.ptp(a) > 1
    assert ((dt >= 1e-3 * .99) & (dt <= 1e-1 * 1.01)).all()
    assert (np.asarray(weights("D", 0)) == 1).all()
    assert conv.shape == (160, 1, 4) and np.abs(conv).max() <= 0.5
    # by the checkpoint's names: every tensor, in its orientation
    assert weights("model.layers.0.mamba.in_proj.weight").shape == (292, 64)
    assert weights("model.layers.3.mamba.out_proj.weight").shape == (64, 128)
    assert weights("model.layers.2.mamba.norm.weight").shape == (128,)
    assert weights("model.layers.1.self_attn.k_proj.weight").shape == (32,
                                                                       64)
    assert weights("model.layers.4.shared_mlp.input_linear.weight"
                   ).shape == (256, 64)
    assert weights("model.layers.4.shared_mlp.output_linear.weight"
                   ).shape == (64, 128)
    assert weights("model.embed_tokens.weight").shape == (256, 64)
    assert weights("model.norm.weight").shape == (64,)
    with pytest.raises(KeyError):
        weights("lm_head")
    # the seeded forward and the reference read the same parameters
    tokens = np.random.RandomState(4).randint(0, 256, (30,))
    got, _ = _in_slot_1(cfg, params, tokens, [16, 13, 1])
    ref = harness.load_plugin("reference", "granite_hybrid_f32")
    want = np.asarray(ref.forward(weights, tokens[None], PUBLISHED)[0])[0]
    np.testing.assert_allclose(got, want, atol=5e-4 * np.std(want))
    with pytest.raises(ValueError, match="dense models"):
        family.build(dict(PUBLISHED, num_local_experts=8))


# -- (f) through ServingEngine --------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests through one engine of two slots whose pool holds
    seven blocks: the youngest is preempted on the way."""
    cfg, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_state_slot_steps_total", "nxd_state_resets_total",
        "nxd_paged_columns_total", "nxd_engine_rows_total",
        "nxd_engine_steps_total"),
        lengths=[50, 40, 5], new=[30, 12, 4], num_blocks=7, max_slots=2)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_decodes_as_a_fresh_one(served):
    """Seven blocks do not hold a and b: b is preempted and re-admitted
    into a slot whose states and tails another request left, and still
    decodes what the reference does (above); the pool is whole at the
    end, and no leaf was ever cleared by the host."""
    eng = served.eng
    fc.check_preempted_and_whole(eng)
    assert float(jnp.abs(eng.cache.states["ssm"]).max()) > 0
    assert float(jnp.abs(eng.cache.states["conv"].astype(
        jnp.float32)).max()) > 0


def test_state_counters(served):
    counters = served.counters
    slot_steps = counters["nxd_state_slot_steps_total"]
    assert set(slot_steps) == {"advanced", "held"}
    # every enqueued step advanced a slot; a slot whose prefill waits for
    # the budget, or a step of one request, holds the other
    enqueued = sum(counters["nxd_engine_steps_total"].values())
    assert enqueued <= slot_steps["advanced"] <= 2 * enqueued
    assert slot_steps["held"] >= 0
    # three admissions and b's second
    assert counters["nxd_state_resets_total"][""] >= 4
    cols = counters["nxd_paged_columns_total"]
    assert cols["live"] > 0 and cols["skipped"] > 0


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES)
def test_refused_features_raise_by_name(feature, kw):
    cfg, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw})


def test_session_export_is_refused_and_the_cache_is_the_kinds():
    cfg, _, params = _model()
    eng = fc.check_session_export_is_refused(
        cfg, params, paging.StatePoolPagedCache, paging.StatePoolCache)
    cache, kind = eng.cache, cfg.serving_family().cache_kind
    assert kind.pack == 2
    assert [leaf.name for leaf in kind.leaves] == ["ssm", "conv"]
    assert cache.k.shape == (2, 40, BS, 1, 32) == cache.v.shape
    assert cache.states["ssm"].shape == (3, 3, 16, 128)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.states["conv"].shape == (3, 3, 3, 160)  # [L, W-1, J, C]
    assert cache.capacity == 40 * BS and cache.max_slots == 3
    # the leaves are one mechanism: the lightning state is such a leaf
    from neuronx_distributed_tpu.models import minicpm_sala

    (leaf,) = minicpm_sala.tiny_config().serving_family().cache_kind.leaves
    assert (leaf.name, leaf.lead, leaf.trail) == ("state", (3, 4), (16, 16))
    assert paging.FULL_CACHE.leaves == () and paging.FULL_CACHE.pack == 1


def test_the_attention_scale_is_the_configs():
    from neuronx_distributed_tpu.models import llama

    assert llama.tiny_config().attn_scale_ == 1 / 4            # 1/sqrt(16)
    assert llama.tiny_config(attn_scale=0.5).attn_scale_ == 0.5
    cfg = gh.tiny_config()
    assert cfg.kind_config("full").attn_scale_ == cfg.attention_multiplier
    assert cfg.kind_config("mamba2").residual_scale == 0.22
    with pytest.raises(ValueError, match="layer_types"):
        gh.tiny_config(layer_types=("mamba",))


# -- groups of B and C (models/nemotron_h.py: eight) -------------------------

def _grouped_recurrence(x, dt, a, b, c, d, state):
    """Token by token in NumPy float64, head ``j`` reading group ``j //
    (heads / groups)`` of ``b, c [S, G, N]``; ``state [heads, P, N]``."""
    each = x.shape[1] // b.shape[1]
    s, ys = state.copy(), []
    for t in range(x.shape[0]):
        b_h, c_h = (np.repeat(v[t], each, axis=0) for v in (b, c))
        s = (np.exp(dt[t] * a)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * b_h[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", s, c_h) + d[:, None] * x[t])
    return np.stack(ys), s


@pytest.mark.parametrize("impl", ["full", "xla", "pallas-interpret"])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_scan_reads_b_and_c_by_group(groups, impl):
    """Sixteen heads of 64 (a group of eight is two heads, one 128-channel
    tile of the kernel) over a state of 8: the chunked scan over a whole
    sequence, and the packed step (a chunk of five rows from position 0 in
    slot 2, a decode row of slot 0 on the state it held, a pad row), each
    against the token-by-token recurrence; one group handed as ``[.., 1,
    N]`` is bit for bit the scan without groups."""
    heads, width, n, slots = 16, 64, 8, 3
    rng = np.random.default_rng(groups)
    a, d = -np.exp(rng.normal(size=heads)), rng.normal(size=heads)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)

    def inputs(length):
        return (rng.normal(size=(length, heads, width)),
                np.log1p(np.exp(rng.normal(size=(length, heads)))),
                rng.normal(size=(length, groups, n)),
                rng.normal(size=(length, groups, n)))

    zero = np.zeros((heads, width, n))
    if impl == "full":
        x, dt, b, c = inputs(13)
        want, _ = _grouped_recurrence(x, dt, a, b, c, d, zero)
        with jax.default_matmul_precision("highest"):
            got = ssd.ssd_full(f32(x)[None], f32(dt)[None], f32(a),
                               f32(b)[None], f32(c)[None], f32(d), chunk=5)
            flat = ssd.ssd_full(f32(x)[None], f32(dt)[None], f32(a),
                                f32(b[:, 0])[None], f32(c[:, 0])[None],
                                f32(d), chunk=5)
        np.testing.assert_allclose(np.asarray(got[0]), want, atol=5e-5)
        if groups == 1:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(flat))
        else:
            assert np.abs(np.asarray(got) - np.asarray(flat)).max() > 0.1
        return
    assert ssd.ssd_packed_impl(n, heads * width, impl != "xla",
                               groups) == impl
    x, dt, b, c = inputs(7)
    held = rng.normal(size=(slots, heads, width, n))
    # the cache's layout: [layers, slots, N, heads * P]
    ssm = f32(held.transpose(0, 3, 1, 2).reshape(1, slots, n, -1))
    slot_ids = np.array([0, 2, 2, 2, 2, 2, slots], np.int32)
    positions = np.array([9, 0, 1, 2, 3, 4, PAD_POSITION], np.int32)
    seg = ssd.step_segments(jnp.asarray(slot_ids), jnp.asarray(positions),
                            slots)

    def packed(b, c):
        return ssd.ssd_packed(f32(x), f32(dt), f32(a), f32(b), f32(c),
                              f32(d), ssm, 0, seg,
                              force_pallas=impl != "xla")

    y, new = packed(b, c)
    decode, s0 = _grouped_recurrence(x[:1], dt[:1], a, b[:1], c[:1], d,
                                     held[0])
    chunk, s2 = _grouped_recurrence(x[1:6], dt[1:6], a, b[1:6], c[1:6], d,
                                    zero)
    np.testing.assert_allclose(np.asarray(y[:1]), decode, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y[1:6]), chunk, atol=2e-4)
    assert (np.asarray(y[6]) == 0).all()
    new = np.asarray(new)[0].reshape(slots, n, heads, width).transpose(
        0, 2, 3, 1)
    np.testing.assert_allclose(new[0], s0, atol=2e-4)
    np.testing.assert_allclose(new[2], s2, atol=2e-4)
    np.testing.assert_array_equal(new[1], np.asarray(held[1], np.float32))
    if groups == 1:
        flat_y, flat_new = packed(b[:, 0], c[:, 0])
        np.testing.assert_array_equal(np.asarray(y), np.asarray(flat_y))
        np.testing.assert_array_equal(new[0], np.asarray(flat_new)[0][
            0].reshape(n, heads, width).transpose(1, 2, 0))


def test_the_convolution_runs_over_every_groups_channels():
    """``x | B | C`` of eight groups is ``d_inner + 2 x 8 x N`` channels:
    a chunk across a step's boundary equals the whole sequence's."""
    heads, width, n, groups, taps, slots = 4, 8, 4, 8, 4, 2
    chans = heads * width + 2 * groups * n
    rng = np.random.default_rng(3)
    weight = jnp.asarray(rng.uniform(-.5, .5, (chans, taps)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=chans) * .1, jnp.float32)
    xbc = jnp.asarray(rng.normal(size=(9, chans)), jnp.float32)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    want = jax.nn.silu(bias + sum(weight[:, k] * padded[k:k + 9]
                                  for k in range(taps)))
    tails = jnp.asarray(rng.normal(size=(1, taps - 1, slots, chans)),
                        jnp.float32)
    got = []
    for lo, hi in ((0, 5), (5, 9)):
        seg = ssd.step_segments(jnp.full((hi - lo,), 1, jnp.int32),
                                jnp.arange(lo, hi, dtype=jnp.int32), slots)
        act, tails = ssd.causal_conv_step(xbc[lo:hi], tails, 0, weight,
                                          bias, seg)
        got.append(np.asarray(act))
    np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tails[0, :, 1]),
                                  np.asarray(xbc[6:9]))
