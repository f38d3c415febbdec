"""MiMo-V2 (full-attention and sliding-window layers of two K/V head
counts, keys of 192 beside values of 128, a sink term in the sliding
layers' softmax, a dense first layer and sigmoid-routed expert layers of
which a share is held) through the model, the paged forward over the
window-pool cache with wide keys, the kernel and ``ServingEngine``,
against the benchmark's plain reference
``benchmarks/reference/mimo_v2_flash_f32.py``.

Tiny widths but the published head sizes (a wide-key pool's chunks are 128
lanes): hidden 64, five layers (full dense, sliding, sliding, full,
sliding sparse); 8 query heads of 192 over 2 (full) and 4 (sliding) K
heads of 192 and V heads of 128; a window of 8 positions, two pool blocks
of 4, so a slot's ring is three blocks and wraps every 12 positions; 8
experts, top 3, of which the first 4 are held. The weights are seeded,
norm multipliers of order one, sinks uniform in [0, 3].
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

import family_checks as fc
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import mimo_v2
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)

BS, WINDOW, RING = 4, 8, 3
#: what a sound float32 run may read of the logits' deviation (it reads
#: 2e-6); each of the four faults below reads over twenty times it
SOUND = 2e-5
#: every comparison runs over these two sequences' positions, so that the
#: reference's eager programs are compiled once
LENGTH = 33
PUBLISHED = dict(
    model_type="mimo_v2_flash", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=5, num_attention_heads=8,
    swa_num_attention_heads=8, num_key_value_heads=2,
    swa_num_key_value_heads=4, head_dim=192, swa_head_dim=192,
    v_head_dim=128, swa_v_head_dim=128, max_position_embeddings=4096,
    attention_bias=False, layernorm_epsilon=1e-5, hidden_act="silu",
    rope_theta=5000000, swa_rope_theta=10000, partial_rotary_factor=0.334,
    attention_value_scale=0.707, sliding_window=WINDOW,
    sliding_window_size=WINDOW, attention_chunk_size=WINDOW,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    hybrid_layer_pattern=[0, 1, 1, 0, 1], moe_layer_freq=[0, 1, 1, 1, 1],
    moe_intermediate_size=32, n_routed_experts=4, n_shared_experts=None,
    num_experts_per_tok=3, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, topk_method="noaux_tc",
    routed_scaling_factor=None, tie_word_embeddings=False,
    initializer_range=0.02,
    share={"n_routed_experts_published": 8, "first_expert": 0},
    family="mimo_v2_flash", reference="mimo_v2_flash_f32")


#: the seeded weights and the comparison's case, made once
_CASE = {}
#: the paged driver's pools: blocks of 4 under two sequences of 33
POOL = dict(num_blocks=40, max_blocks_per_seq=16)


def _family():
    return harness.load_plugin("families", "mimo_v2_flash")


def _reference():
    return harness.load_plugin("reference", "mimo_v2_flash_f32")


@fc.once_a_module
def _model(**kw):
    """The family's config from the published keys, its module and seeded
    weights."""
    cfg, model, _ = _family().build(
        PUBLISHED, **{"dtype": jnp.float32, "param_dtype": jnp.float32,
                      **kw})
    if "params" in _CASE:                  # the same draw for every config
        return cfg, model, _CASE["params"]
    init = meta.unbox(jax.eval_shape(model.init, jax.random.key(3),
                                     jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if name.endswith("['sink']"):
            return 3.0 * jax.random.uniform(key, x.shape, x.dtype)
        # a router of order one, so that the choices are not all ties
        if "router" in name and not name.endswith("['scale']"):
            return 1.0 * noise

    _CASE["params"] = fc.seeded_weights(init, special)
    return cfg, model, _CASE["params"]


def _reference_logits(params, tokens):
    weights = _family().published(params, PUBLISHED)
    return np.asarray(_reference().forward(weights, np.asarray(tokens),
                                           PUBLISHED)[0])


def _case():
    """``(seqs [2, LENGTH], the reference's logits)`` under ``_model()``'s
    weights (seeded: every call draws the same)."""
    if "it" not in _CASE:
        seqs = np.random.RandomState(2).randint(0, 256, (2, LENGTH))
        _CASE["it"] = seqs, _reference_logits(_model()[2], seqs)
    return _CASE["it"]


# -- (a) the layer pattern, the parameters, the two pools --------------------

def test_the_pattern_is_one_stack_a_kind_with_its_own_kv_heads():
    cfg, _, params = _model()
    assert type(cfg) is mimo_v2.MiMoV2Config       # no sink range: plain
    assert cfg.kinds() == ("full_dense", "sliding_sparse", "sliding_sparse",
                           "full_sparse", "sliding_sparse")
    assert cfg.runs() == (("full_dense", 0, 1), ("sliding_sparse", 0, 2),
                          ("full_sparse", 0, 1), ("sliding_sparse", 2, 1))
    assert cfg.pool_layers() == {"full_dense": [0], "sliding_sparse":
                                 [0, 1, 2], "full_sparse": [1]}
    assert (cfg.kv_heads_of("full"), cfg.kv_heads_of("sliding")) == (2, 4)
    assert cfg.rotary_dim == 64
    assert cfg.attn_scale_ == pytest.approx(192 ** -0.5)
    tree = params["params"]["model"]
    attn = {k: tree[f"layers_{k}"]["layer"]["attn"] for k in cfg.carried()}
    assert {k: (a["q_proj"]["kernel"].shape, a["k_proj"]["kernel"].shape,
                a["v_proj"]["kernel"].shape, a["o_proj"]["kernel"].shape)
            for k, a in attn.items()} == {
        "full_dense": ((1, 64, 1536), (1, 64, 384), (1, 64, 256),
                       (1, 1024, 64)),
        "sliding_sparse": ((3, 64, 1536), (3, 64, 768), (3, 64, 512),
                           (3, 1024, 64)),
        "full_sparse": ((1, 64, 1536), (1, 64, 384), (1, 64, 256),
                        (1, 1024, 64))}
    # a sink a query head, in the sliding layers alone
    assert attn["sliding_sparse"]["sink"].shape == (3, 8)
    assert "sink" not in attn["full_dense"] and "sink" not in attn[
        "full_sparse"]
    moe = tree["layers_sliding_sparse"]["layer"]["moe"]
    assert moe["router"]["kernel"].shape == (3, 64, 8)    # all 8 scored
    assert moe["router"]["bias"].shape == (3, 8)
    assert moe["experts"]["down"].shape == (3, 4, 32, 64)  # 4 held
    assert "shared" not in moe
    # the whole model: layers 0, 5, 11, ..., 47 full, the other 39 sliding
    whole = mimo_v2.MiMoV2Config()
    full = [i for i, t in enumerate(whole.hybrid_layer_pattern) if t == 0]
    assert full == [0] + list(range(5, 48, 6)) and whole.runs()[:4] == (
        ("full_dense", 0, 1), ("sliding_sparse", 0, 4),
        ("full_sparse", 0, 1), ("sliding_sparse", 4, 5))
    kind = whole.serving_family().cache_kind
    assert (kind.full_layers, kind.window_layers, kind.window) == (9, 39,
                                                                   128)
    assert (kind.full_rows, kind.window_rows) == ((4, 192, 128),
                                                  (8, 192, 128))
    with pytest.raises(ValueError, match="0 or 1"):
        mimo_v2.tiny_config(hybrid_layer_pattern=(0, 1, 2, 0, 1))


def test_the_wide_key_layout_fills_whole_lanes_and_comes_back():
    """192 = 128 + 64: each head's first 128 values a chunk, then the
    heads' last 64 two heads a chunk; the queries meet the chunks where
    they lie, and their product is the heads' own."""
    rng = np.random.RandomState(0)
    k = rng.randn(5, 4, 192).astype(np.float32)
    rows = np.asarray(pa.keys_to_lanes(jnp.asarray(k)))
    assert rows.shape == (5, 768)
    assert (rows[:, 128:256] == k[:, 1, :128]).all()
    assert (rows[:, 640:704] == k[:, 2, 128:]).all()
    assert (rows[:, 704:768] == k[:, 3, 128:]).all()
    assert pa.key_chunks(4, 192) == ((0, 4), (1, 4), (2, 5), (3, 5))
    assert (np.asarray(pa.keys_of_lanes(jnp.asarray(rows), 4, 192))
            == k).all()
    q = rng.randn(3, 8, 192).astype(np.float32)          # n_rep 2
    wide = np.asarray(pa.queries_to_lanes(jnp.asarray(q), 4))
    assert wide.shape == (3, 8, 256)
    for n in range(8):
        h = n // 2
        got = sum(wide[:, n, i * 128:(i + 1) * 128]
                  @ rows[:, c * 128:(c + 1) * 128].T
                  for i, c in enumerate(pa.key_chunks(4, 192)[h]))
        np.testing.assert_allclose(got, q[:, n] @ k[:, h].T, rtol=1e-5,
                                   atol=1e-5)
    # a width whose rest does not divide the lanes, or heads that do not
    # pair up, has no such layout
    with pytest.raises(ValueError, match="whole"):
        pa.key_chunks(4, 176)
    with pytest.raises(ValueError, match="whole"):
        pa.key_chunks(3, 192)
    assert pa.paged_attention_impl(192, 128, False) == "xla"


# -- (b) the kernel at 192 / 128, both head ratios, with and without sink ----

def _pool_case(n, kv, sliding, seed=0):
    """Three slots of unlike lengths in a pool of 12 blocks of 8: ten
    packed rows (decode rows, a chunk of four, two pad rows)."""
    rng = np.random.RandomState(seed)
    layers, nb, bs, d, dv = 2, 12, 8, 192, 128
    maxb = 3 if sliding else 4
    k = rng.randn(layers, nb, bs, kv, d).astype(np.float32)
    v = rng.randn(layers, nb, bs, kv, dv).astype(np.float32)
    by_slot = np.array([[0, 1, 2, 3], [4, 5, 6, -1], [7, 8, -1, -1]])[
        :, :maxb]
    lengths = [maxb * bs - 3, 17, 9]
    pos = np.full((nb, bs), PAD_POSITION, np.int32)
    for slot, length in enumerate(lengths):
        for p in range(length):
            col = (p // bs) % maxb if sliding else p // bs
            if by_slot[slot, col] >= 0:
                pos[by_slot[slot, col], p % bs] = p
    slot = np.array([0, 1, 2, 2, 2, 2, 0, 0, 1, 1])
    q_pos = np.array([lengths[0] - 1, lengths[1] - 1, 5, 6, 7, 8, 3,
                      PAD_POSITION, 4, PAD_POSITION], np.int32)
    tables = np.where((q_pos < PAD_POSITION)[:, None], by_slot[slot],
                      -1).astype(np.int32)
    q = rng.randn(10, n, d).astype(np.float32)
    return q, k, v, pos, tables, q_pos


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("n_rep,sliding", [(16, None), (8, 12)],
                         ids=["full-gqa16", "sliding-gqa8"])
def test_the_kernel_at_keys_of_192_and_values_of_128(n_rep, sliding, sink):
    """The Pallas body (interpret mode) against the XLA path, and the XLA
    path against a dense softmax written out here: ``n_rep`` 16 over 2 K/V
    heads as a full layer's walk, 8 over 4 under a causal window as a
    ring's, with and without a sink a head."""
    kv = 32 // n_rep
    q, k, v, pos, tables, q_pos = _pool_case(32, kv, sliding)
    b = (np.random.RandomState(1).uniform(-1, 3, 32).astype(np.float32)
         if sink else None)
    args = (jnp.asarray(q), pa.keys_to_lanes(jnp.asarray(k)),
            jnp.asarray(v), jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(q_pos), jnp.int32(1))
    kw = dict(sliding=sliding, sink=None if b is None else jnp.asarray(b))
    xla = np.asarray(pa.paged_attention(*args, force_pallas=False, **kw))
    kernel = np.asarray(pa.paged_attention(*args, force_pallas=True, **kw))
    real = q_pos < PAD_POSITION
    assert xla.shape == (10, 32, 128)
    np.testing.assert_allclose(kernel[real], xla[real], atol=1e-5)
    assert (kernel[~real] == 0).all()          # a pad row, sink or none
    for t in np.nonzero(real)[0]:
        cols = tables[t][tables[t] >= 0]
        at = pos[cols].ravel()
        seen = at <= q_pos[t]
        if sliding:
            seen &= q_pos[t] - at < sliding
        keys = k[1, cols].reshape(-1, kv, 192)[seen]
        values = v[1, cols].reshape(-1, kv, 128)[seen]
        for n in (0, 13, 31):
            s = keys[:, n // n_rep] @ q[t, n] / np.sqrt(192.0)
            e = np.exp(s - s.max())
            extra = np.exp(b[n] - s.max()) if sink else 0.0
            want = (e / (e.sum() + extra)) @ values[:, n // n_rep]
            np.testing.assert_allclose(xla[t, n], want, atol=1e-5)


@pytest.mark.parametrize("run", [2, 8])
@pytest.mark.parametrize("n_rep,sliding", [(16, None), (8, 12)],
                         ids=["full-gqa16", "sliding-gqa8"])
def test_a_rows_blocks_ride_in_runs_at_both_head_counts(n_rep, sliding, run):
    """The same step's walk cut into the kernel's units
    (``tests/walk_checks.py``): at 16 and 8 heads a group is one packed
    row's heads, so a run is one row's blocks."""
    from walk_checks import check_paged_runs

    *_, pos, tables, q_pos = _pool_case(32, 32 // n_rep, sliding)
    cols = np.arange(tables.shape[1])
    live = (pa.column_live(tables, cols, q_pos[:, None], BS)
            if sliding is None else pa.sliding_column_live(
                tables, cols, q_pos[:, None], BS, sliding, tables.shape[1]))
    kinds = check_paged_runs(tables, q_pos, np.asarray(live), BS,
                             pos.shape[0], n_rep, run, sliding=sliding)
    assert kinds[0] > 0


# -- (c) the model and the paged forward against the reference ---------------

def test_full_forward_matches_the_reference_and_a_wide_window_does_not():
    cfg, model, params = _model()
    tokens, want = _case()
    assert np.std(want) > 0.05

    def worst(cfg):
        with jax.default_matmul_precision("highest"):
            got = np.asarray(jax.jit(mimo_v2.MiMoV2ForCausalLM(cfg).apply)(
                params, jnp.asarray(tokens)))
        return np.abs(got - want).max() / np.std(want)

    assert worst(cfg) < SOUND
    # a window one position too wide
    assert worst(dataclasses.replace(cfg, sliding_window=WINDOW + 1)) \
        > 20 * SOUND


@pytest.mark.parametrize("impl,length", [("xla", LENGTH),
                                         ("pallas-interpret", 17)])
def test_paged_prefill_then_decode_matches_the_reference(impl, length):
    """33 positions pass the window four times and wrap the ring of 12
    twice (17 through the kernel in interpret mode: twice, and once); the
    second sequence's chunks straddle blocks beside the first's decode
    row."""
    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    seqs, want = _case()
    got, cache = fc.paged_logits(
        cfg, params, seqs, fc.schedule(length, [3, 4, 2], BS), BS, **POOL)
    assert len(got) == 2 * length
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=SOUND * np.std(want), err_msg=(s, p))
    if length < LENGTH:
        return
    # two pools of unlike rows: the keys in whole lanes, 2 heads of 192 a
    # position in a full layer and 4 in a ring; the values by head
    assert cache.k.shape == (2, 40, BS, 384)
    assert cache.v.shape == (2, 40, BS, 2, 128)
    assert cache.wk.shape == (3, 3 * RING, BS, 768)
    assert cache.wv.shape == (3, 3 * RING, BS, 4, 128)
    assert cache.window_ring == RING
    held = np.asarray(cache.wpos[:2 * RING]).reshape(2, -1)
    assert (np.sort(held, axis=1) == np.arange(33 - 12, 33)).all()
    assert int((np.asarray(cache.pos) < PAD_POSITION).sum()) == 66
    counts = np.asarray(cache.moe_counts)
    assert counts.sum() == 4 * 3 and counts[1] == 0 and counts[2] > 0


def test_what_the_comparison_must_not_pass(monkeypatch):
    """The sink left out, the value scale left out and the router's
    scores rounded to bfloat16 (a window too wide is the full forward's
    case): each reads over twenty times what a sound run may."""
    cfg, _, params = _model()
    seqs, want = _case()
    steps = fc.schedule(30, [4] * 7 + [2], BS)[:8]

    def worst(cfg, **kw):
        got, _ = fc.paged_logits(cfg, params, seqs[:1], steps, BS, **POOL,
                                 **kw)
        return fc.worst_at(got, want)

    assert worst(cfg) < SOUND
    assert worst(dataclasses.replace(cfg, swa_sink=False)) > 0.05
    assert worst(dataclasses.replace(cfg, attention_value_scale=1.0)) > 0.05
    from neuronx_distributed_tpu.modules.moe import routing

    logits = routing.RouterBase.logits
    monkeypatch.setattr(
        routing.RouterBase, "logits",
        lambda self, x: logits(self, x).astype(jnp.bfloat16).astype(
            jnp.float32))
    assert worst(cfg, fresh=True) > 20 * SOUND


# -- (d) the shares add up to the uncut layer --------------------------------

def test_four_shares_routed_sums_are_the_uncut_layer():
    """A sparse layer's routed sums as four devices of eight experts hold
    them, added, equal the plain reference's uncut layer over all 32 under
    the sigmoid router (no shared expert to count once); ``elsewhere``
    counts what the other shares keep."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=32, hidden_size=64, intermediate_size=32,
                   top_k=8, capacity_factor=None, router_type="sigmoid",
                   held=held, dtype=jnp.float32, param_dtype=jnp.float32)

    whole = meta.unbox(moe((0, 32)).init(jax.random.key(1), jnp.asarray(x),
                                         valid=jnp.asarray(valid)))
    whole = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)
    tree = whole["params"]

    def share(first, count):
        p = {"params": dict(tree, experts={
            k: w[first:first + count] for k, w in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = moe((first, count)).apply(p, jnp.asarray(x),
                                               valid=jnp.asarray(valid))
        return np.asarray(y), np.asarray(aux["assignments"])

    def weights(name, layer=None, expert=None):
        if name == "router":
            return np.asarray(tree["router"]["kernel"]).T
        if name == "router_bias":
            return np.asarray(tree["router"]["bias"])
        node = {k: v[expert] for k, v in tree["experts"].items()}
        (w,) = [v for k, v in harness.load_plugin(
            "families", "llama")._leaves(node).items() if name in k]
        return np.asarray(w).T

    config = dict(n_routed_experts=32, num_experts_per_tok=8,
                  norm_topk_prob=True, scoring_func="sigmoid", n_group=1,
                  topk_group=1, n_shared_experts=None,
                  routed_scaling_factor=None)
    ref = _reference()
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.sparse_layer(jnp.asarray(x), weights, 0,
                                            config)[0])
    shares = [share(first, 8) for first in (0, 8, 16, 24)]
    np.testing.assert_allclose(sum(y for y, _ in shares)[valid],
                               uncut[valid], atol=2e-5)
    for y, _ in shares:                          # each share matters
        assert np.abs(y)[valid].max() > 0.05
    # [kept, dropped, elsewhere] of 13 real rows x top 8
    kept = [int(c[0]) for _, c in shares]
    assert sum(kept) == 13 * 8 and all(c[1] == 0 for _, c in shares)
    assert [int(c[2]) for _, c in shares] == [13 * 8 - n for n in kept]
    # the reference's share is the same sum
    third = np.asarray(ref.sparse_layer(
        jnp.asarray(x), weights, 0,
        dict(config, n_routed_experts=8, share={"first_expert": 16}))[0])
    np.testing.assert_allclose(shares[2][0][valid], third[valid], atol=2e-5)


# -- (e) through the engine --------------------------------------------------

def test_engine_greedy_tokens_equal_the_reference():
    """Two requests through ``ServingEngine``'s packed step, prefill and
    then decode: the longer passes the window and wraps its ring; the
    tokens are the reference's greedy choices."""
    cfg, _, params = _model()
    served = fc.serve_three(
        cfg, params, (), lengths=[LENGTH - 8, LENGTH - 4], new=[8, 4],
        block_size=BS, token_budget=BS, max_slots=2, max_blocks_per_seq=16)
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)
