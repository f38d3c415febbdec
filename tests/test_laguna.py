"""Laguna (full-attention and sliding-window layers of two head counts, a
per-head output gate, a dense first layer and expert layers of which a
share is held) through the model, the paged forward over the window-pool
cache, the ``swa_attention`` kernel and ``ServingEngine``, against the
benchmark's plain reference ``benchmarks/reference/laguna_f32.py``.

Tiny widths: hidden 64, five layers (full dense, three sliding sparse,
full sparse); four and six query heads of 16 over two K/V heads; a window
of 8 positions, two pool blocks of 4, so a slot's ring is three blocks and
wraps every 12 positions; 8 experts, top 3, of which the first 4 are
held; YaRN over the first 8 values of a head. The weights are seeded,
norm multipliers of order one.
"""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.engine import ServingEngine
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import laguna
from neuronx_distributed_tpu.modules import attention as attn_mod
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)

BS, WINDOW, RING = 4, 8, 3
#: the paged driver's pools: blocks of 4 under two sequences of 45
POOL = dict(num_blocks=40, max_blocks_per_seq=16)
LAYERS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = dict(
    model_type="laguna", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=5, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, max_position_embeddings=4096,
    attention_bias=False, rms_norm_eps=1e-6, num_experts=4,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False,
    gating="per-head", sliding_window=WINDOW,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=LAYERS, mlp_layer_types=["dense"] + ["sparse"] * 4,
    gating_types=["per_head"] * 5,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    moe_apply_router_weight_on_input=False, moe_routed_scaling_factor=2.5,
    moe_router_logit_softcapping=0, initializer_range=0.02,
    share={"num_experts_published": 8, "first_expert": 0},
    family="laguna", reference="laguna_f32")


def _family():
    return harness.load_plugin("families", "laguna")


def _reference():
    return harness.load_plugin("reference", "laguna_f32")


@fc.once_a_module
def _model(published=PUBLISHED, **kw):
    """The family's config from the published keys, its module and seeded
    weights."""
    cfg, model, _ = _family().build(
        published, **{"dtype": jnp.float32, "param_dtype": jnp.float32,
                      **kw})
    init = meta.unbox(model.init(jax.random.key(3),
                                 jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        # a router of order one, so that the choices are not all ties
        if "router" in name and not name.endswith("['scale']"):
            return 1.0 * noise

    return cfg, model, fc.seeded_weights(init, special)


def _reference_logits(params, tokens, published=PUBLISHED):
    weights = _family().published(params, published)
    return np.asarray(_reference().forward(weights, np.asarray(tokens),
                                           published)[0])


# -- (a) the rotary table, the layer pattern ---------------------------------

def test_yarn_inverse_frequencies_are_the_closed_form():
    """Laguna-S-2.1's numbers: 32 pairs over half a head, theta 500,000,
    factor 128 over 8,192 positions, the ramp from pair 9 to pair 18."""
    d, theta, factor, orig = 64, 5e5, 128.0, 8192
    lo = math.floor(d * math.log(orig / (32 * 2 * math.pi))
                    / (2 * math.log(theta)))
    hi = math.ceil(d * math.log(orig / (1 * 2 * math.pi))
                   / (2 * math.log(theta)))
    assert (lo, hi) == (9, 18)
    f = theta ** (-2 * np.arange(32) / d)
    r = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = f / factor * r + f * (1 - r)
    got = np.asarray(attn_mod.yarn_inv_freq(d, theta, factor, orig, 32, 1))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[9] == np.float32(f[9]) and got[18] == np.float32(f[18] / 128)
    ref, scale = _reference().inverse_frequencies(
        dict(rope_theta=theta, rope_type="yarn", factor=factor,
             original_max_position_embeddings=orig, beta_fast=32,
             beta_slow=1), d)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert scale == pytest.approx(1.4852030263919618)     # 0.1 ln 128 + 1
    cfg = laguna.LagunaConfig()
    cos, sin = cfg.rope_rows(jnp.asarray([0, 1000]))["full"]
    assert cos.shape == (2, 32)                   # half a head of 128
    np.testing.assert_allclose(
        np.asarray(cos[1]), np.cos(1000 * want) * 1.4852030263919618,
        atol=2e-4)
    cos, _ = cfg.rope_rows(jnp.asarray([0, 1000]))["sliding"]
    assert cos.shape == (2, 64)                   # the whole head


def test_the_layer_pattern_is_one_stack_a_kind_and_a_scan_a_run():
    cfg, _, params = _model()
    assert cfg.kinds() == ("full_dense", "sliding_sparse", "sliding_sparse",
                           "sliding_sparse", "full_sparse")
    assert cfg.runs() == (("full_dense", 0, 1), ("sliding_sparse", 0, 3),
                          ("full_sparse", 0, 1))
    assert cfg.pool_layers() == {"full_dense": [0], "sliding_sparse":
                                 [0, 1, 2], "full_sparse": [1]}
    assert cfg.carried()["sliding_sparse"] == ("wk", "wv", "moe_counts")
    tree = params["params"]["model"]
    q = {k: tree[f"layers_{k}"]["layer"]["attn"]["qkv"]["q_kernel"].shape
         for k in cfg.carried()}
    assert q == {"full_dense": (1, 64, 64), "sliding_sparse": (3, 64, 96),
                 "full_sparse": (1, 64, 64)}
    gate = tree["layers_sliding_sparse"]["layer"]["attn"]["g_proj"]["kernel"]
    assert gate.shape == (3, 64, 6)               # one gate a head
    moe = tree["layers_sliding_sparse"]["layer"]["moe"]
    assert moe["router"]["kernel"].shape == (3, 64, 8)    # all 8 scored
    assert moe["experts"]["down"].shape == (3, 4, 32, 64)  # 4 held
    assert "mlp" in tree["layers_full_dense"]["layer"]
    # the whole model's pattern: one period is four layers
    whole = laguna.LagunaConfig()
    assert whole.runs()[:3] == (("full_dense", 0, 1),
                                ("sliding_sparse", 0, 3),
                                ("full_sparse", 0, 1))
    assert (whole.heads_of("full"), whole.heads_of("sliding")) == (48, 72)
    with pytest.raises(ValueError, match="one head count"):
        laguna.tiny_config(heads_per_layer=(4, 6, 6, 8, 4))
    with pytest.raises(ValueError, match="experts_held"):
        laguna.tiny_config(experts_held=(6, 4))


# -- (b) the model and the paged forward against the reference ---------------

def test_full_forward_matches_the_reference():
    cfg, model, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 45))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    want = _reference_logits(params, tokens)
    assert np.std(want) > 0.05
    np.testing.assert_allclose(got, want, atol=3e-4 * np.std(want))


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_prefill_then_decode_matches_the_reference(impl):
    """45 positions pass the window five times and wrap the ring of 12
    three times; the second sequence's chunks straddle blocks beside the
    first's decode row."""
    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    seqs = np.random.RandomState(2).randint(0, 256, (2, 45))
    got, cache = fc.paged_logits(
        cfg, params, seqs, fc.schedule(45, [3, 4, 2, 1, 4, 4], BS), BS,
        **POOL)
    want = _reference_logits(params, seqs)
    assert len(got) == 90
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=3e-4 * np.std(want), err_msg=(s, p))
    # two pools: every position of two sequences in the full layers',
    # a ring of three blocks a table row in the sliding layers'
    assert cache.k.shape == (2, 40, BS, 2, 16)
    assert cache.wk.shape == (3, 3 * RING, BS, 2, 16)
    assert cache.window_ring == RING
    held = np.asarray(cache.wpos[:2 * RING]).reshape(2, -1)
    assert (np.sort(held, axis=1) == np.arange(45 - 12, 45)).all()
    assert (np.asarray(cache.wpos[2 * RING:]) == PAD_POSITION).all()
    assert int((np.asarray(cache.pos) < PAD_POSITION).sum()) == 90
    # kept, dropped, elsewhere of the last step's one row: 4 sparse
    # layers of top 3
    counts = np.asarray(cache.moe_counts)
    assert counts.sum() == 4 * 3 and counts[1] == 0 and counts[2] > 0


def test_a_window_off_by_a_block_or_a_bf16_router_fails_the_comparison(
        monkeypatch):
    """What the comparison must not pass: the sliding layers attending a
    block more, and the router's scores rounded to bfloat16."""
    import dataclasses

    cfg, _, params = _model()
    seqs = np.random.RandomState(2).randint(0, 256, (1, 30))
    steps = fc.schedule(30, [4] * 7 + [2], BS)[:8]
    want = _reference_logits(params, seqs)

    def worst(cfg, **kw):
        got, _ = fc.paged_logits(cfg, params, seqs, steps, BS, **POOL, **kw)
        return fc.worst_at(got, want)

    assert worst(cfg) < 3e-4
    assert worst(dataclasses.replace(cfg, sliding_window=WINDOW + BS)) > 0.05
    from neuronx_distributed_tpu.modules.moe import routing

    logits = routing.RouterBase.logits
    monkeypatch.setattr(
        routing.RouterBase, "logits",
        lambda self, x: logits(self, x).astype(jnp.bfloat16).astype(
            jnp.float32))
    assert worst(cfg, fresh=True) > 3e-3


# -- (c) the window, exact at its edge, in both implementations --------------

def _ring_case(n, kv, d, window, bs, lengths, rows, seed=0):
    """A sliding layer's pool after ``lengths`` positions a slot, and
    packed rows ``(slot, position)``: ``(q, k_pool, v_pool, pos, tables,
    q_pos, dense)`` with ``dense(slot) -> (K, V) [length, kv, d]``."""
    ring = window // bs + 1
    rng = np.random.RandomState(seed)
    slots = len(lengths)
    ks = [rng.randn(m, kv, d).astype(np.float32) for m in lengths]
    vs = [rng.randn(m, kv, d).astype(np.float32) for m in lengths]
    k_pool = np.zeros((2, slots * ring, bs, kv, d), np.float32)
    v_pool = np.zeros_like(k_pool)
    pos = np.full((slots * ring, bs), PAD_POSITION, np.int32)
    for s, m in enumerate(lengths):
        for p in range(m):                       # later laps overwrite
            b = s * ring + (p // bs) % ring
            k_pool[1, b, p % bs], v_pool[1, b, p % bs] = ks[s][p], vs[s][p]
            pos[b, p % bs] = p
    slot_ids = np.array([s for s, _ in rows] + [slots], np.int32)
    q_pos = np.array([p for _, p in rows] + [PAD_POSITION], np.int32)
    tables, _ = paging.ring_write_indices(
        jnp.asarray(slot_ids), jnp.asarray(q_pos), bs, ring, slots)
    q = rng.randn(len(rows) + 1, n, d).astype(np.float32)
    return (q, k_pool, v_pool, pos, np.asarray(tables), q_pos,
            lambda s: (ks[s], vs[s]))


def _dense_window(q, k, v, t, window):
    """Row ``q [n, d]`` at position ``t`` over ``k, v [S, kv, d]``: the
    positions ``t - window < j <= t`` and no others."""
    n, kv = q.shape[0], k.shape[1]
    lo = max(t - window + 1, 0)
    kk = np.repeat(k[lo:t + 1], n // kv, axis=1)
    vv = np.repeat(v[lo:t + 1], n // kv, axis=1)
    s = np.einsum("nd,jnd->nj", q, kk) / math.sqrt(q.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nj,jnd->nd", p / p.sum(-1, keepdims=True), vv)


@pytest.mark.parametrize("force", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("n_rep", [3, 9])
def test_the_window_is_exact_at_its_edge(force, n_rep):
    """Decode rows and a chunk that straddles blocks, at ``n_rep`` 9 (72
    heads over 8) as at 3: position ``t - window`` is out and ``t - window
    + 1`` is in, whatever the ring's stale rows hold."""
    kv, d, window, bs = 2, 16, 8, 4
    lengths = [31, 23, 5]
    rows = [(0, 30), (2, 4)] + [(1, p) for p in range(18, 23)]
    q, k_pool, v_pool, pos, tables, q_pos, dense = _ring_case(
        kv * n_rep, kv, d, window, bs, lengths, rows)
    out = np.asarray(pa.paged_attention(
        *map(jnp.asarray, (q, k_pool, v_pool, pos, tables, q_pos)), 1,
        force_pallas=force, sliding=window))
    for i, (s, t) in enumerate(rows):
        np.testing.assert_allclose(
            out[i], _dense_window(q[i], *dense(s), t, window), atol=2e-5,
            err_msg=(s, t))
    if force:
        assert not out[-1].any()                 # the kernel's pad row
    # the edge: a key at t - window moves nothing, one at t - window + 1
    # moves the row
    for at, moves in ((30 - window, False), (30 - window + 1, True)):
        k2 = k_pool.copy()
        k2[1, (at // bs) % (window // bs + 1), at % bs] += 3.0
        moved = np.asarray(pa.paged_attention(
            *map(jnp.asarray, (q, k2, v_pool, pos, tables, q_pos)), 1,
            force_pallas=force, sliding=window))
        assert (np.abs(moved[0] - out[0]).max() > 1e-3) == moves, at
        np.testing.assert_array_equal(moved[1:-1], out[1:-1])


def test_the_walk_lists_a_rows_window_columns_and_no_others():
    """``tile_walk`` with ``sliding``: of a row's ring the columns that
    hold its window, each once a tile; a ring's other columns, a pad row
    and a row without a slot give no pair."""
    bs, window, ring = 4, 8, 3
    slot_ids = jnp.asarray([0, 1, 1, 1, 5], jnp.int32)
    q_pos = jnp.asarray([30, 6, 7, 8, PAD_POSITION], jnp.int32)
    tables, write = paging.ring_write_indices(slot_ids, q_pos, bs, ring, 2)
    assert np.asarray(tables).tolist() == [
        [0, 1, 2], [3, 4, 5], [3, 4, 5], [3, 4, 5], [-1, -1, -1]]
    # position p in ring column (p // 4) % 3, slot p % 4
    assert np.asarray(write).tolist() == [
        (0 + 7 % 3) * 4 + 2, (3 + 1) * 4 + 2, (3 + 1) * 4 + 3,
        (3 + 2) * 4 + 0, 2 * 3 * 4]
    live = np.asarray(pa.sliding_column_live(
        np.asarray(tables), np.arange(ring), np.asarray(q_pos)[:, None], bs,
        window, ring))
    assert live.tolist() == [
        [True, True, True],        # 23..30: blocks 5, 6, 7
        [True, True, False],       # 0..6: blocks 0, 1
        [True, True, False],       # 0..7
        [True, True, True],        # 1..8: blocks 0, 1, 2
        [False, False, False]]
    walk = pa.tile_walk(tables, q_pos, bs, 6, 9, sliding=window)
    assert pa.tile_rows(9, 5) == 8 and pa.tile_rows(6, 128) == 20
    assert int(walk.count[0]) == 3 + 3           # slot 0's, slot 1's
    assert np.asarray(walk.q_lo)[0, ::9, 0][:4].tolist() == [23, -1, 0, 1]
    assert walk.served.shape == (1, 72, 3)


@pytest.mark.parametrize("run", [2, 4])
@pytest.mark.parametrize("n_rep", [6, 9])
def test_a_rings_columns_ride_in_runs(n_rep, run):
    """The walk of a sliding layer's rings cut into the kernel's units
    (``tests/walk_checks.py``): at 6 and 9 heads a row's group is 16
    stacked rows from the sublane its first head lies in, and the units
    are keyed by that."""
    from walk_checks import check_paged_runs

    bs, window, ring = 4, 8, 3
    slot_ids = jnp.asarray([0, 1, 1, 1, 5, 2, 3, 4, 6], jnp.int32)
    q_pos = jnp.asarray([30, 6, 7, 8, PAD_POSITION, 11, 3, 40, 9], jnp.int32)
    tables, _ = paging.ring_write_indices(slot_ids, q_pos, bs, ring, 7)
    live = np.asarray(pa.sliding_column_live(
        np.asarray(tables), np.arange(ring), np.asarray(q_pos)[:, None], bs,
        window, ring))
    kinds = check_paged_runs(tables, q_pos, live, bs, 21, n_rep, run,
                             sliding=window)
    # slot 1's three rows share their ring's three live columns
    assert kinds[0] > 0 and kinds.sum() == 3 + 3 + 2 + 1 + 3 + 3


# -- (d) the share of the experts ties to the model --------------------------

def test_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """A sparse layer's routed sums as two devices of four experts hold
    them, added, with the shared expert counted once, equal the plain
    reference's uncut layer over all eight; ``elsewhere`` counts what the
    other share keeps."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=8, hidden_size=64, intermediate_size=32,
                   top_k=3, capacity_factor=None, router_scale=2.5,
                   shared_expert_intermediate=32, held=held,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    whole = meta.unbox(moe((0, 8)).init(jax.random.key(1), jnp.asarray(x),
                                        valid=jnp.asarray(valid)))
    whole = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)

    def share(first, count):
        p = jax.tree_util.tree_map(lambda w: w, whole)
        p["params"]["experts"] = {
            k: w[first:first + count]
            for k, w in whole["params"]["experts"].items()}
        with jax.default_matmul_precision("highest"):
            y, aux = moe((first, count)).apply(p, jnp.asarray(x),
                                               valid=jnp.asarray(valid))
        return np.asarray(y), np.asarray(aux["assignments"])

    ref = _reference()
    tree = whole["params"]

    def weights(name, layer=None, expert=None):
        if name == "router":
            return np.asarray(tree["router"]["kernel"]).T
        node = tree["shared"] if name.startswith("shared_") else {
            k: v[expert] for k, v in tree["experts"].items()}
        name = name.removeprefix("shared_")
        leaf = {k: v for k, v in harness.load_plugin(
            "families", "llama")._leaves(node).items() if name in k}
        (w,) = leaf.values()
        return np.asarray(w).T

    config = dict(num_experts=8, num_experts_per_tok=3, norm_topk_prob=True,
                  moe_routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.sparse_layer(jnp.asarray(x), weights, 0,
                                            config)[0])
        shared = np.asarray(ref.swiglu(
            jnp.asarray(x), weights("shared_gate"), weights("shared_up"),
            weights("shared_down")))
    (a, count_a), (b, count_b) = share(0, 4), share(4, 4)
    np.testing.assert_allclose((a + b - shared)[valid], uncut[valid],
                               atol=2e-5)
    assert np.abs(a - shared)[valid].max() > 0.05    # each share matters
    all_of_it, count = share(0, 8)
    np.testing.assert_allclose(all_of_it[valid], uncut[valid], atol=2e-5)
    # [kept, dropped, elsewhere] of 13 real rows x top 3
    assert count.tolist() == [39, 0, 0]
    assert count_a[0] + count_b[0] == 39 and count_a[1] == count_b[1] == 0
    assert (count_a[2], count_b[2]) == (count_b[0], count_a[0])
    # the reference's share is the same sum
    half = np.asarray(ref.sparse_layer(
        jnp.asarray(x), weights, 0,
        dict(config, num_experts=4, share={"first_expert": 4}))[0])
    np.testing.assert_allclose(b[valid], half[valid], atol=2e-5)


def test_held_dispatch_is_the_dispatch_over_the_held_experts():
    """The held experts' masks equal the all-experts masks' slice when no
    expert overflows, pad rows and choices elsewhere take no slot, and an
    expert past its capacity drops its last comers."""
    from neuronx_distributed_tpu.modules.moe import expert_mlps as em

    rng = np.random.RandomState(3)
    idx = np.stack([rng.permutation(8)[:3] for _ in range(12)])
    gates = rng.rand(12, 3).astype(np.float32)
    valid = np.arange(12) < 10
    every = em.build_dispatch_combine(jnp.asarray(gates), jnp.asarray(idx),
                                      8, 12, jnp.asarray(valid))
    held = em.build_dispatch_combine(jnp.asarray(gates), jnp.asarray(idx),
                                     4, 12, jnp.asarray(valid), held=(2, 4))
    for whole, part in zip(every[:2], held[:2]):
        np.testing.assert_allclose(np.asarray(whole)[:, 2:6],
                                   np.asarray(part), atol=1e-6)
    assert float(held[2]) == 0.0
    tight = em.build_dispatch_combine(jnp.asarray(gates), jnp.asarray(idx),
                                      4, 2, jnp.asarray(valid), held=(2, 4))
    mine = ((idx >= 2) & (idx < 6) & valid[:, None]).sum()
    kept = float(np.asarray(tight[0]).sum())
    assert kept == np.minimum(
        [((idx == e) & valid[:, None]).sum() for e in range(2, 6)], 2).sum()
    assert float(tight[2]) == pytest.approx(1 - kept / mine)


# -- (e) the engine: the ring stays a slot's, preemption, counters -----------

#: over ``family_checks.engine_config``: blocks and steps of 4 rows
ENGINE = dict(block_size=BS, num_blocks=64, max_blocks_per_seq=48,
              token_budget=BS)


@pytest.fixture(scope="module")
def served():
    """Three requests through one engine of two slots whose full pool
    holds 52 blocks: ``a`` grows to 160 positions (40 blocks), so ``b`` is
    preempted on the way and re-admitted into a slot whose ring another
    request left."""
    cfg, _, params = _model()

    def ring_blocks(eng):
        return int((np.asarray(eng.cache.wpos).reshape(
            2, RING, BS) < PAD_POSITION).any(-1).sum(-1).max())

    return fc.serve_three(cfg, params, (
        "nxd_window_columns_total", "nxd_kv_blocks_held_total",
        "nxd_moe_held_total", "nxd_moe_assignments_total",
        "nxd_paged_columns_total", "nxd_paged_pairs_total",
        "nxd_paged_shared_pairs_total", "nxd_paged_block_visits_total"),
        lengths=[130, 40, 5], new=[30, 25, 4], watch=ring_blocks,
        **dict(ENGINE, num_blocks=52, max_slots=2))


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_slots_ring_stays_bounded_and_survives_preemption(served):
    """Over a context of 40 blocks a slot's window blocks stay the ring's
    three, reused lap after lap; the full pool's are released whole; the
    preempted request decodes what the reference does (above) from a ring
    that the host never cleared."""
    eng, ring_blocks = served.eng, served.seen
    assert max(ring_blocks) == RING and ring_blocks[-1] == RING
    fc.check_preempted_and_whole(eng)
    kind = eng._cache_kind
    assert isinstance(kind, paging.WindowPoolCache) and kind.ring is None
    assert (kind.full_layers, kind.window_layers, kind.window) == (2, 3, 8)
    assert kind.window_ring(BS) == RING
    assert kind.blocks_for(160, BS) == 40        # the full layers' grow
    assert eng.cache.wk.shape == (3, 2 * RING, BS, 2, 16)
    assert eng.cache.k.shape == (2, 52, BS, 2, 16)
    with pytest.raises(ValueError, match="token_budget"):
        kind.geometry(BS, BS + 1)
    with pytest.raises(ValueError, match="whole blocks"):
        kind.geometry(3)


def test_window_counters(served):
    counters = served.counters
    cols = counters["nxd_window_columns_total"]
    assert set(cols) == {"live", "behind"}
    # a row's window is two or three of the columns it has mapped: of a
    # context that reaches 40 blocks, a small share
    assert 0 < cols["live"] < 0.35 * (cols["live"] + cols["behind"])
    paged = counters["nxd_paged_columns_total"]
    assert paged["live"] == cols["live"] + cols["behind"]
    held = counters["nxd_kv_blocks_held_total"]
    # times the layers that hold them: two full, three sliding
    assert held["full"] % 2 == 0 and held["window"] % 3 == 0
    assert 0 < held["window"] < held["full"]
    moe = counters["nxd_moe_held_total"]
    kept = counters["nxd_moe_assignments_total"]
    assert kept["dropped"] == 0
    assert moe["held"] == kept["kept"] and moe["elsewhere"] > 0
    assert (moe["held"] + moe["elsewhere"]) % (4 * 3) == 0


def test_no_decode_rows_pair_runs_over_the_whole_tile(served):
    """The full layers' pairs a step by how the kernel computes them: a
    pair that one packed row names is narrow whatever the row's place
    (here a chunk's too: its four rows of two heads lie in one group of
    8), and with the shared ones they are the pairs fetched."""
    counters = served.counters
    pairs = counters["nxd_paged_pairs_total"]
    assert set(pairs) == {"narrow", "one_row_whole"}
    assert pairs["narrow"] > 0 and pairs["one_row_whole"] == 0
    shared = counters["nxd_paged_shared_pairs_total"][""]
    assert pairs["narrow"] + shared == counters[
        "nxd_paged_block_visits_total"]["fetched"]


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES)
def test_refused_features_raise_by_name(feature, kw):
    cfg, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, **ENGINE)


def test_session_export_is_refused_and_a_step_wider_than_a_block():
    cfg, _, params = _model()
    fc.check_session_export_is_refused(
        cfg, params, paging.WindowPoolPagedCache, **ENGINE)
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(cfg, params, fc.engine_config(
            **dict(ENGINE, token_budget=2 * BS)))


def test_the_kernel_alone_serves_the_pools_on_a_tpu(monkeypatch):
    """On a TPU a pool that does not tile for the kernel is an error for
    this family, not the XLA gather."""
    cfg, _, params = _model()
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    pa.paged_attention_impl.cache_clear()
    try:
        assert pa.paged_attention_impl(128, 128, None,
                                       kernel_only=True) == "pallas"
        with pytest.raises(ValueError, match="don't tile"):
            fc.paged_logits(cfg, params, np.zeros((1, 4), np.int64),
                            [[(0, 0)]], BS, fresh=True, **POOL)
    finally:
        pa.paged_attention_impl.cache_clear()
