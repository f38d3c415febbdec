"""DeepSeek-V3.2 (the latent family's attention under YaRN over the
positions a learned indexer selects, a dense layer then expert layers
under a sigmoid router limited by groups, a share of the experts held and
a shared expert) through the model, the paged forward over the indexed
latent cache and ``ServingEngine``, against the benchmark's plain
reference ``benchmarks/reference/deepseek_v32_f32.py`` (``kv_b``
expanded, the index scores and the selection over the whole sequence, an
expert at a time).

Tiny widths with every mechanism on: hidden 64, 4 heads of 24 + 8 query
values over a latent of 32 and a rotary key of 8 (a pool row of 128
lanes), values of 16, 2 index heads of 16 that keep 8 positions of
contexts to 62, YaRN by 8 over 16 positions, one dense layer (160) and two
expert layers of 16 experts (32 wide) in 4 groups of which 2 stay, top 3
times 2.5, of which this device holds experts 4 to 11, a shared expert,
pool blocks of 16. The weights are seeded with norm multipliers of order
one, a selection bias that changes the choice and an index key's
LayerNorm bias that moves the selection.
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

from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.engine import ServingEngine
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import deepseek_v32 as ds
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.modules.moe.routing import RouterSigmoid
from neuronx_distributed_tpu.ops import indexed_attention as ia
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from deepseek_v32_faults import FAULTS  # noqa: E402  (tests/)
from runners import serve  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BS = 16
STD = 0.08
TOP = 8
PUBLISHED = dict(
    model_type="deepseek_v32", vocab_size=256, hidden_size=64,
    intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=TOP,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=3,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    hidden_act="silu", attention_bias=False, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=4, topk_group=2, moe_layer_freq=1,
    ep_size=1, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=8, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=16, mscale=1,
                      mscale_all_dim=1),
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    max_position_embeddings=4096, num_nextn_predict_layers=0,
    initializer_range=STD, family="deepseek_v32",
    reference="deepseek_v32_f32",
    share={"n_routed_experts_published": 16, "first_expert": 4})
SOUND = 3e-5        # of the logits' spread: float32 against float32


def _family():
    return harness.load_plugin("families", "deepseek_v32")


@fc.once_a_module
def _model(**kw):
    cfg, model, forward = _family().build(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if name.endswith("['router']['bias']"):
            return 0.2 * noise          # of the scores' own spread

    return cfg, model, forward, fc.seeded_weights(shapes, special)


def _ref():
    return harness.load_plugin("reference", "deepseek_v32_f32")


def _reference(params):
    return _ref(), _family().published(params, PUBLISHED)


def _full(model, params, tokens):
    """The module's whole forward, the index key's bias as the family
    serves it."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(
            _family().with_seeded_key_bias(params, STD),
            jnp.asarray(tokens)))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


def _tokens(seed, shape):
    return np.random.RandomState(seed).randint(0, 256, shape)


# -- the module's full forward ----------------------------------------------

def test_the_parameters_are_glms_and_an_indexer_a_layer():
    cfg, _, _, params = _model()
    assert cfg.experts_held == (4, 8) and cfg.num_experts == 16
    for kind, depth in (("dense", 1), ("moe", 2)):
        attn = params["params"]["model"][f"layers_{kind}"]["layer"]["attn"]
        assert {k: v.shape for k, v in attn.items()
                if k.startswith("index_") and k != "index_k_norm"} == {
            "index_q_b": (depth, 48, 32), "index_k": (depth, 64, 16),
            "index_w": (depth, 64, 2)}
        assert {k: v.shape for k, v in attn["index_k_norm"].items()} == {
            "scale": (depth, 16), "bias": (depth, 16)}
    moe = params["params"]["model"]["layers_moe"]["layer"]["moe"]
    assert moe["router"]["kernel"].shape == (2, 64, 16)
    assert "shared" in moe


def test_full_forward_matches_the_reference_with_every_mechanism_on():
    cfg, model, _, params = _model()
    tokens = _tokens(3, (2, 62))
    ref, weights = _reference(params)
    sets = []
    want, margins = ref.forward(weights, tokens, PUBLISHED, selections=sets)
    assert fc.worst(_full(model, params, tokens), want) < SOUND
    assert margins.shape == (2, 2, 62) and float(margins.min()) > 0
    # every row past the eighth selects: 8 of its causal positions
    assert len(sets) == 2 * 3
    for keep in sets:
        assert (keep.sum(-1) == np.minimum(np.arange(62) + 1, TOP)).all()
        assert not np.triu(keep, 1).any()
    # and the selection is no window and no prefix: it moves with the row
    assert len({tuple(np.flatnonzero(row)) for row in sets[1][40:]}) > 10


def test_positions_are_honoured_by_the_reference():
    _, _, _, params = _model()
    tokens = _tokens(4, (1, 30))
    ref, weights = _reference(params)
    whole = ref.forward(weights, tokens, PUBLISHED)[0]
    some = ref.forward(weights, tokens, PUBLISHED, positions=[3, 17, 29])[0]
    np.testing.assert_allclose(some, whole[:, [3, 17, 29]], atol=1e-5)


def test_every_published_key_is_read_or_refused():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r["config"] for r in map(json.loads, f)
                      if r["name"] == "DeepSeek-V3.2"]
        assert set(row) == ds.PUBLISHED_KEYS
        with pytest.raises(ValueError, match="num_nextn_predict_layers"):
            ds.DeepseekV32Config.from_published(row)
        cfg = ds.DeepseekV32Config.from_published(
            dict(row, num_nextn_predict_layers=0))
        assert cfg == ds.DeepseekV32Config()
        assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
                cfg.top_k, cfg.n_group, cfg.topk_group, cfg.index_n_heads,
                cfg.index_head_dim, cfg.index_topk, cfg.head_dim_,
                cfg.yarn_factor) == (61, 3, 256, 8, 8, 4, 64, 128, 2048,
                                     640, 40.0)
        assert abs(cfg.score_scale - 0.13524) < 1e-5
    assert ds.PUBLISHED_KEYS <= set(PUBLISHED)
    for key, value in (("model_type", "deepseek_v3"),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("moe_layer_freq", 2), ("norm_topk_prob", False),
                       ("scoring_func", "softmax"),
                       ("topk_method", "greedy"),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("num_key_value_heads", 2), ("rope_scaling", None)):
        with pytest.raises(ValueError, match=key):
            _family().build({**PUBLISHED, key: value})
    for wrong, said in ((dict(experts_held=(12, 8)), "experts_held"),
                        (dict(index_head_dim=4), "qk_rope_head_dim"),
                        (dict(index_topk=0), "index_topk"),
                        (dict(first_k_dense=5), "first_k_dense")):
        with pytest.raises(ValueError, match=said):
            ds.tiny_config(**wrong)


# -- the router's groups ------------------------------------------------------

def _router(**kw):
    ps.initialize_model_parallel()
    router = RouterSigmoid(num_experts=16, top_k=3, scale=2.5,
                           dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    x = jnp.asarray(np.random.RandomState(2).randn(40, 64), jnp.float32)
    kernel = 0.3 * jax.random.normal(jax.random.key(1), (64, 16))
    bias = 0.2 * jax.random.normal(jax.random.key(2), (16,))
    params = {"params": {"kernel": kernel, "bias": bias}}
    return router, params, x


def test_one_group_is_the_router_without_groups_to_the_bit():
    router, params, x = _router()
    gates, idx, _ = router.apply(params, x)
    # the router as it was before groups: the choice by s + b, the weights
    # by s over the chosen, times the scale
    scores = jax.nn.sigmoid(jnp.dot(x, params["params"]["kernel"]))
    _, want_idx = jax.lax.top_k(scores + params["params"]["bias"], 3)
    want = jnp.take_along_axis(scores, want_idx, axis=-1)
    want = want / (jnp.sum(want, axis=-1, keepdims=True) + 1e-20) * 2.5
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(gates) == np.asarray(want)).all()
    same, same_idx, _ = _router(n_group=1, topk_group=1)[0].apply(params, x)
    assert (np.asarray(same) == np.asarray(gates)).all()
    assert (np.asarray(same_idx) == np.asarray(idx)).all()


def test_groups_limit_the_choice_as_the_reference_does():
    router, params, x = _router(n_group=4, topk_group=2)
    gates, idx, _ = router.apply(params, x)
    plain = _router()[0].apply(params, x)[1]

    def weights(name, layer=None, expert=None):
        return np.asarray(params["params"][
            "bias" if name.endswith("bias") else "kernel"]).T

    chosen, picked, margin = _ref().route(x, weights, 0, dict(
        PUBLISHED, n_routed_experts=16))
    assert (np.asarray(idx) == np.asarray(chosen)).all()
    np.testing.assert_allclose(gates, picked, rtol=1e-6)
    # a row's experts lie in two groups at most, and the limit bites
    assert max(len(set(row // 4)) for row in np.asarray(idx)) <= 2
    assert (np.asarray(idx) != np.asarray(plain)).any()
    assert float(margin.min()) > 0
    for wrong in (dict(n_group=3, topk_group=1), dict(n_group=16),
                  dict(n_group=4, topk_group=5)):
        with pytest.raises(ValueError, match="RouterSigmoid"):
            _router(**{"topk_group": 1, **wrong})[0].apply(params, x)
    with pytest.raises(ValueError, match="n_group"):
        MoE(num_experts=16, hidden_size=64, intermediate_size=32,
            router_type="top_k", n_group=4).init(jax.random.key(0), x)


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """An expert layer's output as the shares of a deployment hold it
    (four of four experts at the toy's sixteen), the routed sums added and
    the shared expert counted once, is the reference's uncut layer."""
    ps.initialize_model_parallel()
    x = np.random.RandomState(7).randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=16, hidden_size=64, intermediate_size=32,
                   top_k=3, capacity_factor=None, router_type="sigmoid",
                   router_scale=2.5, n_group=4, topk_group=2, held=held,
                   shared_expert_intermediate=32, dtype=jnp.float32,
                   param_dtype=jnp.float32)

    tree = meta.unbox(moe((0, 16)).init(
        jax.random.key(1), jnp.asarray(x), valid=jnp.asarray(valid)))
    tree = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        tree)["params"]
    llama = harness.load_plugin("families", "llama")

    def share(first, count):
        p = {"params": dict(tree, experts={
            k: w[first:first + count] for k, w in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = moe((first, count)).apply(p, jnp.asarray(x),
                                               valid=jnp.asarray(valid))
        return np.asarray(y), np.asarray(aux["assignments"])

    def weights(name, layer=None, expert=None):
        if name == "mlp.gate":
            return np.asarray(tree["router"]["kernel"]).T
        if name == "mlp.gate.e_score_correction_bias":
            return np.asarray(tree["router"]["bias"])
        group, _, tensor = name.rpartition(".")
        node = (tree["shared"] if group == "mlp.shared_experts"
                else {k: v[expert] for k, v in tree["experts"].items()})
        (w,) = [v for k, v in llama._leaves(node).items()
                if tensor.removesuffix("_proj") in k.split("/")[0].split("_")]
        return np.asarray(w).T

    config = dict(PUBLISHED, n_routed_experts=16)
    config.pop("share")
    ref = _ref()
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.expert_layer(jnp.asarray(x), weights, 0,
                                            config)[0])
        shared = np.asarray(ref.swiglu(jnp.asarray(x), *(
            weights(f"mlp.shared_experts.{p}_proj") for p in
            ("gate", "up", "down"))))
        third = np.asarray(ref.expert_layer(
            jnp.asarray(x), weights, 0,
            dict(config, n_routed_experts=4, share={"first_expert": 8}))[0])
    shares = [share(first, 4) for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(
        (sum(y for y, _ in shares) - 3 * shared)[valid], uncut[valid],
        atol=3e-5)
    np.testing.assert_allclose(shares[2][0][valid], third[valid], atol=3e-5)
    kept = [int(c[0]) for _, c in shares]
    assert sum(kept) == 13 * 3 and all(c[1] == 0 for _, c in shares)
    assert [int(c[2]) for _, c in shares] == [13 * 3 - n for n in kept]


# -- the selection ------------------------------------------------------------

def test_equal_scores_take_the_lower_position_and_a_short_row_keeps_all():
    inf = -np.inf
    scores = jnp.asarray([[1., 3., 3., 2., 3., inf, inf, inf],
                          [5., 1., inf, inf, inf, inf, inf, inf],
                          [inf] * 8], jnp.float32)
    positions, chosen, member = ia.select_positions(scores, 3)
    assert positions[0].tolist() == [1, 2, 4]       # not 3, nor 0
    assert chosen.tolist() == [[True] * 3, [True, True, False], [False] * 3]
    assert positions[1][:2].tolist() == [0, 1]      # ascending
    assert member.tolist() == [
        [False, True, True, False, True, False, False, False],
        [True, True] + [False] * 6, [False] * 8]
    # where the last chosen value is tied beyond the cut, the lower stay
    tied = jnp.asarray([[2., 2., 2., 2., 1., inf, inf, inf]], jnp.float32)
    picked = ia.select_positions(tied, 3)
    assert picked.member.tolist() == [[True, True, True] + [False] * 5]
    assert picked.positions.tolist() == [[0, 1, 2]]
    # k is held to the table's width
    assert ia.select_positions(scores, 2048)[0].shape == (3, 8)


def _rows_of_every_length(rng, rows, width, draw):
    scores = draw((rows, width)).astype(np.float32)
    scores[np.arange(width)[None, :] > rng.randint(0, width, (rows, 1))] = \
        -np.inf
    scores[0], scores[1, 3:] = -np.inf, -np.inf
    return scores


def _few_values(rng):
    return lambda shape: rng.randint(0, 7, shape)


def _signed_zeros(rng):
    """Zeros of both signs (one value to the comparison) among negative
    and positive scores."""
    def draw(shape):
        return rng.choice(np.asarray([0.0, -0.0, -1.5, 2.0, -0.25],
                                     np.float32), shape)

    return draw


def _tied_at_the_cut(rng):
    """A few scores above, and far more than ``top`` equal ones below
    them: the cut falls among the equal ones in every long row."""
    return lambda shape: np.where(rng.rand(*shape) < 0.02,
                                  rng.randn(*shape) + 9.0, 1.0)


def _normal(rng):
    return rng.standard_normal


@pytest.mark.parametrize("width,top,rows,draw", [
    (96, 8, 12, _few_values), (100, 8, 12, _few_values),
    (66, 16, 12, _few_values), (64, 8, 12, _few_values),
    (5, 8, 3, _few_values),                       # narrower than ``top``
    (300, 40, 12, _tied_at_the_cut),              # no multiple of 128
    (4097, 300, 9, _tied_at_the_cut), (200, 16, 12, _signed_zeros),
    (1024, 128, 10, _signed_zeros), (1300, 128, 10, _normal),
    (33000, 2048, 4, _few_values),                # table numbers past 255
    (66560, 2048, 3, _normal)])                   # the cell's own
def test_the_selection_by_counting_selects_what_a_sort_selects(
        width, top, rows, draw):
    """Many equal scores, zeros of both signs, rows of every length, an
    all-``-inf`` row: the set, its mask and the tie at the last place are
    ``lax.top_k``'s over the whole row, the positions ascending.
    (``lax.top_k`` orders ``-0.0`` below ``+0.0`` where the comparison,
    the reference's stable sort and this selection tie them: the oracle
    reads the scores with their zeros made one.)"""
    rng = np.random.RandomState(width + top)
    scores = _rows_of_every_length(rng, rows, width, draw(rng))
    k = min(top, width)
    want_values, want = map(np.asarray, jax.lax.top_k(
        jnp.where(jnp.asarray(scores) == 0, 0.0, jnp.asarray(scores)), k))
    positions, chosen, member = map(np.asarray, jax.jit(
        lambda s: ia.select_positions(s, top))(jnp.asarray(scores)))
    assert positions.shape == chosen.shape == (rows, k)
    assert not chosen[0].any() and not member[0].any()
    for r in range(rows):
        kept = sorted(want[r][want_values[r] > -np.inf].tolist())
        assert positions[r][chosen[r]].tolist() == kept, r
        assert np.flatnonzero(member[r]).tolist() == kept, r
        assert chosen[r][:len(kept)].all() and chosen[r].sum() == len(kept)


def _scene(name):
    """Tables and rows of a packed step of 24 rows over 3 slots of 4
    columns of 8 positions."""
    slots, maxb = 3, 4
    tabs = np.full((slots, maxb), -1, np.int32)
    tabs[0, :3], tabs[1, :2], tabs[2, :4] = [3, 5, 7], [1, 2], [4, 6, 9, 10]
    pad = [(slots, PAD_POSITION)]
    rows = {
        "a_chunk_beside_decode_rows":
            [(1, 9), (2, 30)] + [(0, p) for p in range(10, 20)] + pad * 12,
        "two_chunks_and_pads":
            [(0, p) for p in range(5, 17)] + [(2, p) for p in range(20, 27)]
            + pad * 5,
        "decode_rows_alone": [(0, 23), (1, 15), (2, 31)] + pad * 21,
        "slots_that_share_a_prefix_block":
            [(0, p) for p in range(8, 14)] + [(1, p) for p in range(8, 14)]
            + pad * 12,
        "pads_alone": pad * 24,
    }[name]
    if name == "slots_that_share_a_prefix_block":
        tabs[1, 0] = tabs[0, 0]
    slot, pos = map(np.asarray, zip(*rows))
    return (jnp.asarray(tabs[np.minimum(slot, slots - 1)]),
            jnp.asarray(pos, jnp.int32), slots)


@pytest.mark.parametrize("scene", [
    "a_chunk_beside_decode_rows", "two_chunks_and_pads",
    "decode_rows_alone", "slots_that_share_a_prefix_block", "pads_alone"])
def test_index_kernel_in_interpret_mode_equals_the_gathered_scores(scene):
    tables, q_pos, slots = _scene(scene)
    rng = np.random.RandomState(5)
    keys = jnp.asarray(rng.randn(2, 12, 8, 16), jnp.float32)
    q = jnp.asarray(rng.randn(24, 2, 16), jnp.float32)
    w = jnp.asarray(rng.randn(24, 2), jnp.float32)
    want = ia.index_scores(q, w, keys, 1, tables, q_pos, 0.25, slots,
                           force_pallas=False)
    got = jax.jit(lambda *a: ia.index_scores(
        *a, 0.25, slots, force_pallas=True))(q, w, keys, 1, tables, q_pos)
    assert (np.isfinite(got) == np.isfinite(want)).all()
    live = np.isfinite(want)
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], atol=2e-6)
    # a row scores its own sequence's positions at or below its own
    real = np.asarray(q_pos) < PAD_POSITION
    assert (live.sum(-1) == np.where(real, np.asarray(q_pos) + 1, 0)).all()
    # the walk by brute count: a (tile, column, block) once, whoever of
    # the tile's rows names it
    walk = ia.index_walk(tables, q_pos, 8, 2, 16, slots, True)
    t = np.asarray(tables)
    pairs = {(c, int(t[r, c])) for r in range(24) if real[r]
             for c in range(4) if t[r, c] >= 0 and c * 8 <= int(q_pos[r])}
    n = int(walk.pairs[0])
    assert n == len(pairs)
    assert set(zip(np.asarray(walk.col)[:n].tolist(),
                   np.asarray(walk.block)[:n].tolist())) == pairs
    assert int(np.asarray(walk.opens).sum()) == len({c for c, _ in pairs})


def test_the_walks_bounds_are_checked_against_smem():
    assert ia.tile_rows(128, 64) == 32 and ia.tile_rows(16, 2) == 16
    assert ia.max_pairs(128, 64, 8, 260) == 4 * 8 * 260
    tables = jnp.zeros((128, 1040), jnp.int32)
    with pytest.raises(ValueError, match="larger pool blocks"):
        ia.index_walk(tables, jnp.zeros((128,), jnp.int32), 64, 64, 128, 16,
                      True)


# -- the paged forward ------------------------------------------------------------

def _recording(sets):
    """:func:`ia.selection_counts` handing every call's selection to
    ``sets`` as ``(tables, q_pos, positions, chosen)``: a layer of a
    step (unordered: a record says nothing of its layer)."""
    sound = ia.selection_counts

    def counts(selection, tables, q_pos, *rest):
        jax.debug.callback(
            lambda *a: sets.append(tuple(map(np.asarray, a))), tables,
            q_pos, selection.positions, selection.chosen)
        return sound(selection, tables, q_pos, *rest)

    return counts


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_references_logits_and_selected_sets(
        impl, monkeypatch):
    """The harness's own probe: prefill in 16-row and then unaligned 15-row
    chunks beside a decode row and pad rows, then decode; every row past
    the eighth selects, and what it selects in its three layers is what
    the reference's row selects in its three."""
    cfg, _, forward, params = _model(
        attn_force_pallas=impl == "pallas-interpret")
    assert cfg.head_dim_ == 128
    chk = dict(prompt_tokens=50, decode_steps=12)
    records = []
    monkeypatch.setattr(ia, "selection_counts", _recording(records))
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params,
                                       fc.engine_config(), chk)
    jax.effects_barrier()
    ref, weights = _reference(params)
    sets = []
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED,
                                  selections=sets)[0])
    assert got.shape == want.shape == (2, 62, 256)
    assert fc.worst(got, want) < SOUND
    # the program's selected sets by (first block of the row's table: its
    # sequence, position), one a layer
    seen = {}
    for tables, q_pos, positions, chosen in records:
        for r in np.flatnonzero(q_pos < PAD_POSITION):
            seen.setdefault((int(tables[r, 0]), int(q_pos[r])), []).append(
                sorted(positions[r][chosen[r]].tolist()))
    assert len(seen) == 2 * 62
    blocks = sorted({block for block, _ in seen})
    for (block, p), mine in seen.items():
        seq = blocks.index(block)
        theirs = [np.flatnonzero(sets[seq * 3 + layer][p]).tolist()
                  for layer in range(3)]
        assert sorted(mine) == sorted(theirs), (seq, p)
        assert len(mine[0]) == min(p + 1, TOP)


def test_a_context_within_the_selection_attends_every_position():
    """With ``index_topk`` at the sequence's length the indexer decides
    nothing: the logits are those of a model whose indexer is drawn
    anew."""
    cfg, model, _, params = _model()
    wide = dataclasses.replace(cfg, index_topk=40)
    tokens = _tokens(8, (1, 40))

    def redrawn(path, x):
        if "index_" in jax.tree_util.keystr(path):
            return x[::-1] * 1.7 + 0.3
        return x

    def full(cfg, p):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ds.DeepseekV32ForCausalLM(cfg).apply(
                p, jnp.asarray(tokens)))

    other = jax.tree_util.tree_map_with_path(redrawn, params)
    np.testing.assert_allclose(full(wide, params), full(wide, other),
                               atol=1e-5)
    assert np.abs(full(cfg, params) - full(cfg, other)).max() > 1e-2


# -- the faults the comparison must not pass -----------------------------------

@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_put_into_the_program_fails_the_comparison(fault):
    cfg, _, forward, params = _model()
    chk = dict(prompt_tokens=40, decode_steps=6)

    def probe():
        with jax.default_matmul_precision("highest"):
            return serve.probe_logits(9, cfg, forward, params,
                                      fc.engine_config(), chk)

    seqs, sound = probe()
    with FAULTS[fault]():
        _, got = probe()
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert fc.worst(sound, want) < SOUND
    assert fc.worst(got, want) > 3e-2, fault


# -- through ServingEngine ------------------------------------------------------

COUNTERS = ("nxd_moe_assignments_total", "nxd_moe_held_total",
            "nxd_dsa_positions_total", "nxd_dsa_rows_total",
            "nxd_dsa_blocks_total", "nxd_dsa_selected_total",
            "nxd_step_rows_by_context_total", "nxd_engine_rows_total")


@pytest.fixture(scope="module")
def served():
    """Three requests, one of them preempted on the way, through one
    engine."""
    cfg, _, _, params = _model()
    return fc.serve_three(cfg, params, COUNTERS, lengths=[70, 40, 5],
                          new=[30, 12, 4], num_blocks=9, max_slots=2)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_is_readmitted_over_both_leaves(served):
    fc.check_preempted_and_whole(served.eng)
    cache = served.eng.cache
    assert cache.POOL_LEAVES == ("rows", "index_keys")
    assert cache.rows.shape == (3, 9, BS, 128)
    assert cache.index_keys.shape == (3, 9, BS, 16)


def test_the_selections_counts_reach_the_registry_and_add_up(served):
    counters = served.counters
    rows = counters["nxd_engine_rows_total"]
    real = rows["decode"] + rows["prefill"]
    layers = 3
    assert counters["nxd_moe_assignments_total"]["dropped"] == 0
    held = counters["nxd_moe_held_total"]
    # top_k an expert layer a real row, whoever holds the expert
    assert held["held"] + held["elsewhere"] == real * 3 * 2
    assert held["held"] == counters["nxd_moe_assignments_total"]["kept"] > 0
    assert held["elsewhere"] > 0
    dsa = counters["nxd_dsa_rows_total"]
    assert dsa["selecting"] + dsa["whole"] == real * layers
    positions = counters["nxd_dsa_positions_total"]
    # a row's selection is the top 8 of its causal positions: what the
    # rows at a context of 8 or less attend is all they have
    assert positions["selected"] <= real * layers * TOP
    assert positions["selected"] >= dsa["selecting"] * TOP
    assert positions["passed_over"] > 0
    picked = counters["nxd_dsa_selected_total"]
    assert (picked["shared_with_previous_row"] + picked["new"]
            == positions["selected"])
    assert picked["shared_with_previous_row"] > 0
    blocks = counters["nxd_dsa_blocks_total"]
    assert blocks["named"] > 0 and blocks["unnamed"] > 0
    assert blocks["named"] <= positions["selected"]
    assert counters["nxd_step_rows_by_context_total"] == {
        "to_2k": real, "to_8k": 0, "past_8k": 0}


def test_the_counts_of_one_step_are_the_brute_counts():
    """:func:`ia.selection_counts` against sets counted one by one."""
    tables, q_pos, _ = _scene("a_chunk_beside_decode_rows")
    rng = np.random.RandomState(6)
    scores = np.where(
        (np.arange(32)[None, :] <= np.asarray(q_pos)[:, None])
        & (np.asarray(q_pos) < PAD_POSITION)[:, None]
        & np.repeat(np.asarray(tables) >= 0, 8, axis=1),
        rng.randint(0, 6, (24, 32)).astype(np.float32), -np.inf)
    picked = ia.select_positions(jnp.asarray(scores), 5)
    got = dict(zip(ia.COUNT_KINDS, np.asarray(ia.selection_counts(
        picked, tables, q_pos, 8, 5)).tolist()))
    positions, chosen, _ = map(np.asarray, picked)
    sets = [set(positions[r][chosen[r]].tolist()) for r in range(24)]
    real = np.asarray(q_pos) < PAD_POSITION
    causal = [int(p) + 1 if ok else 0 for p, ok in zip(q_pos, real)]
    want = dict(
        selected=sum(map(len, sets)),
        passed_over=sum(causal) - sum(map(len, sets)),
        selecting=sum(c > 5 for c in causal),
        whole=sum(0 < c <= 5 for c in causal),
        named=sum(len({p // 8 for p in s}) for s in sets),
        unnamed=sum(-(-c // 8) for c in causal)
        - sum(len({p // 8 for p in s}) for s in sets),
        # rows 2 to 11 are one chunk: positions 10 to 19 of slot 0
        shared_with_previous_row=sum(len(sets[r] & sets[r - 1])
                                     for r in range(3, 12)))
    want["new"] = want["selected"] - want["shared_with_previous_row"]
    assert got == want
    assert [len(s) for s in sets[:3]] == [5, 5, 5]


def test_prefix_sharing_maps_both_leaves_and_copies_on_write():
    cfg, _, _, params = _model()
    rng = np.random.RandomState(12)
    common = rng.randint(0, 256, (40,)).tolist()   # two blocks and a half
    prompts = [common + rng.randint(0, 256, (9,)).tolist() for _ in range(2)]
    eng = ServingEngine(cfg, params, fc.engine_config(prefix_sharing=True))
    out = []
    for prompt in prompts:
        uid = eng.submit(prompt, 6)
        while eng.has_work():
            eng.step()
        out.append(eng.results[uid].tokens)
    assert eng.stats.prefix_hit_tokens >= 2 * BS
    for prompt, tokens in zip(prompts, out):
        assert tokens == fc.greedy_by_reference(_reference_logits, params,
                                                prompt, tokens)


def test_blocks_are_copied_and_shipped_with_their_index_keys():
    cfg, _, _, _ = _model()
    cache = paging.init_serving_cache(
        cfg, num_blocks=6, block_size=BS, table_rows=2,
        max_blocks_per_seq=3, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    cache = cache.replace(
        rows=jnp.asarray(rng.randn(*cache.rows.shape), jnp.float32),
        index_keys=jnp.asarray(rng.randn(*cache.index_keys.shape),
                               jnp.float32),
        pos=jnp.tile(jnp.arange(BS, dtype=jnp.int32), (6, 1)))
    copied = paging.cow_copy_blocks(
        cache, jnp.asarray([1, 0]), jnp.asarray([4, 6]),
        jnp.asarray([9, 0]))
    for leaf in cache.POOL_LEAVES:
        got, was = getattr(copied, leaf), getattr(cache, leaf)
        assert (np.asarray(got[:, 4]) == np.asarray(was[:, 1])).all()
        assert (np.asarray(got[:, 5]) == np.asarray(was[:, 5])).all()
    payload = paging.extract_blocks(cache, [2, 3], PAD_POSITION)
    assert set(payload) == {"rows", "index_keys", "pos"}
    assert set(payload) <= set(paging.PAYLOAD_BLOCK_AXES)
    landed = paging.inject_blocks(copied, [0, 5], payload)
    assert (np.asarray(landed.index_keys[:, 5])
            == np.asarray(cache.index_keys[:, 3])).all()
    assert (np.asarray(landed.rows[:, 0]) == np.asarray(cache.rows[:, 2])
            ).all()


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES[1:])
def test_refused_features_raise_by_name_with_their_reason(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, reason=True)


def test_the_cache_is_the_indexed_latent_kinds():
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, fc.engine_config())
    assert isinstance(eng.cache, paging.IndexedLatentPagedCache)
    assert isinstance(eng.cache, paging.LatentPagedCache)
    kind = cfg.serving_family().cache_kind
    assert isinstance(kind, paging.IndexedLatentCache)
    assert eng.cache.rows.shape == (3, 40, BS, 128)
    assert eng.cache.index_keys.shape == (3, 40, BS, 16)
    assert eng.cache.moe_counts.shape == (3,)
    assert eng.cache.counts.shape == (len(ia.COUNT_KINDS),)
    (counts,) = kind.device_counts
    assert counts.leaf == "counts"
    assert [family.name for family, _ in counts.reads] == [
        "nxd_dsa_positions_total", "nxd_dsa_rows_total",
        "nxd_dsa_blocks_total", "nxd_dsa_selected_total"]
    real = ds.DeepseekV32Config()
    kind = real.serving_family().cache_kind
    assert (kind.name, kind.row, kind.index_row) == ("indexed_latent", 640,
                                                     128)
