"""Xing4.0 (the latent family's attention under YaRN, two dense layers then
expert layers under a sigmoid router with a selection bias, and a residual
of four streams that every sublayer reads, writes and mixes by
manifold-constrained hyper-connections) through the model, the paged
forward over the latent cache and ``ServingEngine``, against the
benchmark's plain reference ``benchmarks/reference/xing4_f32.py`` (float32
streams, the Sinkhorn rounds a Python loop over ``[S, 4, 4]``, ``kv_b``
expanded, no capacity).

Tiny widths with every mechanism on: hidden 64 in 4 streams (a carry of
256), 4 heads of 24 + 8 query values over a latent of 32 and a rotary key
of 8 (a pool row of 128 lanes), values of 16, YaRN by 8 over 16 positions
(so every sequence here runs past ``original_max_position_embeddings``),
two dense layers (160) and two expert layers of 8 experts (32 wide), top
3 times 2, a shared expert, pool blocks of 16. The weights are seeded with
norm multipliers of order one, a selection bias that changes the choice
and mixing maps that differ from token to token. The toy model runs 3
Sinkhorn rounds, not the published 20: the CPU compiler's time grows with
the square of a scan body's unrolled operations (30 s a program at 20
rounds, 1 s at 3), the reference takes the count from the same key, and
the 20 rounds are held by themselves below.
"""

import json
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
from neuronx_distributed_tpu.models import glm_moe_lite as glm
from neuronx_distributed_tpu.models import xing4
from neuronx_distributed_tpu.modules import attention as attn_mod
from neuronx_distributed_tpu.modules import hyper_connections as hc
from neuronx_distributed_tpu.ops import mla_attention as mla

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from xing4_faults import FAULTS  # noqa: E402  (tests/)
from runners import serve  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BS = 16
STD = 0.08          # what the family reads the drawn alpha and bias against
PUBLISHED = dict(
    model_type="xing4_0", vocab_size=256, hidden_size=64,
    intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=2, n_routed_experts=8, num_experts_per_tok=3,
    n_shared_experts=1, routed_scaling_factor=2, norm_topk_prob=True,
    hidden_act="silu", attention_bias=False, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, moe_layer_freq=1,
    ep_size=1, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=8, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=16, mscale=1,
                      mscale_all_dim=1),
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    max_position_embeddings=4096, num_nextn_predict_layers=0, hc_mult=4,
    hc_sinkhorn_iters=3, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, initializer_range=STD, family="xing4",
    reference="xing4_f32")
SOUND = 3e-5        # of the logits' spread: float32 against float32


def _family():
    return harness.load_plugin("families", "xing4")


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


def _reference(params):
    return (harness.load_plugin("reference", "xing4_f32"),
            _family().published(params, PUBLISHED))


def _full(model, params, tokens):
    """The module's whole forward, the mixing's alpha and bias as the
    family serves them."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(
            _family().with_seeded_mixing(params, STD), jnp.asarray(tokens)))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


# -- the module's full forward ----------------------------------------------

def test_the_parameters_are_glms_and_two_mixings_a_layer():
    cfg, _, _, params = _model()
    assert cfg.runs() == (("dense", 0, 2), ("moe", 0, 2))
    assert isinstance(cfg, glm.GlmMoeLiteConfig) and not cfg.plain_layers()
    layers = params["params"]["model"]
    dense, moe = (layers[f"layers_{k}"]["layer"] for k in ("dense", "moe"))
    assert dense["mlp"]["gate_kernel"].shape == (2, 64, 160)
    assert moe["moe"]["experts"]["gate"].shape == (2, 8, 64, 32)
    assert moe["moe"]["router"]["bias"].shape == (2, 8)
    for stack in (dense, moe):
        assert stack["attn"]["k_up"].shape == (2, 4, 24, 32)
        assert stack["input_norm"]["scale"].shape == (2, 64)
        for mixing in ("hc_attn", "hc_ffn"):
            leaves = {k: (v.shape, v.dtype) for k, v in
                      stack[mixing].items()}
            assert leaves == {"phi": ((2, 256, 24), jnp.float32),
                              "alpha": ((2, 3), jnp.float32),
                              "bias": ((2, 24), jnp.float32)}
    # under the reference's names: the mixing as the family serves it, the
    # head in blocks of the vocabulary, kv_b_proj in the checkpoint's shape
    ref, weights = _reference(params)
    assert weights("hc_ffn.phi", 3).shape == (24, 256)
    np.testing.assert_allclose(weights("hc_attn.alpha", 2),
                               1 + np.asarray(moe["hc_attn"]["alpha"][0]))
    np.testing.assert_allclose(weights("hc_ffn.bias", 1),
                               np.asarray(dense["hc_ffn"]["bias"][1]) / STD,
                               rtol=1e-6)
    assert weights("lm_head", 0).shape == (256, 64) and ref.HEAD_BLOCK > 256
    np.testing.assert_array_equal(weights("lm_head", 0), weights("lm_head"))
    kv_b = np.asarray(weights("self_attn.kv_b_proj", 3))
    assert kv_b.shape == (4 * (24 + 16), 32)
    np.testing.assert_array_equal(
        kv_b[40:64], np.asarray(moe["attn"]["k_up"][1, 1]))
    assert weights("mlp.gate", 2).shape == (8, 64)
    assert weights("mlp.down_proj", 1).shape == (64, 160)
    assert weights("mlp.experts.up_proj", 3, 7).shape == (32, 64)
    assert weights("mlp.shared_experts.down_proj", 2).shape == (64, 32)
    with pytest.raises(KeyError):
        weights("mlp.gate", 0)                     # a dense layer has none


def test_the_head_is_read_in_blocks_of_the_vocabulary(monkeypatch):
    _, _, _, params = _model()
    ref, whole = _reference(params)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 64)
    blocked = _family().published(params, PUBLISHED)
    assert blocked("lm_head", 3).shape == (64, 64)
    np.testing.assert_array_equal(
        np.concatenate([blocked("lm_head", k) for k in range(4)]),
        whole("lm_head"))
    x = jnp.asarray(np.random.RandomState(0).randn(5, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ref.head(x, blocked, 256),
                                   x @ whole("lm_head").T, atol=1e-6)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 96)
    with pytest.raises(ValueError, match="no multiple"):
        _family().published(params, PUBLISHED)


def test_full_forward_matches_the_reference_with_every_mechanism_on():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 70))
    got = _full(model, params, tokens)
    ref, weights = _reference(params)
    want, margins = ref.forward(weights, tokens, PUBLISHED)
    assert got.shape == want.shape == (2, 70, 256)
    assert fc.worst(got, want) < SOUND
    assert margins.shape == (2, 2, 70) and float(margins.min()) >= 0
    at = np.array([0, 33, 69])
    np.testing.assert_allclose(
        ref.forward(weights, tokens, PUBLISHED, positions=at)[0],
        np.asarray(want)[:, at], atol=1e-5)
    # the maps as drawn (alpha near 0, a small bias) give other logits:
    # what the family reads the leaves as matters
    with jax.default_matmul_precision("highest"):
        raw = np.asarray(model.apply(params, jnp.asarray(tokens)))
    assert fc.worst(raw, want) > 1e3 * SOUND


def test_the_maps_differ_from_token_to_token():
    """``H_pre``, ``H_post`` and ``H_res`` of the first sublayer of the
    last layer over 70 tokens: each moves between tokens by far more
    than any limit a check could hold the logits to."""
    _, _, _, params = _model()
    ref, weights = _reference(params)
    tokens = np.random.RandomState(1).randint(0, 256, (70,))
    rng = np.random.RandomState(2)
    X = jnp.asarray(rng.randn(70, 4, 64), jnp.float32) \
        + weights("embedding")[tokens][:, None, :]
    with jax.default_matmul_precision("highest"):
        pre, post, res = (np.asarray(m) for m in ref.mixing_maps(
            X, weights, "hc_attn", 3, PUBLISHED | {"hc_sinkhorn_iters": 20}))
    assert pre.std(axis=0).min() > 0.1 and post.std(axis=0).min() > 0.2
    assert res.std(axis=0).min() > 0.05
    assert (0 < pre).all() and (pre < 1).all() and (post < 2).all()
    np.testing.assert_allclose(res.sum(axis=-2), 1.0, atol=1e-5)   # columns
    assert np.abs(res.sum(axis=-1) - 1.0).max() < 0.05            # rows


# -- the mixing ----------------------------------------------------------------

def _cells(matrix):
    """``[..., n, n] -> cells[i][j]`` as the package's Sinkhorn takes."""
    n = matrix.shape[-1]
    return [[jnp.asarray(matrix[..., i, j]) for j in range(n)]
            for i in range(n)]


@pytest.mark.parametrize("case", ["all_at_min", "all_at_max", "mixed",
                                  "a_permutation_at_max", "random"])
def test_sinkhorn_sums_to_one_by_rows_and_columns(case):
    """20 rounds over ``exp(clip(., -30, 30))`` of logits at and past both
    clamps: rows and columns sum to 1 within 1e-3 (``hc_eps`` is 1e-6
    beside a sum of 4e-13 where every cell is at the lower clamp, and
    still the second round is uniform), and the unrolled cells equal the
    reference's loop over ``[S, n, n]``."""
    rng = np.random.RandomState(4)
    n, tokens = 4, 6
    logits = {
        "all_at_min": np.full((tokens, n, n), -45.0),
        "all_at_max": np.full((tokens, n, n), 45.0),
        "mixed": np.where(rng.rand(tokens, n, n) < 0.5, 45.0, 29.0),
        "a_permutation_at_max": np.stack([
            np.where(np.eye(n)[rng.permutation(n)] > 0, 60.0, -60.0)
            for _ in range(tokens)]),
        "random": 2.6 * rng.randn(tokens, n, n)}[case]
    m0 = np.exp(np.clip(logits, -30, 30)).astype(np.float32)
    got = np.asarray(jnp.stack([jnp.stack(row, -1) for row in
                                hc.sinkhorn_knopp(_cells(m0), 20, 1e-6)],
                               -2))
    assert got.shape == (tokens, n, n) and np.isfinite(got).all()
    # columns come last and sum to 1; rows as nearly as 20 rounds bring
    # them, within 1e-3 at the clamps and 1e-2 for the model's own spread
    # of logits (2.6), whose near-permutations converge the slowest
    np.testing.assert_allclose(got.sum(-1), 1.0,
                               atol=1e-2 if case == "random" else 1e-3)
    np.testing.assert_allclose(got.sum(-2), 1.0, atol=1e-5)
    want = m0.astype(np.float64)
    for _ in range(20):
        want = want / (want.sum(-1, keepdims=True) + 1e-6)
        want = want / (want.sum(-2, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    if case == "a_permutation_at_max":
        np.testing.assert_allclose(got, logits > 0, atol=1e-6)


def test_the_maps_at_twenty_rounds_are_the_references():
    """One sublayer's jitted maps at the published 20 rounds, clamp and
    epsilon against the reference's loop, with logits that reach the
    clamps."""
    rng = np.random.RandomState(9)
    module = hc.HyperConnection(streams=4, hidden=16)
    leaves = {"phi": jnp.asarray(rng.randn(64, 24), jnp.float32),
              "alpha": jnp.asarray([1.3, 0.7, 6.0], jnp.float32),
              "bias": jnp.asarray(rng.randn(24), jnp.float32)}
    x = jnp.asarray(rng.randn(2, 9, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        pre, post, res = jax.jit(lambda p, x: module.apply(
            {"params": p}, x, method="maps"))(leaves, x)
        ref = harness.load_plugin("reference", "xing4_f32")
        named = {"hc.phi": leaves["phi"].T, "hc.alpha": leaves["alpha"],
                 "hc.bias": leaves["bias"]}
        want = [ref.mixing_maps(seq.reshape(9, 4, 16),
                                lambda name, li: named[name], "hc", 0,
                                PUBLISHED | {"hc_sinkhorn_iters": 20})
                for seq in x]
    assert float(jnp.abs(6.0 * (x[0] @ leaves["phi"])[:, 8:]).max()) > 30
    for got, k in ((jnp.stack(pre, -1), 0), (jnp.stack(post, -1), 1),
                   (jnp.stack([jnp.stack(r, -1) for r in res], -2), 2)):
        np.testing.assert_allclose(
            got, np.stack([w[k] for w in want]), atol=3e-6)


def test_one_round_is_not_twenty():
    m0 = np.exp(2.6 * np.random.RandomState(5).randn(8, 4, 4))
    one, twenty = (np.asarray(jnp.stack([jnp.stack(r, -1) for r in
                                         hc.sinkhorn_knopp(_cells(m0), k,
                                                           1e-6)], -2))
                   for k in (1, 20))
    assert np.abs(one.sum(-1) - 1).max() > 0.1 > 1e-2 > \
        np.abs(twenty.sum(-1) - 1).max()


def test_with_static_one_hot_maps_stream_zero_is_glms_layer():
    """``Phi = 0``, ``H_pre`` and ``H_post`` one-hot on stream 0 and
    ``H_res`` the identity: stream 0 of the layer is GLM's layer on the
    same weights (the hook changes nothing it should not), and the other
    streams pass through."""
    cfg, _, _, params = _model()
    for kind, li in (("dense", 1), ("moe", 0)):
        layer = jax.tree_util.tree_map(
            lambda w: w[li], params["params"]["model"][
                f"layers_{kind}"]["layer"])
        bias = np.full((24,), -np.inf, np.float32)
        bias[0] = np.inf                    # H_pre = e_0
        bias[4] = 0.0                       # H_post = 2 sigmoid(0) e_0
        bias[8:] = np.where(np.eye(4).ravel() > 0, 30.0, -30.0)
        for mixing in ("hc_attn", "hc_ffn"):
            layer = {**layer, mixing: {
                "phi": jnp.zeros((256, 24)), "alpha": jnp.ones((3,)),
                "bias": jnp.asarray(bias)}}
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(2, 20, 256), jnp.float32)
        cos, sin = cfg.rotary_rows(jnp.arange(20))
        ours = cfg.kind_config(kind)

        class Scaled(glm.GlmMoeLiteConfig):
            # GLM's layer at Xing4's scale of the scores: the one number
            # of the attention that the new family's config changes
            score_scale = ours.score_scale

        theirs = Scaled(**{name: getattr(ours, name) for name in
                           glm.GlmMoeLiteConfig.__dataclass_fields__})
        with jax.default_matmul_precision("highest"):
            got, aux, _ = ours.decoder_layer().apply(
                {"params": layer}, x, cos, sin)
            want, aux_glm, _ = theirs.decoder_layer().apply(
                {"params": {k: v for k, v in layer.items()
                            if not k.startswith("hc_")}},
                x[..., :64], cos, sin)
        assert type(theirs.decoder_layer()).__name__ == "LlamaDecoderLayer"
        np.testing.assert_allclose(got[..., :64], want, atol=2e-5)
        # hc_eps in the denominators: the identity to a part in 1e5
        np.testing.assert_allclose(got[..., 64:], x[..., 64:], rtol=2e-5)
        np.testing.assert_array_equal(aux, aux_glm)


def test_the_carry_is_widened_behind_the_embedding_and_summed_ahead_of_the_norm():
    cfg, _, _, _ = _model()
    x = jnp.asarray(np.random.RandomState(7).randn(1, 5, 64), jnp.float32)
    wide = cfg.carry_in(x)
    assert wide.shape == (1, 5, 256)
    np.testing.assert_array_equal(wide.reshape(1, 5, 4, 64),
                                  np.broadcast_to(x[:, :, None], (1, 5, 4, 64)))
    np.testing.assert_allclose(cfg.carry_out(wide), 4 * x, rtol=1e-6)
    plain = glm.GlmMoeLiteConfig()
    assert plain.carry_in(x) is x and plain.carry_out(x) is x


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_and_scale_are_the_references_past_the_original_length():
    cfg, _, _, _ = _model()
    ref, _ = _reference(_model()[3])
    scaling = PUBLISHED["rope_scaling"]
    want = ref.yarn_frequencies(8, 1e4, scaling)
    got = np.asarray(attn_mod.yarn_inv_freq(8, 1e4, 8.0, 16, 32.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1e4 ** (-np.arange(0, 8, 2) / 8)
    # the fastest pair keeps its frequency, the slowest is divided by 8
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / 8)
    assert not np.allclose(got, plain)
    positions = jnp.asarray([0, 15, 16, 17, 300, 4000])
    cos, sin = cfg.rotary_rows(positions)
    x = jnp.asarray(np.random.RandomState(8).randn(4001, 3, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        rotated = attn_mod.apply_rotary(x[None, np.asarray(positions)],
                                        cos, sin)[0]
        np.testing.assert_allclose(
            rotated, ref.rotary(x, 1e4, scaling)[np.asarray(positions)],
            atol=2e-4)
    m = 0.1 * math.log(8) + 1
    assert cfg.score_scale == pytest.approx(m * m / math.sqrt(32))
    assert glm.GlmMoeLiteConfig().score_scale == 1 / 16
    # the published numbers
    real = xing4.Xing4Config()
    assert real.score_scale == pytest.approx(1.4158883 ** 2 / 192 ** 0.5)
    lo = np.asarray(attn_mod.yarn_inv_freq(64, 1e4, 64.0, 4096, 32.0, 1.0))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ratio = lo / base
    assert np.allclose(ratio[:11], 1) and np.allclose(ratio[23:], 1 / 64)
    assert (np.diff(ratio[10:24]) < 0).all()
    # mscale and mscale_all_dim apart: cos and sin carry their ratio
    other = xing4.tiny_config(yarn_mscale=2.0)
    cos2, _ = other.rotary_rows(positions)
    np.testing.assert_allclose(
        cos2, cos * (0.2 * math.log(8) + 1) / m, rtol=1e-6)


# -- the published keys --------------------------------------------------------

def test_every_published_key_is_read_or_refused():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r["config"] for r in map(json.loads, f)
                      if r["name"] == "Xing4.0-29B-A4B"]
        assert set(row) == xing4.PUBLISHED_KEYS
        with pytest.raises(ValueError, match="num_nextn_predict_layers"):
            xing4.Xing4Config.from_published(row)
        cfg = xing4.Xing4Config.from_published(
            dict(row, num_nextn_predict_layers=0))
        assert cfg == xing4.Xing4Config()
        assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
                cfg.top_k, cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                cfg.hc_clamp_min, cfg.hc_clamp_max, cfg.head_dim_,
                cfg.yarn_factor, cfg.yarn_original_max_position) == (
            40, 2, 64, 4, 4, 20, 1e-6, -30.0, 30.0, 640, 64.0, 4096)
    assert xing4.PUBLISHED_KEYS <= set(PUBLISHED)
    for key, value in (("model_type", "deepseek_v3"),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("moe_layer_freq", 2), ("n_group", 2),
                       ("topk_group", 2), ("norm_topk_prob", False),
                       ("scoring_func", "softmax"),
                       ("topk_method", "greedy"),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("num_key_value_heads", 2), ("rope_scaling", None),
                       ("rope_scaling", dict(PUBLISHED["rope_scaling"],
                                             type="linear")),
                       ("rope_scaling", dict(PUBLISHED["rope_scaling"],
                                             attention_factor=1.0))):
        with pytest.raises(ValueError, match=key):
            _family().build({**PUBLISHED, key: value})
    for wrong, said in ((dict(hc_mult=0), "hc_mult"),
                        (dict(hc_clamp_min=31.0), "hc_clamp_min"),
                        (dict(first_k_dense=5), "first_k_dense")):
        with pytest.raises(ValueError, match=said):
            xing4.tiny_config(**wrong)
    assert xing4.tiny_config(hc_sinkhorn_iters=3) == \
        xing4.Xing4Config.from_published(
        {k: v for k, v in PUBLISHED.items() if k in xing4.PUBLISHED_KEYS})


# -- the paged forward ------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_references_whole_forward(impl):
    """The harness's own probe: prefill in 16-row and then unaligned 15-row
    chunks beside a decode row and pad rows, then decode, every position
    past YaRN's original 16 but the first block's."""
    cfg, _, forward, params = _model(
        attn_force_pallas=impl == "pallas-interpret")
    assert cfg.head_dim_ == 128
    chk = dict(prompt_tokens=50, decode_steps=12)
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params,
                                       fc.engine_config(), chk)
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert got.shape == want.shape == (2, 62, 256)
    assert fc.worst(got, want) < SOUND


def test_the_kernel_takes_32_heads_over_blocks_of_256():
    """The cell's tile: 8 packed rows of 32 stacked heads are 256 rows,
    scored in two slabs of 128 against units of 2 blocks of 256
    positions; a decode row's run is 8 blocks."""
    assert mla.stacked_heads(32) == 32
    assert mla._unit_lengths(32, 256, 640, 256, 2) == (8, 2, 128)
    # and at the issue's block of 128 a unit of 2 blocks against the tile
    # whole: the walk of 520 columns a row is what did not fit there
    assert mla._unit_lengths(32, 256, 640, 128, 2) == (8, 2, 256)


# -- the faults the comparison must not pass -----------------------------------

@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_put_into_the_program_fails_the_comparison(fault):
    """``H_res`` left at one Sinkhorn round, ``H_post`` without its 2,
    maps that no token moves, plain rotary frequencies and a scale
    without YaRN's factor each read a thousand times the sound program's
    error through the paged forward; columns before rows, which twenty
    rounds all but undo, still a hundred times."""
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
    least = 3e-3 if fault == "columns_before_rows" else 3e-2
    assert fc.worst(got, want) > least, fault


# -- through ServingEngine ------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests, one of them preempted on the way, through one
    engine."""
    cfg, _, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_moe_assignments_total", "nxd_paged_columns_total",
        "nxd_mla_block_fetches_total", "nxd_step_rows_by_context_total",
        "nxd_engine_rows_total"),
        lengths=[70, 40, 5], new=[30, 12, 4], num_blocks=9, max_slots=2)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_is_readmitted_and_gives_the_same_tokens(served):
    fc.check_preempted_and_whole(served.eng)


def test_the_experts_counts_reach_the_registry_as_glms_do(served):
    counters = served.counters
    rows = counters["nxd_engine_rows_total"]
    real = rows["decode"] + rows["prefill"]
    # top_k an expert layer a real row, pads none, nothing dropped
    assert counters["nxd_moe_assignments_total"] == {
        "kept": real * 3 * 2, "dropped": 0}
    assert sum(counters["nxd_mla_block_fetches_total"].values()) > 0
    # every row here is under 2,048 positions
    assert counters["nxd_step_rows_by_context_total"] == {
        "to_2k": real, "to_8k": 0, "past_8k": 0}


def test_prefix_sharing_maps_latent_blocks_and_copies_on_write():
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


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES[1:])
def test_refused_features_raise_by_name_with_their_reason(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, reason=True)


def test_the_cache_is_the_latent_familys_and_the_carry_is_no_leaf_of_it():
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, fc.engine_config())
    assert isinstance(eng.cache, paging.LatentPagedCache)
    assert isinstance(cfg.serving_family().cache_kind, paging.LatentCache)
    assert eng.cache.rows.shape == (4, 40, BS, 128)
    assert eng.cache.moe_counts.shape == (2,)
    assert sorted(cfg.serving_family().unsupported) == sorted(
        glm.GlmMoeLiteConfig().serving_family().unsupported)
    real = xing4.Xing4Config()
    kind = real.serving_family().cache_kind
    assert (kind.name, kind.row, real.head_dim_) == ("latent", 640, 640)
