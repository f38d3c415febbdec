"""Nemotron-H (NVIDIA-Nemotron-3-Super's layout: layers that are a Mamba-2
mixer with several groups of ``B`` and ``C``, a NoPE attention or a
LatentMoE feed-forward alone) through the model, the paged forward over
the state-pool cache, the kernels in interpret mode and ``ServingEngine``,
against the benchmark's plain reference
``benchmarks/reference/nemotron_h_f32.py``.

Tiny widths (``benchmarks/tests/configs/tiny-nemotron-h.json``): hidden
64, nine published layers ``MEM*EEME*`` (six decoder layers of five kinds
in six runs); eight mamba heads of 32 over a state of 16 in two groups;
four query heads of 16 over two K/V heads; 8 squared-ReLU experts of 24 in
a latent of 32, top 3, of which the first 4 are held, a shared expert of
48. The weights are seeded, norm multipliers of order one, the scan's own
parameters through the family's mapping onto Mamba-2's initialisation, a
selection bias that decides choices. ``tests/test_granite_hybrid.py``
holds the scan and the convolution at 1, 2 and 8 groups.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

import family_checks as fc
from nemotron_faults import faults
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.models import nemotron_h as nh
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.modules.moe.routing import RouterSigmoid
from neuronx_distributed_tpu.modules.norms import GroupRMSNorm, RMSNorm
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)

BS = 16
#: what a sound float32 run may read of the logits' deviation (it reads
#: 4e-6); each fault below reads over the stated multiple of it
SOUND = 3e-5
#: every comparison against the reference runs over these positions, so
#: that its eager programs are compiled once
LENGTH = 37
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = harness.read_json(os.path.join(
    BENCH, "tests", "configs", "tiny-nemotron-h.json"))
STD = PUBLISHED["initializer_range"]

_CASE = {}


def _family():
    return harness.load_plugin("families", "nemotron_h")


def _reference():
    return harness.load_plugin("reference", "nemotron_h_f32")


@fc.once_a_module
def _model(**kw):
    """The family's config from the published keys, its module and seeded
    weights: what ``make_weights`` would draw for the scan's leaves (the
    family reads them as Mamba-2's), order one elsewhere, a router whose
    scores spread and a bias of their spacing's size."""
    cfg, model, _ = _family().build(
        PUBLISHED, **{"dtype": jnp.float32, "param_dtype": jnp.float32,
                      **kw})
    if "params" in _CASE:                  # the same draw for every config
        return cfg, model, _CASE["params"]
    init = meta.unbox(jax.eval_shape(model.init, jax.random.key(3),
                                     jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if any(leaf in name for leaf in ("A_log", "dt_bias", "['D']",
                                         "conv_kernel")):
            return STD * noise
        if "router" in name:
            return (0.15 if name.endswith("['bias']") else 1.0) * noise

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


# -- (a) the model and the paged forward against the reference --------------

def test_the_pattern_makes_layers_of_one_block_and_of_two():
    cfg, _, params = _model()
    assert nh.layers_of_pattern("MEMEMEM*EME") == (
        "mamba2_moe",) * 3 + ("mamba2", "full_moe", "mamba2_moe")
    assert cfg.kinds() == ("mamba2_moe", "mamba2", "full_moe", "moe",
                           "mamba2_moe", "full")
    assert cfg.runs() == (("mamba2_moe", 0, 1), ("mamba2", 0, 1),
                          ("full_moe", 0, 1), ("moe", 0, 1),
                          ("mamba2_moe", 1, 1), ("full", 0, 1))
    assert nh.NemotronHConfig().runs() == (
        ("mamba2_moe", 0, 3), ("mamba2", 0, 1), ("full_moe", 0, 1),
        ("mamba2_moe", 3, 1))
    # a layer's place among the layers of its mixer: the cache's index
    assert cfg.cache_layers() == {
        "mamba2_moe": (0, 2), "mamba2": (1,), "full_moe": (0,),
        "full": (1,), "moe": (0,)}
    assert nh.NemotronHConfig().cache_layers()["mamba2_moe"] == (0, 1, 2, 4)
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held,
            cfg.expert_intermediate_size, cfg.moe_latent_size,
            cfg.intermediate_size, cfg.mamba_n_groups, cfg.d_inner,
            cfg.conv_channels) == (8, 3, (0, 4), 24, 32, 48, 2, 256, 320)
    assert cfg.carried()["mamba2_moe"] == ("ssm", "conv", "moe_counts")
    assert cfg.carried()["full"] == ("k", "v")
    assert cfg.carried()["moe"] == ("moe_counts",)
    layers = params["params"]["model"]
    assert set(layers) == {"embed", "norm"} | {
        f"layers_{k}" for k in cfg.stacks()}
    assert set(layers["layers_mamba2"]["layer"]) == {"attn", "input_norm"}
    assert set(layers["layers_moe"]["layer"]) == {"moe", "post_norm"}
    assert set(layers["layers_full_moe"]["layer"]) == {
        "attn", "input_norm", "moe", "post_norm"}
    moe = layers["layers_mamba2_moe"]["layer"]["moe"]
    assert set(moe["experts"]) == {"up", "down"}          # no gate
    assert moe["experts"]["up"].shape == (2, 4, 32, 24)
    assert moe["experts"]["down"].shape == (2, 4, 24, 32)
    assert set(moe["shared"]) == {"up_kernel", "down"}
    assert moe["shared"]["up_kernel"].shape == (2, 64, 48)
    assert moe["latent_in"].shape == (2, 64, 32)
    assert moe["latent_out"].shape == (2, 32, 64)
    assert moe["router"]["kernel"].shape == (2, 64, 8)
    assert moe["router"]["bias"].shape == (2, 8)
    assert params["params"]["lm_head"]["kernel"].shape == (64, 256)
    family = cfg.serving_family()
    assert family.moe_counts and family.device_counts() == (
        paging.MOE_KEPT_DROPPED_ELSEWHERE_HIT,)
    assert [f.name for f, _ in family.device_counts()[0].reads] == [
        "nxd_moe_assignments_total", "nxd_moe_held_total",
        "nxd_moe_experts_hit_total"]
    from neuronx_distributed_tpu.models import granite_hybrid as gh

    assert sorted(family.unsupported) == sorted(
        gh.GraniteHybridConfig().serving_family().unsupported)
    for wrong, said in ((dict(experts_held=(6, 4)), "experts"),
                        (dict(top_k=9), "top_k"),
                        (dict(mamba_n_groups=3), "groups"),
                        (dict(pattern="MEM-EEME*"), "no block"),
                        (dict(num_layers=8), "num_hidden_layers")):
        with pytest.raises(ValueError, match=said):
            nh.tiny_config(**wrong)


def test_every_published_key_is_read_or_refused():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r["config"] for r in map(json.loads, f)
                      if r["name"].startswith("NVIDIA-Nemotron-3-Super")]
        assert set(row) == nh.PUBLISHED_KEYS
        with pytest.raises(ValueError, match="num_nextn_predict_layers"):
            nh.NemotronHConfig.from_published(row)
        cfg = nh.NemotronHConfig.from_published(
            dict(row, num_nextn_predict_layers=0))
        assert (cfg.num_layers, cfg.num_experts, cfg.top_k,
                cfg.expert_intermediate_size, cfg.moe_latent_size,
                cfg.intermediate_size, cfg.mamba_n_heads, cfg.mamba_n_groups,
                cfg.d_inner, cfg.conv_channels, cfg.head_dim_,
                cfg.num_kv_heads, cfg.routed_scaling_factor) == (
            88, 512, 22, 2688, 1024, 5376, 128, 8, 8192, 10240, 128, 2, 5.0)
        kinds = cfg.kinds()
        assert (sum(k.startswith("mamba2") for k in kinds),
                sum(k.startswith("full") for k in kinds),
                sum(k.endswith("moe") for k in kinds)) == (40, 8, 40)
        assert cfg.kind_config("full").attn_scale_ == pytest.approx(
            128 ** -0.5)
        assert not cfg.use_rope and not cfg.tie_embeddings
        cut = nh.NemotronHConfig.from_published(dict(
            row, num_nextn_predict_layers=0, num_hidden_layers=11,
            hybrid_override_pattern=row["hybrid_override_pattern"][:11]))
        assert cut == nh.NemotronHConfig()
    assert nh.PUBLISHED_KEYS <= set(PUBLISHED)
    assert set(nh._UNREAD) == {
        "rope_theta", "partial_rotary_factor", "chunk_size",
        "time_step_floor", "time_step_max", "time_step_min",
        "rescale_prenorm_residual", "mtp_hybrid_override_pattern",
        "num_logits_to_keep", "use_mamba_kernels"}
    for key, value in (("hybrid_override_pattern", "MEM-EEME*"),
                       ("n_group", 2), ("topk_group", 2),
                       ("residual_in_fp32", True), ("use_bias", True),
                       ("mlp_bias", True), ("attention_bias", True),
                       ("mamba_proj_bias", True), ("use_conv_bias", False),
                       ("mlp_hidden_act", "silu"),
                       ("mamba_hidden_act", "gelu"), ("expand", 2),
                       ("intermediate_size", 32),
                       ("layer_norm_epsilon", 1e-6),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("norm_topk_prob", False), ("n_shared_experts", 2),
                       ("sliding_window", 128), ("num_hidden_layers", 8),
                       ("model_type", "granitemoehybrid")):
        with pytest.raises(ValueError, match=key):
            _family().build(dict(PUBLISHED, **{key: value}))


def test_the_family_serves_the_tree_under_the_checkpoints_names():
    cfg, _, params = _model()
    weights = _family().published(params, PUBLISHED)
    names = nh.published_names(cfg)
    # MEM*EEME*: layer 3 is the attention of (*, E), layer 5 the lone E
    assert names["backbone.layers.3.mixer.q_proj.weight"][:2] == (
        "full_moe", 0)
    assert names["backbone.layers.4.norm.weight"] == (
        "full_moe", 0, ("post_norm", "scale"))
    assert names["backbone.layers.5.mixer.fc1_latent_proj.weight"][:2] == (
        "moe", 0)
    assert names["backbone.layers.6.mixer.A_log"][:2] == ("mamba2_moe", 1)
    assert names["backbone.layers.8.norm.weight"] == (
        "full", 0, ("input_norm", "scale"))
    assert len({n.split(".")[2] for n in names}) == 9
    layer = "backbone.layers.%d.mixer."
    assert weights(layer % 1 + "experts.3.up_proj.weight").shape == (24, 32)
    assert weights(layer % 4 + "experts.0.down_proj.weight").shape == (32,
                                                                       24)
    assert weights(layer % 5 + "gate.weight").shape == (8, 64)
    assert weights(layer % 5 + "gate.e_score_correction_bias").shape == (8,)
    with pytest.raises(KeyError, match="held elsewhere"):
        weights(layer % 1 + "experts.4.up_proj.weight")
    with pytest.raises(KeyError):
        weights(layer % 1 + "in_proj.weight")     # an E layer has none
    assert weights(layer % 7 + "shared_experts.up_proj.weight").shape == (
        48, 64)
    assert weights(layer % 7 + "fc2_latent_proj.weight").shape == (64, 32)
    assert weights(layer % 0 + "in_proj.weight").shape == (
        2 * 256 + 2 * 2 * 16 + 8, 64)
    assert weights(layer % 2 + "conv1d.weight").shape == (320, 1, 4)
    assert weights(layer % 2 + "norm.weight").shape == (256,)
    assert weights(layer % 8 + "k_proj.weight").shape == (32, 64)
    assert weights("backbone.embeddings.weight").shape == (256, 64)
    assert weights("lm_head.weight").shape == (256, 64)
    assert weights("backbone.norm_f.weight").shape == (64,)
    a = np.exp(np.asarray(weights("A_log", 2)))
    assert ((a >= 1) & (a <= 16)).all() and np.ptp(a) > 1
    up = np.asarray(weights("up_proj", 7, 1))
    tree = params["params"]["model"]["layers_mamba2_moe"]["layer"]["moe"]
    np.testing.assert_array_equal(up,
                                  np.asarray(tree["experts"]["up"][1, 1]).T)


def test_full_forward_matches_the_reference():
    cfg, model, params = _model()
    tokens, want = _case()
    assert np.std(want) > 0.05
    served = _family().with_mamba2_init(params, STD)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(served, jnp.asarray(tokens)))
    assert fc.worst(got, want) < SOUND


@pytest.mark.parametrize("impl,length", [("xla", LENGTH),
                                         ("pallas-interpret", 21)])
def test_paged_prefill_then_decode_matches_the_reference(impl, length):
    """State, tails and the routed assignments carried across every step
    boundary: chunks of 1 to 8 rows, then a decode row beside the second
    sequence's unaligned chunks, then both decoding among pad rows."""
    from neuronx_distributed_tpu.ops import ssd

    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    assert ssd.ssd_packed_impl(cfg.mamba_d_state, cfg.d_inner,
                               cfg.attn_force_pallas,
                               cfg.mamba_n_groups) == impl
    seqs, want = _case()
    steps = fc.schedule(length, [3, 8, 2, 1, 5], BS)
    got, cache = fc.paged_logits(cfg, params, seqs, steps, BS)
    assert len(got) == 2 * length
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=SOUND * np.std(want), err_msg=(s, p))
    assert cache.k.shape == (2, 24, BS, 2, 16) == cache.v.shape
    assert cache.states["ssm"].shape == (3, 3, 16, 256)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.states["conv"].shape == (3, 3, 3, 320)
    # [kept, dropped, elsewhere, hit, idle] of the last step's rows: 4 E
    # layers x top 3 a row; 4 held experts a layer
    counts = np.asarray(cache.moe_counts)
    assert counts[:3].sum() == len(steps[-1]) * 4 * 3
    assert counts[1] == 0 < counts[2] and counts[0] > 0
    assert counts[3:].sum() == 4 * 4 and 0 < counts[3] <= counts[0]


# -- (b) what the comparison must not pass ------------------------------------

#: what each reads of the logits' deviation is over this many times what a
#: sound run may
FAULTS = {name: 100 for name in faults(4, 5)}


@pytest.mark.parametrize("fault", FAULTS)
def test_what_the_comparison_must_not_pass(fault):
    """The sound paged run reads under ``SOUND``; the same run with one
    fault put in reads over its stated multiple of it."""
    cfg, _, params = _model()
    seqs, want = _case()
    steps = fc.schedule(30, [4, 5, 3, 4, 4], BS)[:12]

    def worst(**kw):
        got, _ = fc.paged_logits(cfg, params, seqs, steps, BS, **kw)
        return fc.worst_at(got, want)

    if "sound" not in _CASE:
        _CASE["sound"] = worst()
    assert _CASE["sound"] < SOUND
    with faults(4, 5)[fault]():
        read = worst(fresh=True)
    print(fault, "reads", read)
    assert read > FAULTS[fault] * SOUND


# -- (c) the norm, the router and the shares ----------------------------------

def test_the_gated_norm_is_by_group():
    """Each group's channels over their own root mean square, one weight
    a channel; one group is ``RMSNorm``."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(5)
    x = rng.randn(3, 7, 48).astype(np.float32) * np.repeat(
        [1.0, 10.0, 0.1, 3.0], 12)
    scale = 1.0 + 0.3 * rng.randn(48).astype(np.float32)
    params = {"params": {"scale": jnp.asarray(scale)}}

    def grouped(groups):
        return np.asarray(GroupRMSNorm(groups=groups, eps=1e-5,
                                       dtype=jnp.float32).apply(
            params, jnp.asarray(x)))

    by_hand = x.reshape(3, 7, 4, 12).astype(np.float64)
    by_hand = by_hand / np.sqrt((by_hand ** 2).mean(-1, keepdims=True)
                                + 1e-5)
    np.testing.assert_allclose(grouped(4), by_hand.reshape(x.shape) * scale,
                               rtol=2e-6)
    np.testing.assert_array_equal(
        grouped(1), np.asarray(RMSNorm(eps=1e-5, dtype=jnp.float32).apply(
            params, jnp.asarray(x))))
    assert np.abs(grouped(4) - grouped(1)).max() > 0.5
    with jax.default_matmul_precision("highest"):
        ref = _reference().group_rms_norm(
            jnp.asarray(x[0]), jnp.asarray(scale), 1e-5, 4)
    np.testing.assert_allclose(np.asarray(ref), grouped(4)[0], rtol=2e-6)


def test_the_router_chooses_by_score_and_bias_and_weighs_by_score():
    """``RouterSigmoid`` and the reference's ``route``: the same choices
    (by ``s + b``; equal: the lower index), the same weights (``s`` over
    the chosen's sum, times the factor); the bias moves choices and no
    weight."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(3)
    x = rng.randn(40, 64).astype(np.float32)
    router = RouterSigmoid(num_experts=32, top_k=6, scale=5.0,
                           dtype=jnp.float32, param_dtype=jnp.float32)
    kernel = rng.randn(64, 32).astype(np.float32) * 0.2
    kernel[:, 7] = kernel[:, 3]                      # two equal scores
    bias = (0.1 * rng.randn(32)).astype(np.float32)
    bias[7] = bias[3]
    params = {"params": {"kernel": jnp.asarray(kernel),
                         "bias": jnp.asarray(bias)}}
    with jax.default_matmul_precision("highest"):
        gates, idx, _ = router.apply(params, jnp.asarray(x))
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ kernel)))
    order = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.asarray(idx), order)
    chosen = np.take_along_axis(scores, order, axis=-1)
    np.testing.assert_allclose(np.asarray(gates),
                               5 * chosen / chosen.sum(-1, keepdims=True),
                               atol=3e-6)
    plain = np.argsort(-scores, axis=-1, kind="stable")[:, :6]
    assert (np.sort(plain) != np.sort(order)).any(axis=-1).mean() > 0.3

    def weights(name, layer):
        return jnp.asarray(kernel).T if name == "router" else jnp.asarray(
            bias)

    with jax.default_matmul_precision("highest"):
        ref = _reference().route(
            jnp.asarray(x), weights, 0,
            {"num_experts_per_tok": 6, "routed_scaling_factor": 5})
    np.testing.assert_array_equal(np.asarray(ref[0]), order)
    np.testing.assert_allclose(np.asarray(ref[1]), np.asarray(gates),
                               atol=3e-6)
    assert (np.asarray(ref[2]) >= 0).all()


def test_four_quarters_routed_sums_and_the_shared_expert_once_are_the_layer():
    """An ``E`` layer as the four chips of a stage hold it, 8 experts each
    of 32, six choices a row: the quarters' routed sums, each through
    ``latent_out``, added, plus the shared expert counted once (each
    share computes it whole) equal the plain reference's uncut layer;
    the experts a quarter hit are those its rows chose, by hand."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=32, hidden_size=64, intermediate_size=24,
                   top_k=6, capacity_factor=None, router_type="sigmoid",
                   router_scale=5.0, shared_expert_intermediate=48,
                   held=held, expert_act="relu2", latent_size=16,
                   count_hit=True, dtype=jnp.float32,
                   param_dtype=jnp.float32)

    whole = meta.unbox(moe((0, 32)).init(jax.random.key(1), jnp.asarray(x),
                                         valid=jnp.asarray(valid)))
    assert set(whole["params"]) == {"router", "experts", "shared",
                                    "latent_in", "latent_out"}
    tree = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)["params"]

    def weights(name, layer=None, expert=None):
        t = lambda w: np.asarray(w).T
        return {"router": lambda: t(tree["router"]["kernel"]),
                "router_bias": lambda: np.asarray(tree["router"]["bias"]),
                "latent_in": lambda: t(tree["latent_in"]),
                "latent_out": lambda: t(tree["latent_out"]),
                "shared_up_proj": lambda: t(tree["shared"]["up_kernel"]),
                "shared_down_proj":
                    lambda: t(tree["shared"]["down"]["kernel"]),
                "up_proj": lambda: t(tree["experts"]["up"][expert]),
                "down_proj": lambda: t(tree["experts"]["down"][expert]),
                }[name]()

    ref = _reference()
    config = dict(n_routed_experts=32, num_experts_per_tok=6,
                  routed_scaling_factor=5.0)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.latent_moe(jnp.asarray(x), weights, 0,
                                          config)[0])
        shared = np.asarray(ref.relu2(
            jnp.asarray(x) @ weights("shared_up_proj").T)
            @ weights("shared_down_proj").T)
        chosen = np.asarray(ref.route(jnp.asarray(x), weights, 0,
                                      config)[0])
    assert np.abs(shared)[valid].max() > 0.05

    def of(first):
        p = {"params": dict(tree, experts={
            k: w[first:first + 8] for k, w in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = moe((first, 8)).apply(p, jnp.asarray(x),
                                           valid=jnp.asarray(valid))
            mine = np.asarray(ref.latent_moe(
                jnp.asarray(x), weights, 0,
                dict(config, n_routed_experts=8,
                     share={"first_expert": first}))[0])
        np.testing.assert_allclose(np.asarray(y)[valid], mine[valid],
                                   atol=3e-5)
        hit = len({e for e in chosen[valid].ravel()
                   if first <= e < first + 8})
        np.testing.assert_array_equal(np.asarray(aux["experts_hit"]),
                                      [hit, 8 - hit])
        return np.asarray(y), np.asarray(aux["assignments"])

    shares = [of(first) for first in (0, 8, 16, 24)]
    routed = sum(y - shared for y, _ in shares)
    np.testing.assert_allclose((routed + shared)[valid], uncut[valid],
                               atol=5e-5)
    assert all(np.abs(y - shared)[valid].max() > 0.02 for y, _ in shares)
    # [kept, dropped, elsewhere] of 13 real rows x top 6
    kept = [int(c[0]) for _, c in shares]
    assert sum(kept) == 13 * 6 and all(c[1] == 0 for _, c in shares)
    assert [int(c[2]) for _, c in shares] == [13 * 6 - k for k in kept]


def test_an_idle_expert_is_one_no_real_row_chose():
    """By hand: four rows of which one is padding, two choices a row over
    six experts of which the last four are held; the pad row's choice
    hits nothing."""
    from neuronx_distributed_tpu.modules.moe.expert_mlps import ExpertMLPs

    ps.initialize_model_parallel()
    bank = ExpertMLPs(num_experts=4, hidden_size=8, intermediate_size=4,
                      top_k=2, capacity_factor=None, held=(2, 4),
                      act="relu2", count_hit=True, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    x = jnp.ones((4, 8))
    gates = jnp.full((4, 2), 0.5)
    idx = jnp.asarray([[0, 2], [2, 5], [1, 0], [3, 4]])
    valid = jnp.asarray([True, True, True, False])
    params = bank.init(jax.random.key(0), x, gates, idx, valid=valid)
    assert set(meta.unbox(params)["params"]) == {"up", "down"}
    _, aux = bank.apply(params, x, gates, idx, valid=valid)
    # held experts 2..5: 2 (twice) and 5 are chosen by real rows; 3 and 4
    # by the pad row alone
    np.testing.assert_array_equal(np.asarray(aux["experts_hit"]), [2, 2])
    np.testing.assert_array_equal(np.asarray(aux["assignments"]), [3, 0, 3])
    with pytest.raises(ValueError, match="act"):
        ExpertMLPs(num_experts=4, hidden_size=8, intermediate_size=4,
                   act="gelu").init(jax.random.key(0), x, gates, idx)
    with pytest.raises(ValueError, match="capacity"):
        ExpertMLPs(num_experts=4, hidden_size=8, intermediate_size=4,
                   act="relu2", dispatch_mode="blockwise").init(
            jax.random.key(0), x, gates, idx)


# -- (d) through ServingEngine -------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One request of 20 prompt tokens and 3 new ones through an engine
    whose steps hold 16 rows: a chunk of 16, a chunk of 4, two decode
    rows."""
    cfg, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_moe_assignments_total", "nxd_moe_held_total",
        "nxd_moe_experts_hit_total", "nxd_state_segment_rows_total",
        "nxd_state_bytes_held_total"), lengths=[20], new=[3])


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)
    assert served.eng.compile_count() == 1
    assert served.eng.cache.moe_counts.shape == (5,)


def test_the_routed_assignments_and_the_experts_hit_are_counted(served):
    """By hand: 22 real rows (20 prompt positions, two decode rows) in
    four steps, each one segment; three choices a row in each of four
    ``E`` layers; four held experts a layer a step."""
    counters = served.counters
    assert counters["nxd_state_segment_rows_total"] == {"first": 4,
                                                        "later": 18}
    kept_dropped = counters["nxd_moe_assignments_total"]
    held = counters["nxd_moe_held_total"]
    assert sum(held.values()) == 22 * 3 * 4
    assert held["held"] > 0 < held["elsewhere"]
    assert kept_dropped == {"kept": held["held"], "dropped": 0}
    hit = counters["nxd_moe_experts_hit_total"]
    assert sum(hit.values()) == 4 * 4 * 4
    # a decode step's one row hits at most three of a layer's four
    assert 2 * 4 <= hit["idle"] < 4 * 4 * 4 and hit["hit"] <= held["held"]
    assert counters["nxd_state_bytes_held_total"]["state"] == (
        4 * 3 * 16 * 256 * 4)


def test_the_engine_refuses_what_the_family_cannot_serve():
    cfg, _, params = _model()
    fc.check_refused_features(cfg, params, dict(fc.REFUSED_FEATURES),
                              reason=True)
    eng = fc.check_session_export_is_refused(
        cfg, params, paging.StatePoolPagedCache, paging.StatePoolCache)
    assert eng.cache.states["ssm"].shape == (3, 3, 16, 256)
