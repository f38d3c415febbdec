"""Granite 4.0-H with routed experts (granite-4.0-h-small's layout: a
top-k softmax router over experts of which a share is held, beside a
shared MLP, after every Mamba-2 and every attention layer) through the
model, the paged forward over the state-pool cache, the kernels in
interpret mode and ``ServingEngine``, against the benchmark's plain
reference ``benchmarks/reference/granite_moe_hybrid_f32.py``.

Tiny widths (``benchmarks/tests/configs/tiny-granite-moe-hybrid.json``):
hidden 64, five layers in runs of 1, 1, 2, 1; four mamba heads of 32 over
a state of 16; four query heads of 16 over two K/V heads; 8 experts of
width 32, top 3, of which the first 4 are held, a shared MLP of 64. The
weights are seeded, norm multipliers of order one, the scan's own
parameters through the family's mapping onto Mamba-2's initialisation.
``tests/test_granite_hybrid.py`` holds the scan, the convolution and the
dense models.
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
from granite_faults import faults
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.models import granite_hybrid as gh
from neuronx_distributed_tpu.models.llama import LlamaMLP
from neuronx_distributed_tpu.modules.moe import MoE
from neuronx_distributed_tpu.modules.moe.routing import RouterTopK
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)

BS = 16
#: what a sound float32 run may read of the logits' deviation (it reads
#: 2e-6); each fault below reads over the stated multiple of it
SOUND = 2e-5
#: every comparison against the reference runs over these positions, so
#: that its eager programs are compiled once
LENGTH = 37
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = dict(harness.read_json(os.path.join(
    BENCH, "tests", "configs", "tiny-granite-moe-hybrid.json")),
    initializer_range=0.02)

_CASE = {}


def _family():
    return harness.load_plugin("families", "granite_moe_hybrid")


def _reference():
    return harness.load_plugin("reference", "granite_moe_hybrid_f32")


@fc.once_a_module
def _model(**kw):
    """The family's config from the published keys, its module and seeded
    weights: what ``make_weights`` would draw for the scan's leaves (the
    family reads them as Mamba-2's), order one elsewhere."""
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
            return 0.02 * noise
        if "router" in name and not name.endswith("['scale']"):
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


# -- (a) the model and the paged forward against the reference --------------

def test_the_routed_feed_forward_is_under_both_kinds_of_layer():
    cfg, _, params = _model()
    assert (cfg.num_experts, cfg.top_k, cfg.experts_held,
            cfg.expert_intermediate_size, cfg.intermediate_size) == (
        8, 3, (0, 4), 32, 64)
    assert cfg.carried() == {"full": ("k", "v", "moe_counts"),
                             "mamba2": ("ssm", "conv", "moe_counts")}
    layers = params["params"]["model"]
    for kind, depth in (("mamba2", 3), ("full", 2)):
        moe = layers[f"layers_{kind}"]["layer"]["moe"]
        assert moe["router"]["kernel"].shape == (depth, 64, 8)
        assert moe["experts"]["gate"].shape == (depth, 4, 64, 32)
        assert moe["experts"]["down"].shape == (depth, 4, 32, 64)
        assert moe["shared"]["gate_kernel"].shape == (depth, 64, 64)
        assert "mlp" not in layers[f"layers_{kind}"]["layer"]
    family = cfg.serving_family()
    assert family.moe_counts and family.device_counts() == (
        paging.MOE_KEPT_DROPPED_ELSEWHERE,)
    for wrong in (dict(experts_held=(6, 4)), dict(top_k=0), dict(top_k=9),
                  dict(expert_intermediate_size=0)):
        with pytest.raises(ValueError, match="experts"):
            gh.tiny_config(**{**dict(num_experts=8, top_k=3,
                                     expert_intermediate_size=32), **wrong})


def test_the_dense_configuration_builds_what_it_built():
    """No routed experts: the shared MLP alone as ``LlamaMLP``, the
    parameter tree of before, no ``moe_counts`` in the family, the carry
    or the cache."""
    ps.initialize_model_parallel()
    cfg = gh.tiny_config(dtype=jnp.float32, param_dtype=jnp.float32)
    assert (cfg.num_experts, cfg.experts_held) == (0, None)
    assert cfg.carried() == gh.CARRIED
    assert not cfg.serving_family().moe_counts
    assert cfg.serving_family().device_counts() == ()
    tree = meta.unbox(jax.eval_shape(
        gh.GraniteHybridForCausalLM(cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32)))["params"]["model"]
    for kind in ("mamba2", "full"):
        layer = tree[f"layers_{kind}"]["layer"]
        assert set(layer) == {"attn", "input_norm", "mlp", "post_norm"}
        assert set(layer["mlp"]) == {"down", "gate_kernel", "up_kernel"}

    class Probe(LlamaMLP):
        built = []

        def __post_init__(self):
            Probe.built.append(self.name)
            super().__post_init__()

    import neuronx_distributed_tpu.models.llama as llama

    sound, llama.LlamaMLP = llama.LlamaMLP, Probe
    try:
        jax.eval_shape(gh.GraniteHybridForCausalLM(cfg).init,
                       jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    finally:
        llama.LlamaMLP = sound
    assert Probe.built and set(Probe.built) == {"mlp"}
    cache = paging.init_serving_cache(
        cfg, num_blocks=6, block_size=BS, table_rows=3,
        max_blocks_per_seq=4, dtype=jnp.float32)
    assert cache.moe_counts is None


def test_every_published_key_is_read_or_refused():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = {r["name"]: r["config"] for r in map(json.loads, f)
                    if r["name"].startswith("granite-4.0-h-")}
        small, micro = rows["granite-4.0-h-small"], rows["granite-4.0-h-micro"]
        assert set(small) == set(micro) == gh.PUBLISHED_KEYS
        cfg = gh.GraniteHybridConfig.from_published(small)
        assert (cfg.num_experts, cfg.top_k, cfg.expert_intermediate_size,
                cfg.intermediate_size, cfg.mamba_n_heads, cfg.d_inner,
                cfg.conv_channels, cfg.pool_pack, cfg.head_dim_) == (
            72, 10, 768, 1536, 128, 8192, 8448, 1, 128)
        assert (cfg.attention_multiplier, cfg.logits_scaling) == (
            0.0078125, 16.0)
        assert gh.GraniteHybridConfig.from_published(micro) \
            == gh.GraniteHybridConfig()
    assert gh.PUBLISHED_KEYS <= set(PUBLISHED)
    for key, value in (("position_embedding_type", "rope"),
                       ("hidden_act", "gelu"),
                       ("normalization_function", "layernorm"),
                       ("attention_bias", True), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False),
                       ("tie_word_embeddings", False),
                       ("mamba_n_groups", 8), ("mamba_expand", 4),
                       ("num_experts_per_tok", 0),
                       ("model_type", "granitemoe")):
        with pytest.raises(ValueError, match=key):
            _family().build(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError, match="dense model"):
        _family().build(dict(PUBLISHED, num_local_experts=0,
                             num_experts_per_tok=0))


def test_the_family_serves_the_tree_under_the_checkpoints_names():
    _, _, params = _model()
    weights = _family().published(params, PUBLISHED)
    moe = "model.layers.%d.block_sparse_moe."
    assert weights(moe % 0 + "input_linear.weight", expert=3).shape == (64,
                                                                        64)
    assert weights(moe % 1 + "output_linear.weight", expert=0).shape == (64,
                                                                         32)
    assert weights(moe % 4 + "router.layer.weight").shape == (8, 64)
    with pytest.raises(KeyError, match="held elsewhere"):
        weights(moe % 0 + "input_linear.weight", expert=4)
    shared = "model.layers.2.shared_mlp."
    assert weights(shared + "input_linear.weight").shape == (128, 64)
    assert weights(shared + "output_linear.weight").shape == (64, 64)
    assert weights("model.layers.0.mamba.in_proj.weight").shape == (292, 64)
    assert weights("model.layers.3.mamba.conv1d.weight").shape == (160, 1, 4)
    assert weights("model.layers.1.self_attn.k_proj.weight").shape == (32,
                                                                       64)
    assert weights("model.embed_tokens.weight").shape == (256, 64)
    a = np.exp(np.asarray(weights("A_log", 0)))
    assert ((a >= 1) & (a <= 16)).all() and np.ptp(a) > 1
    with pytest.raises(KeyError):
        weights("lm_head")
    gate_up = np.asarray(weights("input_linear", 2, 1))
    tree = params["params"]["model"]["layers_mamba2"]["layer"]["moe"]
    np.testing.assert_array_equal(gate_up[:32],
                                  np.asarray(tree["experts"]["gate"][1, 1]).T)
    np.testing.assert_array_equal(gate_up[32:],
                                  np.asarray(tree["experts"]["up"][1, 1]).T)


def test_full_forward_matches_the_reference():
    cfg, model, params = _model()
    tokens, want = _case()
    assert np.std(want) > 0.05
    served = harness.load_plugin("families", "granite_hybrid"
                                 ).with_mamba2_init(params, 0.02)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(served, jnp.asarray(tokens)))
    assert fc.worst(got, want) < SOUND


@pytest.mark.parametrize("impl,length", [("xla", LENGTH),
                                         ("pallas-interpret", 21)])
def test_paged_prefill_then_decode_matches_the_reference(impl, length):
    """State, tails and the routed assignments carried across every step
    boundary: chunks of 1 to 8 rows, then a decode row beside the second
    sequence's unaligned chunks, then both decoding among pad rows."""
    cfg, _, params = _model(
        attn_force_pallas=True if impl == "pallas-interpret" else None)
    seqs, want = _case()
    steps = fc.schedule(length, [3, 8, 2, 1, 5], BS)
    got, cache = fc.paged_logits(cfg, params, seqs, steps, BS)
    assert len(got) == 2 * length
    for (s, p), logits in got.items():
        np.testing.assert_allclose(logits, want[s, p],
                                   atol=SOUND * np.std(want), err_msg=(s, p))
    assert cache.k.shape == (2, 24, BS, 1, 32) == cache.v.shape
    assert cache.states["ssm"].shape == (3, 3, 16, 128)
    assert cache.states["ssm"].dtype == jnp.float32
    # [kept, dropped, elsewhere] of the last step's rows, 5 layers x top 3
    counts = np.asarray(cache.moe_counts)
    assert counts.sum() == len(steps[-1]) * 5 * 3
    assert counts[1] == 0 < counts[2] and counts[0] > 0


# -- (b) what the comparison must not pass ------------------------------------

#: what each reads of the logits' deviation is over this many times what a
#: sound run may (they read 0.007 to 1.4; tests/test_granite_hybrid.py holds
#: the state's precision)
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


# -- (c) the router and the shares --------------------------------------------

def test_the_routers_gates_are_a_softmax_over_the_chosen_logits():
    """``RouterTopK`` takes a softmax over all 72 and renormalises over
    the ten chosen; the published router takes a softmax over the ten
    chosen logits. The same gates, the same choices (equal logits: the
    lower index)."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(3)
    x = rng.randn(40, 64).astype(np.float32)
    router = RouterTopK(num_experts=72, top_k=10, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    params = meta.unbox(router.init(jax.random.key(0), jnp.asarray(x)))
    kernel = rng.randn(64, 72).astype(np.float32)
    kernel[:, 7] = kernel[:, 3]                      # two equal logits
    params = {"params": {**params["params"], "kernel": jnp.asarray(kernel)}}
    with jax.default_matmul_precision("highest"):
        gates, idx, _ = router.apply(params, jnp.asarray(x))
    logits = x.astype(np.float64) @ kernel.astype(np.float64)
    order = np.argsort(-logits, axis=-1, kind="stable")[:, :10]
    np.testing.assert_array_equal(np.asarray(idx), order)
    chosen = np.take_along_axis(logits, order, axis=-1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(gates),
                               want / want.sum(-1, keepdims=True), atol=2e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        ref = _reference().route(
            jnp.asarray(x), lambda name, li: jnp.asarray(kernel).T, 0,
            {"num_experts_per_tok": 10})
    np.testing.assert_array_equal(np.asarray(ref[0]), order)
    np.testing.assert_allclose(np.asarray(ref[1]), np.asarray(gates),
                               atol=2e-6)


def test_two_shares_routed_sums_and_the_shared_mlp_once_are_the_layer():
    """A layer's feed-forward as the two chips of a stage hold it, 36
    experts each of 72, ten choices a row: the shares' routed sums,
    added, plus the shared MLP counted once (each share computes it
    whole) equal the plain reference's uncut layer; ``elsewhere`` counts
    what the other share keeps."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=72, hidden_size=64, intermediate_size=32,
                   top_k=10, capacity_factor=None, router_type="top_k",
                   shared_expert_intermediate=48, held=held,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    whole = meta.unbox(moe((0, 72)).init(jax.random.key(1), jnp.asarray(x),
                                         valid=jnp.asarray(valid)))
    tree = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)["params"]

    def weights(name, layer=None, expert=None):
        t = lambda w: np.asarray(w).T
        if name == "router":
            return t(tree["router"]["kernel"])
        if name == "shared_input_linear":
            return np.concatenate([t(tree["shared"]["gate_kernel"]),
                                   t(tree["shared"]["up_kernel"])])
        if name == "shared_output_linear":
            return t(tree["shared"]["down"]["kernel"])
        if name == "input_linear":
            return np.concatenate([t(tree["experts"]["gate"][expert]),
                                   t(tree["experts"]["up"][expert])])
        assert name == "output_linear"
        return t(tree["experts"]["down"][expert])

    ref = _reference()
    config = dict(num_local_experts=72, num_experts_per_tok=10)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.feed_forward(jnp.asarray(x), weights, 0,
                                            config)[0])
        shared = np.asarray(ref.glu(jnp.asarray(x),
                                    weights("shared_input_linear"),
                                    weights("shared_output_linear")))
    assert np.abs(shared)[valid].max() > 0.05

    def of(first):
        p = {"params": dict(tree, experts={
            k: w[first:first + 36] for k, w in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = moe((first, 36)).apply(p, jnp.asarray(x),
                                            valid=jnp.asarray(valid))
            mine = np.asarray(ref.feed_forward(
                jnp.asarray(x), weights, 0,
                dict(config, num_local_experts=36,
                     share={"first_expert": first}))[0])
        np.testing.assert_allclose(np.asarray(y)[valid], mine[valid],
                                   atol=3e-5)
        return np.asarray(y), np.asarray(aux["assignments"])

    shares = [of(0), of(36)]
    routed = sum(y - shared for y, _ in shares)
    np.testing.assert_allclose((routed + shared)[valid], uncut[valid],
                               atol=3e-5)
    assert all(np.abs(y - shared)[valid].max() > 0.02 for y, _ in shares)
    # [kept, dropped, elsewhere] of 13 real rows x top 10
    kept = [int(c[0]) for _, c in shares]
    assert sum(kept) == 13 * 10 and all(c[1] == 0 for _, c in shares)
    assert [int(c[2]) for _, c in shares] == kept[::-1]


# -- (d) through ServingEngine -------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One request of 20 prompt tokens and 3 new ones through an engine
    whose steps hold 16 rows: a chunk of 16, a chunk of 4, two decode
    rows."""
    cfg, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_moe_assignments_total", "nxd_moe_held_total",
        "nxd_state_segment_rows_total", "nxd_state_bytes_held_total"),
        lengths=[20], new=[3])


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)
    assert served.eng.compile_count() == 1
    assert served.eng.cache.moe_counts.shape == (3,)


def test_the_routed_assignments_and_the_segments_rows_are_counted(served):
    """By hand: 22 real rows (20 prompt positions, two decode rows) in
    four steps, each one segment: a first row a step, the chunks' 15 and
    3 rows after theirs; three choices a row in each of five layers."""
    counters = served.counters
    assert counters["nxd_state_segment_rows_total"] == {"first": 4,
                                                        "later": 18}
    kept_dropped = counters["nxd_moe_assignments_total"]
    held = counters["nxd_moe_held_total"]
    assert sum(held.values()) == 22 * 3 * 5
    assert held["held"] > 0 < held["elsewhere"]
    assert kept_dropped == {"kept": held["held"], "dropped": 0}
    assert counters["nxd_state_bytes_held_total"]["state"] == (
        4 * 3 * 16 * 128 * 4)
