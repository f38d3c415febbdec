"""LongCat-Flash (a shortcut-connected double layer: two latent attentions,
two dense feed-forwards, one expert bank read after the first attention and
added after the second feed-forward; a softmax router with a selection bias
over real and identity experts) through the model, the paged forward over a
latent cache of two layers of rows a decoder layer, the
``mla_paged_attention`` kernel and ``ServingEngine``, against the
benchmark's plain reference ``benchmarks/reference/longcat_flash_f32.py``
(which expands ``kv_b`` for every position and head and has no capacity).

Tiny widths with every mechanism on: hidden 64, 4 heads of 24 + 8 query
values over a latent of 32 and a rotary key of 8 (a pool row of 128
lanes), values of 16, a query rank of 48, both ``mla_scale`` factors, two
double layers with dense feed-forwards of 96 and 8 real experts (32 wide)
beside 4 identity experts, top 3 times 6, not renormalised, pool blocks of
16. The weights are seeded with norm multipliers of order one and a
selection bias that changes the choice.
"""

import dataclasses
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
from neuronx_distributed_tpu.modules.moe import MoE, RouterSoftmaxBias
from neuronx_distributed_tpu.ops import mla_attention as mla
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from longcat_faults import faults  # noqa: E402  (tests/)
from walk_checks import check_tile_walk  # noqa: E402  (tests/)
from runners import serve  # noqa: E402

BS = 16
STD = 0.02          # what the family reads the drawn bias against
PUBLISHED = dict(
    vocab_size=256, hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=16, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, routed_scaling_factor=6, n_routed_experts=8,
    zero_expert_num=4, zero_expert_type="identity", moe_topk=3,
    rope_theta=1e7, rms_norm_eps=1e-5, max_position_embeddings=4096,
    attention_bias=False, attention_method="MLA", initializer_range=STD,
    family="longcat_flash", reference="longcat_flash_f32")
#: experts 2 and 3 of the 8, as the second of four chips holds them
SHARE = dict(PUBLISHED, n_routed_experts=2,
             share=dict(n_routed_experts_published=8, first_expert=2))
SOUND = 3e-5        # of the logits' spread: float32 against float32


def _family():
    return harness.load_plugin("families", "longcat_flash")


@fc.once_a_module
def _model(published=PUBLISHED, **kw):
    cfg, model, forward = _family().build(
        published, dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if name.endswith("['bias']"):
            # served at 0.2 of the leaf (families/longcat_flash.py): 0.03,
            # of the spread of p over 12 slots
            return 0.15 * noise

    return cfg, model, forward, fc.seeded_weights(shapes, special)


def _reference(params, published=PUBLISHED):
    return (harness.load_plugin("reference", "longcat_flash_f32"),
            _family().published(params, published))


def _full(model, params, tokens):
    """The module's whole forward, the selection bias as the family
    serves it."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(
            _family().with_seeded_bias(params, STD), jnp.asarray(tokens)))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


# -- the module's full forward ----------------------------------------------

def test_the_parameters_are_one_stack_of_double_layers():
    cfg, _, _, params = _model()
    assert cfg.runs() == (("double", 0, 2),)
    assert (cfg.q_lora_scale, cfg.kv_lora_scale) == (
        (64 / 48) ** 0.5, 2 ** 0.5)
    layer = params["params"]["model"]["layers_double"]["layer"]
    assert layer["moe"]["experts"]["gate"].shape == (2, 8, 64, 32)
    assert layer["moe"]["router"]["kernel"].shape == (2, 64, 12)
    assert layer["moe"]["router"]["bias"].shape == (2, 12)
    assert "shared" not in layer["moe"]
    for which in (0, 1):
        attn = layer[f"attn_{which}"]
        assert attn["q_a"].shape == (2, 64, 48)
        assert attn["kv_a"].shape == (2, 64, 40)
        assert attn["k_up"].shape == (2, 4, 24, 32)
        assert attn["v_up"].shape == (2, 4, 32, 16)
        assert layer[f"mlp_{which}"]["gate_kernel"].shape == (2, 64, 96)
        assert layer[f"input_norm_{which}"]["scale"].shape == (2, 64)
        assert layer[f"post_norm_{which}"]["scale"].shape == (2, 64)
    # kv_b_proj in the checkpoint's shape, a head's key rows then its values
    _, weights = _reference(params)
    kv_b = np.asarray(weights("self_attn.1.kv_b_proj", 1))
    assert kv_b.shape == (4 * (24 + 16), 32)
    np.testing.assert_array_equal(
        kv_b[40:64], np.asarray(layer["attn_1"]["k_up"][1, 1]))
    np.testing.assert_array_equal(
        kv_b[64:80], np.asarray(layer["attn_1"]["v_up"][1, 1]).T)
    assert weights("mlp.router.classifier", 0).shape == (12, 64)
    np.testing.assert_allclose(
        weights("mlp.router.e_score_correction_bias", 1),
        0.2 * np.asarray(layer["moe"]["router"]["bias"][1]), rtol=1e-6)
    assert weights("mlps.1.down_proj", 0).shape == (64, 96)
    assert weights("mlp.experts.up_proj", 1, 7).shape == (32, 64)


def test_full_forward_matches_the_reference_with_every_mechanism_on():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 70))
    got = _full(model, params, tokens)
    ref, weights = _reference(params)
    want, margins = ref.forward(weights, tokens, PUBLISHED)
    assert got.shape == want.shape == (2, 70, 256)
    assert fc.worst(got, want) < SOUND
    assert margins.shape == (2, 2, 70) and float(margins.min()) >= 0
    # the bias changes the choice: without it the logits differ
    bare = jax.tree_util.tree_map_with_path(
        lambda p, x: x * 0 if jax.tree_util.keystr(p).endswith("['bias']")
        else x, params)
    assert fc.worst(_full(model, bare, tokens), want) > 1e-2
    at = np.array([0, 33, 69])
    np.testing.assert_allclose(
        ref.forward(weights, tokens, PUBLISHED, positions=at)[0],
        np.asarray(want)[:, at], atol=1e-5)


# -- the paged forward, XLA path and Pallas kernel ----------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_references_expanded_keys_and_values(
        impl):
    """The harness's own probe: prefill in 16-row and then unaligned 15-row
    chunks beside a decode row and pad rows, then decode, absorbed
    attention over the two layers of rows a double layer keeps in the
    engine's own cache."""
    cfg, _, forward, params = _model(
        attn_force_pallas=impl == "pallas-interpret")
    assert cfg.head_dim_ == 128
    assert pa.paged_attention_impl(cfg.head_dim_, BS,
                                   cfg.attn_force_pallas) == impl
    chk = dict(prompt_tokens=50, decode_steps=12)
    schedule = serve.probe_schedule(50, 12, 16)
    assert any(len(rows) < 16 for rows in schedule)          # pad rows
    assert any({s for s, _ in rows} == {0, 1} for rows in schedule)
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params,
                                       fc.engine_config(), chk)
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert got.shape == want.shape == (2, 62, 256)
    assert fc.worst(got, want) < SOUND


def test_a_share_of_the_experts_is_the_references_share():
    """Experts 2 and 3 of 8 held: the program and the reference leave the
    other real experts' terms out alike, and the identity term is whole."""
    cfg, _, forward, params = _model(SHARE)
    assert cfg.experts_held == (2, 2) and cfg.num_experts == 8
    layer = params["params"]["model"]["layers_double"]["layer"]
    assert layer["moe"]["experts"]["gate"].shape == (2, 2, 64, 32)
    assert layer["moe"]["router"]["kernel"].shape == (2, 64, 12)
    chk = dict(prompt_tokens=40, decode_steps=6)
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(9, cfg, forward, params,
                                       fc.engine_config(), chk)
    ref, weights = _reference(params, SHARE)
    want = np.asarray(ref.forward(weights, seqs, SHARE)[0])
    assert fc.worst(got, want) < SOUND
    with pytest.raises(KeyError, match="held elsewhere"):
        weights("mlp.experts.gate_proj", 0, 4)


# -- the faults the comparison must not pass -----------------------------------

FAULTS = sorted(faults(8)) + ["s_q_left_out", "s_kv_left_out"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_put_into_the_program_fails_the_comparison(fault):
    """The identity term left out, the weights renormalised, the bank's
    output added after ``FFN_0`` and not at the layer's end, a bfloat16
    router, and either ``mla_scale`` factor left out: each reads a
    thousand times the sound program's error or more, through the paged
    forward (the sound one is under 3e-5 above)."""
    flags = {"s_q_left_out": dict(mla_scale_q_lora=False),
             "s_kv_left_out": dict(mla_scale_kv_lora=False)}
    cfg, _, forward, params = _model(**flags.get(fault, {}))
    chk = dict(prompt_tokens=40, decode_steps=6)

    def probe():
        with jax.default_matmul_precision("highest"):
            return serve.probe_logits(9, cfg, forward, params,
                                      fc.engine_config(), chk)

    if fault in flags:
        seqs, got = probe()
    else:
        with faults(8)[fault]():
            seqs, got = probe()
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert fc.worst(got, want) > 3e-2, fault


# -- the router ---------------------------------------------------------------

def test_the_bias_chooses_and_the_unbiased_probability_weighs():
    rng = np.random.RandomState(3)
    x = rng.randn(40, 16).astype(np.float32)
    router = RouterSoftmaxBias(num_experts=12, top_k=3, scale=6.0,
                               param_dtype=jnp.float32)
    params = meta.unbox(router.init(jax.random.key(0), jnp.asarray(x)))
    kernel = rng.randn(16, 12).astype(np.float32)
    bias = (0.05 * rng.randn(12)).astype(np.float32)
    params = {"params": {"kernel": jnp.asarray(kernel),
                         "bias": jnp.asarray(bias)}}
    with jax.default_matmul_precision("highest"):
        gates, idx, _ = router.apply(params, jnp.asarray(x))
    logits = x.astype(np.float64) @ kernel
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-(p + bias), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), want)
    np.testing.assert_allclose(np.asarray(gates),
                               6 * np.take_along_axis(p, want, -1),
                               rtol=2e-5)
    # the bias moved a choice somewhere, and the weights are not normalised
    assert (want != np.argsort(-p, axis=-1, kind="stable")[:, :3]).any()
    sums = np.asarray(gates).sum(-1)
    assert (sums < 6).all() and (6 - sums).max() > 0.5
    assert gates.dtype == jnp.float32
    # equal scores take the lower index
    flat = {"params": {"kernel": jnp.zeros((16, 12)),
                       "bias": jnp.zeros((12,))}}
    assert np.asarray(router.apply(flat, jnp.asarray(x))[1]).tolist() \
        == [[0, 1, 2]] * 40


# -- the shares add up to the uncut layer ---------------------------------------

def test_four_shares_routed_sums_and_one_identity_term_are_the_layer():
    """A layer's expert bank as four devices of two real experts hold it,
    8 real and 4 identity slots: the shares' routed sums, added, plus the
    identity term counted once (every share computes it whole, for its
    own rows) equal the plain reference's uncut bank; the counts are
    ``[kept, dropped, elsewhere, identity]`` by a NumPy count."""
    ps.initialize_model_parallel()
    rng = np.random.RandomState(7)
    x = rng.randn(16, 64).astype(np.float32)
    valid = np.arange(16) < 13                   # three pad rows

    def moe(held):
        return MoE(num_experts=8, identity_experts=4, hidden_size=64,
                   intermediate_size=32, top_k=3, capacity_factor=None,
                   router_type="softmax_bias", router_scale=6.0, held=held,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    whole = meta.unbox(moe(None).init(jax.random.key(1), jnp.asarray(x),
                                      valid=jnp.asarray(valid)))
    tree = jax.tree_util.tree_map(
        lambda w: 0.3 * jax.random.normal(jax.random.key(w.size), w.shape),
        whole)["params"]
    tree["router"]["bias"] = 0.1 * tree["router"]["bias"]
    leaves = harness.load_plugin("families", "llama")._leaves

    def weights(name, layer=None, expert=None):
        if name == "mlp.router.classifier":
            return np.asarray(tree["router"]["kernel"]).T
        if name == "mlp.router.e_score_correction_bias":
            return np.asarray(tree["router"]["bias"])
        which = name.removeprefix("mlp.experts.").removesuffix("_proj")
        (w,) = [v[expert] for k, v in leaves(tree["experts"]).items()
                if which in k.split("/")[0].split("_")]
        return np.asarray(w).T

    ref = harness.load_plugin("reference", "longcat_flash_f32")
    config = dict(PUBLISHED)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.expert_bank(jnp.asarray(x), weights, 0,
                                           config)[0])
        chosen, w, _ = ref.route(jnp.asarray(x), weights, 0, config)
        identity = np.asarray(ref.identity_term(jnp.asarray(x), chosen, w,
                                                config))
    chosen = np.asarray(chosen)
    assert np.abs(identity)[valid].max() > 0.05
    assert (chosen[valid] >= 8).any() and (chosen[valid] < 8).any()

    def of(held):
        p = {"params": dict(tree, experts={
            k: v[held[0]:held[0] + held[1]]
            for k, v in tree["experts"].items()})}
        with jax.default_matmul_precision("highest"):
            y, aux = moe(held).apply(p, jnp.asarray(x),
                                     valid=jnp.asarray(valid))
        return np.asarray(y), np.asarray(aux["assignments"])

    shares = [of((first, 2)) for first in range(0, 8, 2)]
    routed = sum(y - identity for y, _ in shares)
    np.testing.assert_allclose((routed + identity)[valid], uncut[valid],
                               atol=3e-5)
    assert float(np.abs(shares[0][0])[~valid].max()) == 0.0   # the pads
    assert sum(np.abs(y - identity)[valid].max() > 0.02
               for y, _ in shares) >= 3          # the shares matter
    # [kept, dropped, elsewhere, identity] of 13 real rows x top 3
    real = chosen[valid]
    for first, (_, counts) in zip(range(0, 8, 2), shares):
        mine = int(((real >= first) & (real < first + 2)).sum())
        assert counts.tolist() == [mine, 0, int((real < 8).sum()) - mine,
                                   int((real >= 8).sum())]
    # all the real experts on one device: nothing elsewhere, and the
    # reference's share is the package's
    y, counts = of((0, 8))
    np.testing.assert_allclose(y[valid], uncut[valid], atol=3e-5)
    assert counts[2] == 0 and counts.sum() == 13 * 3
    with jax.default_matmul_precision("highest"):
        third = np.asarray(ref.expert_bank(
            jnp.asarray(x), weights, 0,
            dict(config, n_routed_experts=2,
                 share=dict(n_routed_experts_published=8,
                            first_expert=4)))[0])
    np.testing.assert_allclose(shares[2][0][valid], third[valid], atol=3e-5)


# -- the kernel at 64 heads, and GLM's walk as it was --------------------------

def test_the_walk_at_24_stacked_heads_is_what_it_was_and_64_heads_tile():
    """GLM's 20 heads ride as 24 in a tile of 8 rows, a decode row's run
    8 blocks and a tile's shared pairs 4 over the whole tile (one slab of
    192 rows), as before this family; 64 heads are their own number of
    whole sublanes, a tile of 8 rows is 512 stacked rows, a run 8 blocks,
    and a tile that tall (float32 scores ``[512, 128]`` are
    ``SCORE_BYTES`` already) is scored in slabs of 2 packed rows (128
    stacked rows), a shared unit 4 blocks: all from the shapes
    (``unit_blocks``), no family's name."""
    row = mla.row_width(512, 64)
    assert (mla.stacked_heads(20), pa.tile_rows(24, 128)) == (24, 8)
    assert mla._unit_lengths(24, 8 * 24, row, 128, 2) == (8, 4, 8 * 24)
    assert (mla.stacked_heads(64), pa.tile_rows(64, 128)) == (64, 8)
    assert mla._unit_lengths(64, 8 * 64, row, 128, 2) == (8, 4, 2 * 64)
    assert pa.narrow_rows(64) == 64
    # a slab is whole packed rows: 128 heads a row are a slab each, and
    # a tile that already takes two blocks a unit is not cut
    assert mla._unit_lengths(128, 8 * 128, row, 128, 2) == (4, 4, 128)
    assert mla._unit_lengths(32, 8 * 32, row, 128, 2) == (8, 2, 8 * 32)
    # the tests' blocks of 16 positions are an eighth of the cell's: at
    # an eighth of the scores' room the rule is the cell's
    assert mla._unit_lengths(64, 8 * 64, row, 16, 4) == (8, 8, 8 * 64)


def _slabs_as_in_the_cell(monkeypatch):
    """Blocks of 16 positions under scores of 48 KiB: the cell's blocks
    of 128 under its 384 KiB, so a tile of 512 stacked rows is cut as the
    cell's is."""
    monkeypatch.setattr(pa, "SCORE_BYTES", pa.SCORE_BYTES // 8)
    assert mla._unit_lengths(64, 8 * 64, 640, 16, 4) == (8, 4, 2 * 64)


#: rows of a step, ``(slot, position)``, over the slots of
#: :func:`_scene_64`; a pad row is ``(5, PAD_POSITION)``, slot 4 unmapped
_PAD = (5, PAD_POSITION)
_KERNEL_CASES = {
    # the tile whole (the tests' small blocks leave the scores room): a
    # chunk in unaligned pieces, decode rows of which two share prefix
    # blocks, an unmapped row and a pad row
    "one_slab": (False, [(0, p) for p in range(41, 48)]
                 + [(1, 39), (2, 89), (3, 4), (4, 7), _PAD]),
    # a chunk of 20 rows over three tiles (8, 8 and 4 rows): the first
    # tile's five shared blocks are a unit of four and one alone, the
    # others' six a unit of four and a short one of two; the third
    # tile's last two slabs name nothing and are skipped
    "a_chunk_over_three_tiles": (True, [(2, p) for p in range(70, 90)]),
    # two chunk rows in the first slab, decode rows in the others: the
    # shared units skip three slabs of four
    "slabs_that_name_no_block": (
        True, [(0, 58), (0, 59), (2, 89), (3, 4), (1, 39), (4, 7), _PAD,
               _PAD]),
    # slots 0 and 1 share their first two blocks: packed rows 1 and 2,
    # in two slabs, each beside a row that names neither block
    "a_prefix_shared_across_slabs": (
        True, [(2, 89), (0, 59), (1, 39), (3, 4)]),
    # and in one slab, with a pad row, an unmapped row and a decode row
    # beside them
    "a_prefix_shared_in_one_slab": (
        True, [(0, 59), (1, 39), _PAD, (4, 7), (2, 89), (3, 4)]),
}


def _scene_64(rng, nb=24, bs=16, maxb=6, rank=512, rope=64):
    row = mla.row_width(rank, rope)
    pool = rng.randn(2, nb, bs, row)
    pool[..., rank + rope:] = 0
    tables = np.full((5, maxb), -1)
    tables[0, :4] = [3, 7, 1, 9]            # 60 positions
    tables[1, :3] = [3, 7, 12]              # shares its first two blocks
    tables[2, :6] = rng.permutation(np.arange(13, 24))[:6]
    tables[3, :1] = [2]
    pos = np.full((nb, bs), PAD_POSITION)
    for s, length in enumerate([60, 40, 90, 5]):
        for p in range(length):
            pos[tables[s, p // bs], p % bs] = p
    return pool, pos, tables, row


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_kernel_equals_the_xla_path_at_64_heads_of_the_published_row(
        case, dtype, atol, monkeypatch):
    """Rows of 576 values on 640 lanes, values of 512, 64 heads (a tile
    of 8 rows x 64), float32 and bf16 pools, in interpret mode against
    the gather reference: the tile whole, and cut into slabs of 2 packed
    rows with shared units of 4 blocks as the cell's is
    (``_KERNEL_CASES``). The walk serves every live (row, column) once
    and the host's counts of the fetches and of the shared pairs' units
    are the walk's."""
    slabs, rows = _KERNEL_CASES[case]
    if slabs:
        _slabs_as_in_the_cell(monkeypatch)
    rng = np.random.RandomState(6)
    n, rank, rope, nb, bs = 64, 512, 64, 24, 16
    pool, pos, tables, row = _scene_64(rng)
    slot, q_pos = (np.array(x) for x in zip(*rows))
    tok_tables = tables[np.minimum(slot, 4)]
    q = rng.randn(len(rows), n, row)
    q[..., rank + rope:] = 0
    args = (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tok_tables, jnp.int32),
            jnp.asarray(q_pos, jnp.int32), 1, rank, 192 ** -0.5)
    want = np.asarray(mla.mla_paged_attention(*args, force_pallas=False),
                      np.float32)
    got = np.asarray(mla.mla_paged_attention(*args, force_pallas=True),
                     np.float32)
    assert got.shape == want.shape == (len(rows), n, rank)
    live = (slot < 4) & (q_pos < PAD_POSITION)
    np.testing.assert_allclose(got[live], want[live], atol=atol)
    assert (got[~live] == 0).all()
    if dtype != jnp.float32:
        return
    # the walk in the kernel's units, and the host's counts of it
    lengths = mla._unit_lengths(n, 8 * n, row, bs, 4)
    attends = np.asarray(pa.column_live(tok_tables, np.arange(6),
                                        q_pos[:, None], bs))
    walk = pa.tile_walk(jnp.asarray(tok_tables, jnp.int32),
                        jnp.asarray(q_pos, jnp.int32), bs, nb, n)
    units = np.zeros((2,), np.int64)
    kinds = check_tile_walk(
        type(walk)(*(None if x is None else np.asarray(x) for x in walk)),
        attends, tok_tables, 8, n,
        runs=(mla.run_walk(walk, nb, n, row, bs, 4), *lengths[:2]),
        shared_units=units)
    served = np.where(attends, tok_tables, -1)
    assert tuple(mla.block_fetches(served, n, row, bs, 4)) == tuple(kinds)
    assert tuple(mla.shared_blocks(served, n, row, bs, 4)) == tuple(units)
    assert units.tolist() == {
        "one_slab": [3, 0], "a_chunk_over_three_tiles": [16, 1],
        "slabs_that_name_no_block": [4, 0],
        "a_prefix_shared_across_slabs": [2, 0],
        "a_prefix_shared_in_one_slab": [2, 0]}[case]


def test_a_shared_pair_left_over_is_a_unit_alone(monkeypatch):
    """A tile whose shared pairs are one more than its units hold: the
    last is a unit by itself, by the walk's ``lens`` and by the host's
    count; where the scores leave a unit one block, every one is."""
    _slabs_as_in_the_cell(monkeypatch)
    bs, nb, n, row = 16, 24, 64, 640
    tables = np.full((2, 6), -1)
    tables[:, :5] = [3, 7, 1, 9, 11]        # one prefix of five blocks
    tables[1, 5] = 12
    q_pos = np.array([79, 95])
    attends = np.asarray(pa.column_live(tables, np.arange(6),
                                        q_pos[:, None], bs))
    served = np.where(attends, tables, -1)
    walk = pa.tile_walk(jnp.asarray(tables, jnp.int32),
                        jnp.asarray(q_pos, jnp.int32), bs, nb, n)
    units = np.zeros((2,), np.int64)
    check_tile_walk(
        type(walk)(*(None if x is None else np.asarray(x) for x in walk)),
        attends, tables, 8, n,
        runs=(mla.run_walk(walk, nb, n, row, bs, 4), 8, 4),
        shared_units=units)
    assert tuple(mla.shared_blocks(served, n, row, bs, 4)) == tuple(
        units) == (4, 1)
    monkeypatch.setattr(mla, "unit_blocks", lambda rows, *_: 1)
    assert mla._unit_lengths(n, 8 * n, row, bs, 4) == (1, 1, 2 * n)
    assert tuple(mla.shared_blocks(served, n, row, bs, 4)) == (0, 5)


# -- through ServingEngine ------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests, one of them preempted on the way, through one
    engine."""
    cfg, _, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_moe_assignments_total", "nxd_moe_held_total",
        "nxd_moe_identity_total", "nxd_paged_columns_total",
        "nxd_mla_block_fetches_total", "nxd_mla_shared_blocks_total",
        "nxd_engine_rows_total"),
        lengths=[70, 40, 5], new=[30, 12, 4], num_blocks=9, max_slots=2)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)
    # one of the two was preempted and re-admitted on the way
    fc.check_preempted_and_whole(served.eng)


def test_the_counters_tell_identity_from_routed_and_held(served):
    counters = served.counters
    rows = counters["nxd_engine_rows_total"]
    choices = (rows["decode"] + rows["prefill"]) * 3 * 2
    identity = counters["nxd_moe_identity_total"]
    assert set(identity) == {"identity", "routed"}
    assert identity["identity"] + identity["routed"] == choices
    assert 0 < identity["identity"] < choices
    # every real expert is held: the routed choices are all kept
    assert counters["nxd_moe_assignments_total"] == {
        "kept": identity["routed"], "dropped": 0}
    assert counters["nxd_moe_held_total"] == {
        "held": identity["routed"], "elsewhere": 0}
    fetches = counters["nxd_mla_block_fetches_total"]
    assert fetches["in_run"] > 0 and fetches["whole"] > 0
    shared = counters["nxd_mla_shared_blocks_total"]
    assert sum(shared.values()) == fetches["whole"] and shared["in_unit"] > 0


def test_the_cache_has_two_layers_of_rows_a_decoder_layer():
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, fc.engine_config())
    cache = eng.cache
    assert isinstance(cache, paging.LatentPagedCache)
    assert cache.rows.shape == (4, 40, BS, 128)
    assert cache.moe_counts.shape == (4,)
    kind = cfg.serving_family().cache_kind
    assert (kind.name, kind.attentions, kind.row) == ("latent", 2, 128)
    assert [kind.stack_index(layer, which) for layer in (0, 1)
            for which in (0, 1)] == [0, 1, 2, 3]
    assert [leaf.leaf for leaf in eng._device_counts] == ["moe_counts"]
    # GLM's kind is the same class at one attention a layer and two counts
    from neuronx_distributed_tpu.models.glm_moe_lite import GlmMoeLiteConfig

    glm = GlmMoeLiteConfig().serving_family().cache_kind
    assert (type(glm), glm.attentions, glm.moe_leaf.entries) == (
        type(kind), 1, 2)
    # the published pool row: 576 values on 640 lanes, eight layers of
    # rows for four double layers
    from neuronx_distributed_tpu.models.longcat_flash import (
        LongcatFlashConfig)

    real = LongcatFlashConfig(num_layers=4)
    assert (real.head_dim_, real.q_lora_scale, real.kv_lora_scale) == (
        640, 2.0, 12 ** 0.5)
    shape = jax.eval_shape(lambda: real.serving_family().cache_kind.init_cache(
        real, num_blocks=2, block_size=16, table_rows=1,
        max_blocks_per_seq=2, dtype=jnp.bfloat16)).rows.shape
    assert shape == (8, 2, 16, 640)


def test_a_rows_second_attention_reads_its_own_layer_of_rows():
    """After a prefill the two attentions of a double layer have written
    unlike rows side by side in the stack, every layer of rows holds the
    positions written, and none past them."""
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, fc.engine_config())
    eng.submit(list(range(20)), 4)
    for _ in range(3):
        eng.step()
    eng._settle()
    block = int(eng._tables[0][0])
    rows = np.asarray(eng.cache.rows[:, block])
    assert all(np.abs(rows[i, :BS]).max() > 0 for i in range(4))
    assert not np.allclose(rows[0], rows[1])
    assert not np.allclose(rows[2], rows[3])
    assert (rows[..., 40:] == 0).all()           # the idle lanes


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES[1:])
def test_refused_features_raise_by_name_with_their_reason(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, reason=True)


def test_prefix_sharing_maps_latent_blocks_of_both_attentions():
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


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", True), ("zero_expert_type", "zero"),
    ("attention_bias", True)])
def test_the_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match="are what is built"):
        _family().build({**PUBLISHED, key: value})


def test_the_config_says_which_layer_module_its_kind_has():
    """``run_layers`` asks the config; every other family's answer is the
    llama layer."""
    from neuronx_distributed_tpu.models import glm_moe_lite, llama
    from neuronx_distributed_tpu.models import longcat_flash as lc

    cfg = lc.LongcatFlashConfig(num_layers=1)
    assert isinstance(cfg.kind_config("double").decoder_layer(),
                      lc.LongcatFlashDecoderLayer)
    for other in (llama.LlamaConfig(), glm_moe_lite.GlmMoeLiteConfig()):
        layer = other.decoder_layer(name="layer")
        assert type(layer) is llama.LlamaDecoderLayer
        assert layer.name == "layer"
    assert dataclasses.replace(cfg, mla_scale_q_lora=False).q_lora_scale \
        == 1.0
