"""GLM-4.7-Flash (latent attention, a dense layer then expert layers under
a sigmoid router with a selection bias) through the model, the paged
forward, the ``mla_paged_attention`` kernel and ``ServingEngine``, against
the benchmark's plain reference ``benchmarks/reference/glm_moe_lite_f32.py``
(which expands ``kv_b`` for every position and head and has no capacity).

Tiny widths with every mechanism on: hidden 64, 4 heads of 24 + 8 query
values over a latent of 32 and a rotary key of 8 (a pool row of 128
lanes), values of 16, a query rank of 48, one dense layer (160) and two
expert layers of 8 experts (32 wide), top 3, scale 1.8, a shared expert,
pool blocks of 16. The weights are seeded with norm multipliers of order
one and a selection bias that changes the choice.
"""

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
from neuronx_distributed_tpu.modules.moe import (ExpertMLPs, RouterSigmoid,
                                                 build_dispatch_combine)
from neuronx_distributed_tpu.ops import mla_attention as mla
from neuronx_distributed_tpu.ops import paged_attention as pa

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from runners import serve  # noqa: E402
from walk_checks import check_tile_walk  # noqa: E402  (tests/)

BS = 16
PUBLISHED = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=3,
    n_shared_experts=1, routed_scaling_factor=1.8, norm_topk_prob=True,
    hidden_act="silu", attention_bias=False,
    topk_method="noaux_tc", n_group=1, topk_group=1, rope_theta=1e6,
    rope_scaling=None, partial_rotary_factor=1, rms_norm_eps=1e-5,
    max_position_embeddings=4096, num_nextn_predict_layers=0,
    family="glm_moe_lite", reference="glm_moe_lite_f32")


@fc.once_a_module
def _model(**kw):
    family = harness.load_plugin("families", "glm_moe_lite")
    cfg, model, forward = family.build(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if name.endswith("['bias']"):
            return 0.2 * noise          # of the scores' own spread

    return cfg, model, forward, fc.seeded_weights(shapes, special)


def _reference(params):
    return (harness.load_plugin("reference", "glm_moe_lite_f32"),
            harness.load_plugin("families", "glm_moe_lite").published(
                params, PUBLISHED))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


# -- the module's full forward ----------------------------------------------

def test_the_parameters_are_one_stack_a_feed_forward_kind():
    cfg, _, _, params = _model()
    assert cfg.runs() == (("dense", 0, 1), ("moe", 0, 2))
    layers = params["params"]["model"]
    dense, moe = (layers[f"layers_{k}"]["layer"] for k in ("dense", "moe"))
    assert dense["mlp"]["gate_kernel"].shape == (1, 64, 160)
    assert moe["moe"]["experts"]["gate"].shape == (2, 8, 64, 32)
    assert moe["moe"]["shared"]["gate_kernel"].shape == (2, 64, 32)
    assert moe["moe"]["router"]["bias"].shape == (2, 8)
    for stack, depth in ((dense, 1), (moe, 2)):
        assert stack["attn"]["q_a"].shape == (depth, 64, 48)
        assert stack["attn"]["kv_a"].shape == (depth, 64, 40)
        assert stack["attn"]["k_up"].shape == (depth, 4, 24, 32)
        assert stack["attn"]["v_up"].shape == (depth, 4, 32, 16)
    # kv_b_proj in the checkpoint's shape, a head's key rows then its values
    _, weights = _reference(params)
    kv_b = np.asarray(weights("kv_b_proj", 2))
    assert kv_b.shape == (4 * (24 + 16), 32)
    np.testing.assert_array_equal(
        kv_b[40:64], np.asarray(moe["attn"]["k_up"][1, 1]))
    np.testing.assert_array_equal(
        kv_b[64:80], np.asarray(moe["attn"]["v_up"][1, 1]).T)


def test_full_forward_matches_the_reference_with_every_mechanism_on():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 70))
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(tokens))
    ref, weights = _reference(params)
    want, margins = ref.forward(weights, tokens, PUBLISHED)
    assert got.shape == want.shape == (2, 70, 256)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(np.std(want)))
    assert margins.shape == (2, 2, 70) and float(margins.min()) >= 0
    # the bias changes the choice: without it the logits differ
    bare = jax.tree_util.tree_map_with_path(
        lambda p, x: x * 0 if jax.tree_util.keystr(p).endswith("['bias']")
        else x, params)
    with jax.default_matmul_precision("highest"):
        other = model.apply(bare, jnp.asarray(tokens))
    assert float(jnp.abs(other - got).max()) > 1e-3 * float(np.std(want))
    at = np.array([0, 33, 69])
    np.testing.assert_allclose(
        ref.forward(weights, tokens, PUBLISHED, positions=at)[0],
        np.asarray(want)[:, at], atol=1e-5)


@pytest.mark.parametrize("rows", [40, 5])
def test_an_experts_capacity_is_the_rows_whatever_their_number(rows):
    """More rows than experts and fewer: every token reaches every expert
    it chose, as in the reference, which has no capacity."""
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(2).randint(0, 256, (1, rows))
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(tokens))
    ref, weights = _reference(params)
    want, _ = ref.forward(weights, tokens, PUBLISHED)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(np.std(want)))


# -- the paged forward, XLA path and Pallas kernel ----------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_references_expanded_keys_and_values(
        impl):
    """The harness's own probe: prefill in 16-row and then unaligned 15-row
    chunks beside a decode row and pad rows, then decode, absorbed
    attention over the latent rows of the engine's own cache."""
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
    np.testing.assert_allclose(got, want, atol=3e-5 * float(np.std(want)))


# -- the kernel -----------------------------------------------------------------

@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_kernel_equals_the_xla_path_at_the_published_row(dtype, atol):
    """Rows of 576 values on 640 lanes, values of 512, 20 heads: a prefill
    chunk in unaligned pieces, decode rows of other slots of which two
    share prefix blocks, an unmapped row and pad rows."""
    rng = np.random.RandomState(6)
    layers, nb, bs, maxb, n, rank, rope = 2, 24, 16, 6, 20, 512, 64
    row = mla.row_width(rank, rope)
    assert row == 640 and mla.stacked_heads(n) == 24
    pool = rng.randn(layers, nb, bs, row)
    pool[..., rank + rope:] = 0
    tables = np.full((5, maxb), -1)
    tables[0, :4] = [3, 7, 1, 9]            # 60 positions
    tables[1, :3] = [3, 7, 12]              # shares its first two blocks
    tables[2, :6] = rng.permutation(np.arange(13, 24))[:6]
    tables[3, :1] = [2]
    lengths = [60, 40, 90, 5]
    pos = np.full((nb, bs), PAD_POSITION)
    for s, length in enumerate(lengths):
        for p in range(length):
            pos[tables[s, p // bs], p % bs] = p
    # chunk of slot 0 at 37..47 (unaligned), decode rows of 1, 2, 3, a row
    # of the unmapped slot 4 and a pad row
    rows = ([(0, p) for p in range(37, 48)] + [(1, 39), (2, 89), (3, 4)]
            + [(4, 7)] + [(5, PAD_POSITION)])
    slot, q_pos = (np.array(x) for x in zip(*rows))
    tok_tables = tables[np.minimum(slot, 4)]
    q = rng.randn(len(rows), n, row)
    q[..., rank + rope:] = 0
    args = (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tok_tables, jnp.int32),
            jnp.asarray(q_pos, jnp.int32), 1, rank, 0.0625)
    want = mla.mla_paged_attention(*args, force_pallas=False)
    got = mla.mla_paged_attention(*args, force_pallas=True)
    assert got.shape == want.shape == (len(rows), n, rank)
    live = np.arange(len(rows)) < 14
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live], atol=atol)
    assert (np.asarray(got, np.float32)[~live] == 0).all()
    # the two slots that share a prefix share its fetch: the walk lists a
    # (column, block) pair once a tile
    walk = pa.tile_walk(jnp.asarray(tok_tables, jnp.int32),
                        jnp.asarray(q_pos, jnp.int32), bs, nb, 24)
    assert walk.served.shape == (2, 8 * 24, maxb)
    first = set(zip(np.asarray(walk.cols)[:int(walk.count[0])].tolist(),
                    np.asarray(walk.blocks)[:int(walk.count[0])].tolist()))
    assert first == {(0, 3), (1, 7), (2, 1)}
    # a decode row's own blocks are narrow pairs, its 24 stacked heads
    # one group of the tile (slot 2's row is the tile's fifth); a block
    # that rows of two groups name is computed over the whole tile
    second = slice(8 * maxb, 8 * maxb + int(walk.count[1]))
    narrow = dict(zip(np.asarray(walk.blocks)[second].tolist(),
                      np.asarray(walk.narrow)[second].tolist()))
    assert all(narrow[b] == 4 * 24 for b in tables[2])
    assert narrow[2] == 5 * 24 and narrow[3] == -1 and narrow[12] == 3 * 24
    # and the kernel's units of it, at the lengths these shapes take
    live = np.asarray(pa.column_live(tok_tables, np.arange(maxb),
                                     q_pos[:, None], bs))
    lengths = mla._unit_lengths(24, 8 * 24, row, bs, 4)[:2]
    units = np.zeros((2,), np.int64)
    kinds = check_tile_walk(
        type(walk)(*(None if x is None else np.asarray(x) for x in walk)),
        live, tok_tables, 8, 24,
        runs=(mla.run_walk(walk, nb, 24, row, bs, 4), *lengths),
        shared_units=units)
    served = np.where(live, tok_tables, -1)
    assert tuple(kinds) == tuple(mla.block_fetches(
        served, n, row, bs, 4)) == (6, 2, 6)
    # the first tile's three shared pairs are one unit, the second's
    # three another
    assert tuple(units) == tuple(mla.shared_blocks(
        served, n, row, bs, 4)) == (6, 0)


def _latent_scene(lengths, shared=(), nb=48, bs=16, maxb=12, n=20,
                  rank=128, rope=64, seed=11):
    """A pool of random float32 rows in which slot ``s`` holds
    ``lengths[s]`` positions in blocks of its own (``shared``: ``(slot,
    other, blocks)``, the slot's first blocks are the other's), and
    queries for ``rows`` (built by the caller): ``(pool, pos, tables, q
    maker)``."""
    rng = np.random.RandomState(seed)
    row = mla.row_width(rank, rope)
    pool = rng.randn(1, nb, bs, row)
    pool[..., rank + rope:] = 0
    free = list(rng.permutation(nb))
    tables = np.full((len(lengths) + 1, maxb), -1)     # the last: unmapped
    pos = np.full((nb, bs), PAD_POSITION)
    for s, length in enumerate(lengths):
        for c in range(-(-length // bs)):
            tables[s, c] = free.pop()
    for s, other, blocks in shared:
        tables[s, :blocks] = tables[other, :blocks]
    for s, length in enumerate(lengths):
        for p in range(length):
            pos[tables[s, p // bs], p % bs] = p

    def queries(count):
        q = rng.randn(count, n, row)
        q[..., rank + rope:] = 0
        return q

    return pool, pos, tables, queries


#: name -> (positions a slot holds, shared prefixes, the packed rows as
#: (slot, position), what block_fetches must count: in_run, alone, whole),
#: under runs of 4 (one row's pairs) and 2 (shared pairs), blocks of 16
_RUN_CASES = {
    # 7 live blocks: a run of four and one of three
    "no_multiple_of_the_run": ([102], (), [(0, 101)], (7, 0, 0)),
    # 8 live blocks, the last holds 4 positions: two full runs
    "last_block_partly_filled": ([116], (), [(0, 115)], (8, 0, 0)),
    # one live column: a run of one
    "a_single_live_column": ([6], (), [(0, 5)], (0, 1, 0)),
    # a tile of 8 decode rows of 8 slots, 1 to 10 blocks each
    "eight_decode_rows_a_tile": (
        [10, 150, 33, 64, 97, 16, 160, 120], (),
        [(s, n - 1) for s, n in enumerate(
            [10, 150, 33, 64, 97, 16, 160, 120])],
        (42, 2, 0)),
    # a chunk's 5 rows (3 blocks, shared by the tile) and 3 decode rows
    "a_decode_row_beside_a_chunk": (
        [42, 70, 20, 130], (),
        [(0, p) for p in range(37, 42)] + [(1, 69), (2, 19), (3, 129)],
        (14, 2, 3)),
    # two decode rows whose first two blocks are one prefix: those pairs
    # are the tile's, the rest run
    "two_rows_share_a_prefix": (
        [90, 75], ((1, 0, 2),), [(0, 89), (1, 74)], (7, 0, 2)),
}


@pytest.mark.parametrize("case", list(_RUN_CASES) + ["pad_rows_between"])
def test_a_decode_rows_blocks_are_walked_in_runs(case, monkeypatch):
    """The kernel's units against the gather reference in float32,
    interpret mode, with runs of 4 and 2 so that small tables cut them:
    each case's rows equal the reference's, the walk serves every live
    (row, column) exactly once in units of one kind, and the host's count
    of the fetches is the case's. ``pad_rows_between``: pad rows in the
    middle and at the end of the batch leave the real rows' bits as they
    were."""
    monkeypatch.setattr(mla, "unit_blocks",
                        lambda rows, *_: 4 if rows == 24 else 2)
    n, rank, bs, nb = 20, 128, 16, 48
    if case == "pad_rows_between":
        lengths, shared = [102, 6, 75], ()
        rows = [(0, 101), (1, 5), (2, 74)]
        counts = None
    else:
        lengths, shared, rows, counts = _RUN_CASES[case]
    pool, pos, tables, queries = _latent_scene(lengths, shared, nb=nb, bs=bs)
    q = queries(len(rows))

    def run(rows, q, force):
        slot, q_pos = (np.array(x) for x in zip(*rows))
        return np.asarray(mla.mla_paged_attention(
            jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(pos, jnp.int32),
            jnp.asarray(tables[np.minimum(slot, len(lengths))], jnp.int32),
            jnp.asarray(q_pos, jnp.int32), 0, rank, 0.125,
            force_pallas=force))

    got = run(rows, q, True)
    np.testing.assert_allclose(got, run(rows, q, False), atol=2e-5)
    if counts is None:
        # the same rows with pad rows among and behind them
        pad = (len(lengths), PAD_POSITION)
        spread = [rows[0], pad, rows[1], pad, pad, rows[2]] + [pad] * 5
        zeros = np.zeros_like(q[:1])
        wide = np.concatenate([q[:1], zeros, q[1:2], zeros, zeros, q[2:]]
                              + [zeros] * 5)
        padded = run(spread, wide, True)
        np.testing.assert_array_equal(padded[[0, 2, 5]], got)
        assert (padded[[1, 3, 4, 6, 7, 8, 9, 10]] == 0).all()
        return
    slot, q_pos = (np.array(x) for x in zip(*rows))
    tok_tables = tables[slot]
    live = np.asarray(pa.column_live(tok_tables, np.arange(tables.shape[1]),
                                     q_pos[:, None], bs))
    walk = pa.tile_walk(jnp.asarray(tok_tables, jnp.int32),
                        jnp.asarray(q_pos, jnp.int32), bs, nb, 24)
    runs = mla.run_walk(walk, nb, 24, 256, bs, 4)
    units = np.zeros((2,), np.int64)
    kinds = check_tile_walk(
        type(walk)(*(None if x is None else np.asarray(x) for x in walk)),
        live, tok_tables, 8, 24, runs=(runs, 4, 2), shared_units=units)
    served = np.where(live, tok_tables, -1)
    fetches = mla.block_fetches(served, n, 256, bs, 4)
    assert tuple(fetches) == tuple(kinds) == counts
    # the shared pairs in units of 2: a tile's odd one out is alone
    assert tuple(mla.shared_blocks(served, n, 256, bs, 4)) == tuple(units)
    assert units.sum() == counts[2] and units[1] == counts[2] % 2
    assert fetches.sum() == int(np.asarray(walk.count).sum())


def test_on_a_tpu_no_shape_falls_to_the_reference(monkeypatch):
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    pa.paged_attention_impl.cache_clear()
    try:
        assert mla.mla_attention_impl(640, 512, 128) == "pallas"
        # a row of whole lanes and a rest that shares none (600 = 4 x 128
        # + 88) warns and serves; 576 = 4 x 128 + 64 is what a wide-key
        # pool lays in whole lanes (ops/paged_attention.keys_to_lanes)
        assert pa.paged_attention_impl(600, 128) == "xla"
        assert pa.paged_attention_impl(576, 128) == "pallas"
        for row, rank, bs in ((576, 512, 128), (640, 512, 16)):
            with pytest.raises(ValueError, match="don't tile"):
                mla.mla_attention_impl(row, rank, bs)
        with pytest.raises(ValueError, match="no whole lanes"):
            mla.mla_attention_impl(640, 500, 128)
        with pytest.raises(ValueError, match="don't tile"):
            pa.paged_attention_impl(600, 128, None, kernel_only=True)
    finally:
        pa.paged_attention_impl.cache_clear()


# -- the router and the dispatch ------------------------------------------------

def _route(logits, bias, top_k=2, scale=1.8):
    router = RouterSigmoid(num_experts=logits.shape[-1], top_k=top_k,
                           scale=scale, param_dtype=jnp.float32)
    # logits = x @ kernel with x the identity's rows
    params = {"params": {"kernel": jnp.asarray(logits, jnp.float32),
                         "bias": jnp.asarray(bias, jnp.float32)}}
    return router.apply(params, jnp.eye(logits.shape[0], dtype=jnp.float32))


def test_the_bias_changes_the_choice_and_not_the_weights():
    logits = np.array([[2.0, 1.0, 0.0, -1.0], [0.5, 0.4, 0.3, 0.2],
                       [1.0, 1.0, 1.0, 1.0], [0.0, 3.0, 0.0, 3.0]])
    s = 1 / (1 + np.exp(-logits))
    gates, idx, _ = _route(logits, np.zeros(4))
    assert idx.tolist() == [[0, 1], [0, 1], [0, 1], [1, 3]]   # ties: lower
    np.testing.assert_allclose(gates.sum(-1), 1.8, rtol=1e-6)
    np.testing.assert_allclose(
        gates[0], 1.8 * s[0, :2] / s[0, :2].sum(), rtol=1e-6)
    # a bias that lifts expert 3 over expert 1 in row 0 and 1
    gates_b, idx_b, _ = _route(logits, np.array([0.0, 0.0, 0.0, 0.6]))
    assert idx_b.tolist() == [[0, 3], [3, 0], [3, 0], [3, 1]]
    # weighed by s alone: the chosen experts' sigmoid scores, normalised
    picked = np.take_along_axis(s, np.asarray(idx_b), -1)
    np.testing.assert_allclose(
        gates_b, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # the reference chooses and weighs alike
    ref = harness.load_plugin("reference", "glm_moe_lite_f32")
    scores = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
    _, want = jax.lax.top_k(scores + jnp.array([0, 0, 0, 0.6]), 2)
    assert want.tolist() == idx_b.tolist() and hasattr(ref, "expert_layer")


def _crowded_step(real):
    """128 rows of which every ``128 / real``-th is real, 64 experts, top
    4: the first real rows and every pad would choose expert 0."""
    rng = np.random.RandomState(9)
    t, e, k, h = 128, 64, 4, 32
    valid = np.arange(t) % (t // real) == 0
    idx = rng.randint(0, e, (t, k))
    idx[:, 0] = np.where(np.arange(t) < 100, 0, idx[:, 0])  # crowd expert 0
    idx[~valid] = 0                                         # the pads too
    for row in idx[valid]:                                  # distinct picks
        row[1:] = rng.choice(np.arange(1, e), k - 1, replace=False)
    return (jnp.asarray(rng.randn(t, h), jnp.float32),
            jnp.asarray(rng.rand(t, k), jnp.float32), jnp.asarray(idx),
            valid)


@pytest.mark.parametrize("real", [64, 8])
def test_no_assignment_is_dropped_and_none_taken_by_a_pad_row(real):
    """At the factor where capacity is the step's rows every real
    assignment is kept, the pads' count nothing, and the real rows'
    outputs are what they are without the pads."""
    x, gates, idx, valid = _crowded_step(real)
    e, k = 64, 4
    bank = ExpertMLPs(num_experts=e, hidden_size=32, intermediate_size=16,
                      top_k=k, capacity_factor=e / k, dtype=jnp.float32)
    params = bank.init(jax.random.key(0), x, gates, idx)
    y, aux = bank.apply(params, x, gates, idx, valid=jnp.asarray(valid))
    assert aux["assignments"].tolist() == [real * k, 0]
    assert float(aux["dropped_fraction"]) == 0.0
    alone, _ = bank.apply(params, x[valid], gates[valid], idx[valid],
                          valid=jnp.ones(real, bool))
    np.testing.assert_allclose(y[valid], alone, atol=1e-5)
    assert float(jnp.abs(y[~valid]).max()) == 0.0
    # without the mask the pads crowd the real rows out of expert 0 at
    # any capacity under the step's rows
    _, _, dropped = build_dispatch_combine(gates, idx, e, 64)
    _, _, masked = build_dispatch_combine(gates, idx, e, 64,
                                          jnp.asarray(valid))
    assert float(dropped) > 0 and float(masked) == 0.0


def test_the_dropless_dispatch_counts_valid_rows_and_drops_none():
    """Under the blockwise dispatch, which drops nothing, ``valid`` rows
    only count (since PR 67: a family that declares ``moe_counts`` over
    the grouped product): every real row's choice is kept, the output is
    what it is without ``valid``, and a share of the experts (``held``)
    stays the capacity dispatch's alone (the one this family runs:
    ROADMAP R1)."""
    x, gates, idx, valid = _crowded_step(64)
    kw = dict(num_experts=64, hidden_size=32, intermediate_size=16,
              top_k=4, dispatch_mode="blockwise", block_size=8,
              dtype=jnp.float32)
    bank = ExpertMLPs(**kw)
    params = bank.init(jax.random.key(0), x, gates, idx)
    plain, _ = bank.apply(params, x, gates, idx)
    y, aux = bank.apply(params, x, gates, idx, valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(plain))
    assert aux["assignments"].tolist() == [int(np.sum(valid)) * 4, 0]
    with pytest.raises(ValueError, match="capacity dispatch"):
        ExpertMLPs(**kw, held=(0, 64)).apply(params, x, gates, idx,
                                             valid=jnp.asarray(valid))


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("attention_bias", True)])
def test_the_family_refuses_what_the_package_does_not_build(key, value):
    family = harness.load_plugin("families", "glm_moe_lite")
    with pytest.raises(ValueError, match="are what is built"):
        family.build({**PUBLISHED, key: value})


# -- through ServingEngine ------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests, one of them preempted on the way, through one
    engine."""
    cfg, _, _, params = _model()
    return fc.serve_three(cfg, params, (
        "nxd_moe_assignments_total", "nxd_paged_columns_total",
        "nxd_paged_block_visits_total", "nxd_mla_block_fetches_total",
        "nxd_mla_shared_blocks_total", "nxd_engine_rows_total"),
        lengths=[70, 40, 5], new=[30, 12, 4], num_blocks=9, max_slots=2)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_is_readmitted_and_gives_the_same_tokens(served):
    """9 blocks do not hold a and b: one is preempted, re-admitted, and
    still decodes what the reference does (above); the pool is whole at
    the end and the step compiled once."""
    fc.check_preempted_and_whole(served.eng)


def test_the_counters_of_the_latent_walk_and_of_the_experts(served):
    counters = served.counters
    rows = counters["nxd_engine_rows_total"]
    moe = counters["nxd_moe_assignments_total"]
    # top_k an expert layer a real row, pads none, nothing dropped
    assert moe == {"kept": (rows["decode"] + rows["prefill"]) * 3 * 2,
                   "dropped": 0}
    cols = counters["nxd_paged_columns_total"]
    assert cols["live"] > 0 and cols["skipped"] > 0
    assert sum(cols.values()) == sum(rows.values()) * 12
    visits = counters["nxd_paged_block_visits_total"]
    assert visits["fetched"] > 0 and visits["shared"] > 0
    assert visits["fetched"] + visits["shared"] == cols["live"]
    # every fetch is one of the latent kernel's three kinds: a prefill
    # chunk's blocks are the tile's, a decode row past its first block
    # runs
    fetches = counters["nxd_mla_block_fetches_total"]
    assert set(fetches) == {"in_run", "alone", "whole"}
    assert sum(fetches.values()) == visits["fetched"]
    assert fetches["in_run"] > 0 and fetches["whole"] > 0
    # and every shared one rode a unit of several blocks or was one
    shared = counters["nxd_mla_shared_blocks_total"]
    assert set(shared) == {"in_unit", "alone"}
    assert sum(shared.values()) == fetches["whole"] and shared["in_unit"] > 0


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


def test_a_sessions_latent_blocks_are_shipped_and_landed():
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, fc.engine_config())
    eng.submit(list(range(20)), 8)
    for _ in range(3):
        eng.step()
    blocks = [int(b) for b in eng._tables[0] if b >= 0]
    payload = paging.extract_blocks(eng.cache, blocks, PAD_POSITION)
    assert set(payload) == {"rows", "pos"}
    assert payload["rows"].shape == (3, len(blocks), BS, 128)
    other = ServingEngine(cfg, params, fc.engine_config())
    landed = paging.inject_blocks(other.cache, [5, 6][:len(blocks)], payload)
    np.testing.assert_array_equal(
        np.asarray(landed.rows[:, 5]), payload["rows"][:, 0])
    assert int(landed.pos[5, 3]) == 3 and float(
        jnp.abs(landed.rows[:, 5, :BS]).max()) > 0


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES[1:])
def test_refused_features_raise_by_name_with_their_reason(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, reason=True)


def test_the_cache_has_one_leaf_of_rows_and_none_of_heads():
    cfg, _, _, params = _model()
    cache = ServingEngine(cfg, params, fc.engine_config()).cache
    assert isinstance(cache, paging.LatentPagedCache)
    assert cache.rows.shape == (3, 40, BS, 128)
    assert cache.capacity == 40 * BS and cache.max_slots == 3
    shapes = {name: leaf.shape for name, leaf in zip(
        ("rows", "moe_counts", "pos", "block_tables", "lengths"),
        jax.tree_util.tree_leaves(cache))}
    assert shapes == {"rows": (3, 40, BS, 128), "moe_counts": (2,),
                      "pos": (40, BS), "block_tables": (3, 12),
                      "lengths": (3,)}
    heads, head_dim = cfg.num_heads, cfg.qk_nope_head_dim + \
        cfg.qk_rope_head_dim
    assert not any(heads in s and (head_dim in s or cfg.v_head_dim in s)
                   for s in shapes.values())
    # the published pool row: 576 values on 640 lanes, one leaf
    from neuronx_distributed_tpu.models.glm_moe_lite import GlmMoeLiteConfig

    real = GlmMoeLiteConfig()
    kind = real.serving_family().cache_kind
    assert (kind.name, kind.row, real.head_dim_) == ("latent", 640, 640)
    with pytest.raises(ValueError, match="int8"):
        kind.init_cache(real, num_blocks=2, block_size=16, table_rows=1,
                        max_blocks_per_seq=2, dtype=jnp.bfloat16,
                        quantized=True)


def test_the_family_and_not_the_cache_kind_declares_the_experts_counter():
    """A latent cache holds ``moe_counts`` only for a family that says its
    forward fills it; the engine asks the family, not the kind's name."""
    import dataclasses

    cfg, _, _, params = _model()
    family = cfg.serving_family()
    assert family.moe_counts and not paging.ServingFamily(
        forward=family.forward).moe_counts
    geometry = dict(num_blocks=2, block_size=BS, table_rows=1,
                    max_blocks_per_seq=2, dtype=jnp.float32)
    assert family.cache_kind.init_cache(cfg, **geometry).moe_counts.shape \
        == (2,)

    class Dense:
        num_layers = 1

        def serving_family(self):
            return dataclasses.replace(family, moe_counts=False)

    assert family.cache_kind.init_cache(Dense(), **geometry).moe_counts \
        is None
    assert [leaf.leaf for leaf in
            ServingEngine(cfg, params, fc.engine_config())._device_counts] \
        == ["moe_counts"]
