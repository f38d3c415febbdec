"""MiniCPM-SALA (block-sparse layers beside lightning layers) through the
model, the paged forward, the Pallas kernel and ``ServingEngine``, against
the benchmark's plain reference ``benchmarks/reference/minicpm_sala_f32.py``.

Tiny widths: hidden 64, 4 query heads of 16 over 2 K/V heads (sparse
layers) or 4 (lightning layers), six layers in runs of 1, 2, 2, 1, pool
blocks of 16; the selection scaled down (kernel 4, stride 2, blocks of 8,
top 6 with 1 first block and a window of 16, dense below 64), so that a
sequence of a hundred positions crosses the dense threshold and selects.
The weights are seeded with norm multipliers of order one.
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
from neuronx_distributed_tpu.ops import lightning_attention as la
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.ops import sparse_attention as sp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from runners import serve  # noqa: E402
from walk_checks import (check_score_walk, check_sparse_walk,  # noqa: E402
                         scored_pairs)

BS = 16
SPARSE = dict(kernel=4, stride=2, block=8, topk=6, init_blocks=1, window=16,
              dense_len=64)
SPEC = sp.SparseSpec(**SPARSE)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
          "minicpm4", "lightning-attn"]
PUBLISHED = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, lightning_head_dim=16, lightning_nh=4, lightning_nkv=4,
    lightning_use_rope=True, attn_use_rope=False, qk_norm=True,
    attn_use_output_gate=True, rope_theta=1e4, rms_norm_eps=1e-6,
    max_position_embeddings=4096, scale_emb=12, scale_depth=1.4,
    dim_model_base=256, mixer_types=MIXERS, sparse=SPARSE,
    reduced={"num_hidden_layers": {"from": 32, "to": 6}},
    family="minicpm_sala", reference="minicpm_sala_f32")


@fc.once_a_module
def _model(**kw):
    family = harness.load_plugin("families", "minicpm_sala")
    cfg, model, forward = family.build(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, forward, fc.seeded_weights(shapes)


def _reference(params):
    return (harness.load_plugin("reference", "minicpm_sala_f32"),
            harness.load_plugin("families", "minicpm_sala").published(
                params, PUBLISHED))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


# -- (a) the module's full forward ------------------------------------------

def test_the_layer_pattern_is_one_stack_a_kind_and_a_scan_a_run():
    cfg, _, _, params = _model()
    assert cfg.runs() == (("sparse", 0, 1), ("lightning", 0, 2),
                          ("sparse", 1, 2), ("lightning", 2, 1))
    layers = params["params"]["model"]
    assert layers["layers_sparse"]["layer"]["attn"]["qkv"][
        "k_kernel"].shape == (3, 64, 32)
    assert layers["layers_lightning"]["layer"]["attn"]["qkv"][
        "k_kernel"].shape == (3, 64, 64)
    assert "o_norm" in layers["layers_lightning"]["layer"]["attn"]
    assert "o_norm" not in layers["layers_sparse"]["layer"]["attn"]


def test_full_forward_matches_the_reference_across_the_dense_threshold():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(1).randint(0, 256, (2, 150))
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(tokens))
    ref, weights = _reference(params)
    want, _ = ref.forward(weights, tokens, PUBLISHED)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(np.std(want)))
    # at chosen positions the reference cuts its head, not its layers
    at = np.array([0, 70, 149])
    np.testing.assert_allclose(
        ref.forward(weights, tokens, PUBLISHED, positions=at)[0],
        np.asarray(want)[:, at], atol=1e-5)


# -- (b) the paged forward, XLA path and Pallas kernel ------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_reference_across_the_threshold(impl):
    """The harness's own probe: prefill in 16-row and then unaligned 15-row
    chunks beside a decode row and pad rows, then decode; 110 positions
    cross the dense threshold (64) in prefill and every decode row
    selects."""
    cfg, _, forward, params = _model(
        attn_force_pallas=impl == "pallas-interpret")
    assert pa.paged_attention_impl(cfg.head_dim_, BS,
                                   cfg.attn_force_pallas) == impl
    chk = dict(prompt_tokens=90, decode_steps=20)
    schedule = serve.probe_schedule(90, 20, 16)
    assert any(len(rows) < 16 for rows in schedule)          # pad rows
    assert any({s for s, _ in rows} == {0, 1} for rows in schedule)
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params,
                                       fc.engine_config(), chk)
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert got.shape == want.shape == (2, 110, 256)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(np.std(want)))


# -- (c) the selection ---------------------------------------------------------

def test_selected_blocks_equal_the_reference_for_every_row_and_group():
    rng = np.random.RandomState(3)
    s, g, r, d = 200, 2, 2, 16
    q = jnp.asarray(rng.randn(s, g, r, d), jnp.float32)
    k = jnp.asarray(rng.randn(s, g, d), jnp.float32)
    ref = harness.load_plugin("reference", "minicpm_sala_f32")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.selected_blocks(q, k, SPARSE))
        ck = sp.compress_keys(k, SPEC)
        got, forced = sp.select_blocks(q, ck, jnp.arange(s), SPEC, d ** -0.5)
    got, forced = np.asarray(got), np.asarray(forced)
    np.testing.assert_array_equal(got, want)
    t = np.arange(s)
    # dense below the threshold, exactly topk blocks beyond it, the first
    # block and the window among them, and never a block ahead of the row
    assert (got.sum(-1)[t < 64] == (t[t < 64] // 8 + 1)[:, None]).all()
    assert (got.sum(-1)[t >= 64] == 6).all()
    assert got[t >= 64, :, 0].all() and not forced[t < 64].any()
    for row in (64, 131, 199):
        assert got[row, :, (row - 15) // 8:row // 8 + 1].all()
        assert not got[row, :, row // 8 + 1:].any()
        assert forced[row].sum(-1).tolist() == [2 + row // 8
                                                - (row - 15) // 8] * 2
    # the free picks differ between groups and rows: it is a selection
    assert (got[64:, 0] != got[64:, 1]).any()


# -- (d) the packed step's state ----------------------------------------------

def test_a_packed_step_of_three_slots_equals_each_sequences_recurrence():
    rng = np.random.RandomState(4)
    h, d, slots = 4, 16, 4
    lens = {0: 23, 2: 9, 3: 40}
    seqs = {s: [jnp.asarray(rng.randn(n, h, d), jnp.float32)
                for _ in range(3)] for s, n in lens.items()}
    ref = harness.load_plugin("reference", "minicpm_sala_f32")
    want = {s: np.asarray(ref.lightning_attention(*qkv))
            for s, qkv in seqs.items()}
    state = jnp.asarray(rng.randn(2, h, slots, d, d), jnp.float32)  # stale
    got = {s: [] for s in lens}
    done = {s: 0 for s in lens}
    step = 0
    while any(done[s] < lens[s] for s in lens):
        rows = []                      # (slot, position): ragged chunks
        for s, take in ((3, 5), (0, 7 if step else 1), (2, 3)):
            rows += [(s, p) for p in range(done[s],
                                           min(done[s] + take, lens[s]))]
        rows = rows[:14] + [(slots, PAD_POSITION)] * (16 - len(rows[:14]))
        pick = lambda i: jnp.stack([  # noqa: E731
            seqs[s][i][p] if s < slots else jnp.zeros((h, d))
            for s, p in rows])
        out, state = la.lightning_attention_packed(
            pick(0), pick(1), pick(2), state, 1,
            jnp.asarray([s for s, _ in rows]),
            jnp.asarray([p for _, p in rows]), d ** -0.5)
        for i, (s, p) in enumerate(rows):
            if s < slots:
                got[s].append(np.asarray(out[i]))
                done[s] = p + 1
        step += 1
    for s in lens:
        np.testing.assert_allclose(np.stack(got[s]), want[s], atol=2e-5)
    # a slot with no rows keeps its state, the other layer is untouched
    assert (np.asarray(state[1, :, 1]) != 0).all()
    full = la.lightning_attention_full(*(x[None] for x in seqs[3]),
                                       d ** -0.5, chunk=16)
    np.testing.assert_allclose(full[0], want[3], atol=2e-5)


# -- (e) the kernel and its walk ----------------------------------------------

PAD = (0, PAD_POSITION)
#: packed rows as (slot, position): the engine's order, decode rows first
#: and then a prefill chunk's run; the tile's height where not one tile
SCENES = {
    "chunk_crosses_dense_len_inside_one_tile": (
        [(0, p) for p in range(58, 70)], None),
    "chunk_over_two_tiles": ([(0, p) for p in range(90, 106)], 8),
    "decode_rows_of_three_slots_beside_a_chunk": (
        [(1, 150), (2, 100), (3, 30)] + [(0, p) for p in range(70, 83)],
        None),
    "decode_rows_and_a_chunk_over_three_tiles": (
        [(1, 150), (2, 100), (3, 30)] + [(0, p) for p in range(120, 139)],
        8),
    "pad_rows_in_the_middle_and_at_the_end": (
        [(1, 150), PAD] + [(0, p) for p in range(70, 76)] + [PAD, PAD]
        + [(0, p) for p in range(76, 80)] + [PAD, PAD], None),
    "scattered_slots_and_an_unmapped_table": (
        [(0, 100), (1, 150), (0, 101), (2, 3), (1, 151), (3, 120), (0, 102),
         PAD, (2, 63)], None),
}


def _pools(seed, nb=64, layers=2, kv=2, d=16):
    rng = np.random.RandomState(seed)
    k_pool = jnp.asarray(rng.randn(layers, nb, kv, BS, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(layers, nb, kv, BS, d), jnp.float32)
    ck = jnp.asarray(rng.randn(layers, nb * (BS // 2), kv * d), jnp.float32)
    return rng, k_pool, v_pool, ck


def _scene(name, seed=6, maxb=12, n=4, d=16):
    """The scene's rows over four slots' tables (distinct blocks of a pool
    of 64; slot 3's is unmapped in ``scattered_...``)."""
    rows, height = SCENES[name]
    rng, k_pool, v_pool, ck = _pools(seed, d=d)
    slot_tables = rng.permutation(64)[:4 * maxb].reshape(4, maxb)
    if name.startswith("scattered"):
        slot_tables[3] = -1
    tables = jnp.asarray([slot_tables[s] for s, _ in rows], jnp.int32)
    q_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    q = jnp.asarray(rng.randn(len(rows), n, d), jnp.float32)
    return q, k_pool, v_pool, ck, tables, q_pos, height


def _set_tile_height(monkeypatch, height, heads=2, d=16):
    if height is not None:
        monkeypatch.setattr(sp, "ACC_BYTES", height * heads * d * 4)
        assert sp.tile_height(100, heads, d, jnp.float32) == height


@pytest.mark.parametrize("scene", list(SCENES))
def test_pallas_kernel_in_interpret_mode_equals_the_xla_path(scene,
                                                             monkeypatch):
    """The selection of the rows' own queries, through the tile kernel and
    through the gather reference: the same rows attend the same
    positions. A pad row's output is zero, and so is a row's whose table
    is unmapped."""
    q, k_pool, v_pool, ck, tables, q_pos, height = _scene(scene)
    _set_tile_height(monkeypatch, height)
    outs, counts = {}, {}
    for force in (False, True):
        outs[force], counts[force] = sp.sparse_paged_attention(
            q, k_pool, v_pool, ck, 1, tables, q_pos, SPEC,
            force_pallas=force)
    live = np.asarray((q_pos < PAD_POSITION) & (tables[:, 0] >= 0))
    assert live.sum() >= 7
    np.testing.assert_allclose(np.asarray(outs[True])[live],
                               np.asarray(outs[False])[live], atol=2e-6)
    assert (np.asarray(outs[True])[~live] == 0).all()
    # both paths count the same, the tiles' fetches included
    np.testing.assert_array_equal(counts[True], counts[False])
    got = dict(zip(sp.COUNT_KINDS, np.asarray(counts[True]).tolist()))
    # the selection's counts, exactly, from the scene's positions: every
    # (row, group, column of the walk's width) is counted once; a row
    # below dense_len attends every causal column; every causal position
    # of a real row is attended or skipped
    kv, width = 2, SPEC.walk_width(BS, tables.shape[1])
    assert width == 6
    real = [p for _, p in SCENES[scene][0] if p < PAD_POSITION]
    assert (got["selected"] + got["forced"] + got["dense"]
            + got["skipped"]) == len(SCENES[scene][0]) * kv * width
    assert got["dense"] == kv * sum(p // BS + 1 for p in real
                                    if p < SPEC.dense_len)
    assert got["attended"] + got["skipped_positions"] == kv * sum(
        p + 1 for p in real)
    assert got["selected"] > 0 and got["forced"] > 0
    assert got["skipped_positions"] > 0
    # (the selection counts an unmapped table's columns too: no tile
    # fetches those)
    assert (got["fetched"] + got["shared"] == got["selected"]
            + got["forced"] + got["dense"]) == (
                not scene.startswith("scattered"))
    assert got["fetched"] > 0


@pytest.mark.parametrize("scene", list(SCENES))
def test_selection_counts_equal_a_brute_count(scene):
    """``selection_counts`` against loops over the selection's bits: the
    sources of ``nxd_sparse_columns_total`` and
    ``nxd_sparse_positions_total``."""
    q, _, _, ck, tables, q_pos, _ = _scene(scene)
    sel, forced = sp.select_blocks(
        q.reshape(q.shape[0], 2, 2, -1),
        sp.gather_compressed_keys(ck, 1, tables, SPEC, BS, 2), q_pos, SPEC,
        0.25)
    width = SPEC.walk_width(BS, tables.shape[1])
    got = dict(zip(sp.COUNT_KINDS, np.asarray(sp.selection_counts(
        sel, forced, q_pos, SPEC, BS, width)).tolist()))
    sel, forced, q_pos = (np.asarray(x) for x in (sel, forced, q_pos))
    per = BS // SPEC.block
    want = dict.fromkeys(sp.COUNT_KINDS[:6], 0)
    for t, p in enumerate(q_pos):
        for g in range(sel.shape[1]):
            live = 0
            for c in range(tables.shape[1]):
                blocks = slice(c * per, (c + 1) * per)
                if not sel[t, g, blocks].any():
                    continue
                live += 1
                kind = ("dense" if p < SPEC.dense_len else "forced"
                        if forced[t, g, blocks].any() else "selected")
                want[kind] += 1
            assert live <= width
            want["skipped"] += width - live
            mine = sum(1 for b in np.flatnonzero(sel[t, g])
                       for x in range(b * SPEC.block, (b + 1) * SPEC.block)
                       if x <= p)
            want["attended"] += mine
            want["skipped_positions"] += (p + 1 if p < PAD_POSITION
                                          else 0) - mine
    assert got == want


def _selection(scene):
    q, k_pool, v_pool, ck, tables, q_pos, height = _scene(scene)
    qg = q.reshape(q.shape[0], 2, 2, -1)
    sel, _ = sp.select_blocks(
        qg, sp.gather_compressed_keys(ck, 1, tables, SPEC, BS, 2), q_pos,
        SPEC, 0.25)
    return qg, k_pool, v_pool, tables, q_pos, sel, height


@pytest.mark.parametrize("scene", list(SCENES))
def test_every_live_column_is_served_by_one_pair_of_its_tile(scene):
    """The walk by brute count (``walk_checks.check_sparse_walk``), at the
    scene's tile height and at the others: a chunk's rows share their
    slot's blocks, decode rows' pairs are narrow where their neighbours
    in the part are other slots' rows."""
    _, _, _, tables, q_pos, sel, height = _selection(scene)
    per, width = BS // SPEC.block, SPEC.walk_width(BS, tables.shape[1])
    parts = sp.column_parts(sel, tables, per)
    live = int((np.asarray(parts) > 0).sum())
    fetched = {}
    for rows in sorted({height or 16, 8, 16, 24}):
        walk = sp.sparse_tile_walk(parts, tables, rows, 8, width, per)
        fetched[rows] = check_sparse_walk(walk, parts, tables, rows, 8,
                                          width, per)
        assert fetched[rows] == int(np.asarray(
            sp.first_namers(parts, tables, rows)).sum())
        assert 0 < fetched[rows] <= live
    # a taller tile fetches no more
    assert fetched[24] <= fetched[16] <= fetched[8]
    if scene.startswith("chunk"):
        assert fetched[8] < live            # the chunk's rows share


def test_real_rows_are_unchanged_by_pad_rows_beside_them():
    """The rows of ``pad_rows_...`` with and without the pad rows between
    and after them: the real rows' outputs are the same bits."""
    q, k_pool, v_pool, ck, tables, q_pos, _ = _scene(
        "pad_rows_in_the_middle_and_at_the_end")
    real = np.flatnonzero(np.asarray(q_pos) < PAD_POSITION)
    with_pads, _ = sp.sparse_paged_attention(
        q, k_pool, v_pool, ck, 1, tables, q_pos, SPEC, force_pallas=True)
    alone, _ = sp.sparse_paged_attention(
        q[real], k_pool, v_pool, ck, 1, tables[real], q_pos[real], SPEC,
        force_pallas=True)
    np.testing.assert_array_equal(np.asarray(with_pads)[real],
                                  np.asarray(alone))
    assert (np.delete(np.asarray(with_pads), real, 0) == 0).all()


def _attend_given(sel, q_pos, seed=9):
    """Rows of one slot under a selection written by hand: the kernel
    against the reference, where a (row, group) attends anything (the
    reference's softmax over nothing is uniform; the kernel's is zero)."""
    t = len(q_pos)
    rng, k_pool, v_pool, _ = _pools(seed)
    tables = jnp.broadcast_to(
        jnp.asarray(rng.permutation(64)[:12], jnp.int32), (t, 12))
    q = jnp.asarray(rng.randn(t, 2, 2, 16), jnp.float32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    sel = jnp.asarray(sel)
    want = sp._sparse_paged_xla(q, k_pool, v_pool, 0, tables, q_pos, sel,
                                SPEC, 0.25)
    parts = sp.column_parts(sel, tables, BS // SPEC.block)
    got, fetched = sp._sparse_paged_pallas(q, k_pool, v_pool, 0, tables,
                                           q_pos, parts, SPEC, 0.25,
                                           interpret=True)
    some = np.asarray(sel).any(-1)
    np.testing.assert_allclose(np.asarray(got)[some], np.asarray(want)[some],
                               atol=2e-6)
    assert (np.asarray(got)[~some] == 0).all()
    return np.asarray(parts), int(fetched)


def test_two_rows_that_select_different_halves_of_one_pool_block():
    """Pool block 3 holds selection blocks 6 and 7: one row attends the
    first, its neighbour the second, a third both, in one group; the
    other group the other way round. One fetch a group serves all three,
    each under its own mask."""
    sel = np.zeros((3, 2, 24), bool)
    sel[0, 0, 6] = sel[1, 0, 7] = sel[2, 0, 6] = sel[2, 0, 7] = True
    sel[0, 1, 7] = sel[1, 1, 6] = sel[2, 1, 6] = sel[2, 1, 7] = True
    sel[:, :, 0] = True
    parts, fetched = _attend_given(sel, [180, 181, 182])
    assert parts[:, 0, 3].tolist() == [1, 2, 3]
    assert parts[:, 1, 3].tolist() == [2, 1, 3]
    assert fetched == 4                 # columns 0 and 3, two groups


def test_a_row_whose_selection_is_disjoint_from_its_tiles():
    """Nine rows of one slot: eight attend blocks 0-5, the ninth blocks
    16-21 alone (and in the other group nothing at all: its output there
    is zero, as a row's that attends nothing is)."""
    sel = np.zeros((9, 2, 24), bool)
    sel[:8, :, :6] = True
    sel[8, 0, 16:22] = True
    parts, fetched = _attend_given(sel, list(range(176, 185)))
    assert fetched == 3 * 2 + 3
    assert not (parts[:8, 0] > 0)[:, 8:].any() and not parts[8, 0, :8].any()


# -- (e2) the selection's scores from the pool -------------------------------

def _step_scene(seed=12, maxb=20, nb=96):
    """A serving step of the cell's shape at tiny widths: three decode
    rows of other slots, one prefill chunk of 124 rows past ``dense_len``
    and a pad row (128 rows, one tile; 20 table columns: a whole unit of
    16 and a unit of 4)."""
    rows = ([(1, 200), (2, 90), (3, 310)]
            + [(0, p) for p in range(150, 274)] + [PAD])
    rng, _, _, ck = _pools(seed, nb=nb)
    slot_tables = rng.permutation(nb)[:4 * maxb].reshape(4, maxb)
    tables = jnp.asarray([slot_tables[s] for s, _ in rows], jnp.int32)
    q_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    q = jnp.asarray(rng.randn(len(rows), 4, 16), jnp.float32)
    return q, ck, tables, q_pos


def _scores_both_ways(q, ck, tables, q_pos, layer=1):
    """The scores by the kernel (interpret mode, the walk built as a step
    builds it) and by the gather, and the walk."""
    qg = q.reshape(q.shape[0], 2, 2, -1)
    walk = sp.score_walk(tables, q_pos, SPEC, BS, 2, qg.shape[-1], q.dtype,
                         force_pallas=True)
    got = sp._key_scores_pallas(qg, ck, layer, walk, BS // SPEC.stride,
                                tables.shape[1], 0.25, interpret=True)
    want = sp.key_scores(
        qg, sp.gather_compressed_keys(ck, layer, tables, SPEC, BS, 2), 0.25)
    return np.asarray(got), np.asarray(want), walk


def _read(tables, q_pos):
    """``[T, N]`` bool: the scores the selection reads: whole kernels of
    real rows at or past ``dense_len``."""
    n = tables.shape[1] * (BS // SPEC.stride)
    ends = np.arange(n) * SPEC.stride + SPEC.kernel - 1
    p = np.asarray(q_pos)
    return (ends[None] <= p[:, None]) & ((p >= SPEC.dense_len)
                                         & (p < PAD_POSITION))[:, None]


def _assert_same_selection(got, want, tables, q_pos):
    """(An unmapped table's row reads block 0's keys through the gather
    and nothing through the kernel: it attends nothing either way.)"""
    mapped = np.asarray(tables[:, 0] >= 0)
    read = (_read(tables, q_pos) & mapped[:, None])[:, None, None]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(np.where(read, got, 0),
                               np.where(read, want, 0), atol=2e-6)
    ours, theirs = (sp.blocks_of_scores(jnp.asarray(s), q_pos, SPEC)
                    for s in (got, want))
    for a, b in zip(ours, theirs):              # sel, forced: bit for bit
        np.testing.assert_array_equal(np.asarray(a)[mapped],
                                      np.asarray(b)[mapped])
    return np.asarray(ours[0])


@pytest.mark.parametrize("scene", list(SCENES) + ["a_serving_step"])
def test_score_kernel_in_interpret_mode_equals_the_gathered_scores(
        scene, monkeypatch):
    """``compressed_key_scores`` reads the compressed keys where they lie
    in the pool, by block id; ``select_blocks`` over
    ``gather_compressed_keys`` reads each row's whole table. Where the
    selection reads a score the two agree to a float32 sum's order, and
    the selections are the same bits."""
    if scene == "a_serving_step":
        q, ck, tables, q_pos = _step_scene()
        height = 128
    else:
        q, _, _, ck, tables, q_pos, height = _scene(scene)
        _set_tile_height(monkeypatch, height)
        height = height or sp.tile_height(len(q_pos), 2, 16, jnp.float32)
    got, want, walk = _scores_both_ways(q, ck, tables, q_pos)
    sel = _assert_same_selection(got, want, tables, q_pos)
    assert sel.any()
    ucols = min(16, tables.shape[1])
    fetched, shared = check_score_walk(walk, tables, q_pos, SPEC, BS, height,
                                       8, ucols)
    selecting = np.asarray(q_pos >= SPEC.dense_len) & np.asarray(
        q_pos < PAD_POSITION) & np.asarray(tables[:, 0] >= 0)
    assert (fetched > 0) == bool(selecting.any())
    if scene == "a_serving_step":
        # 124 rows of one slot: a column's keys are copied once for them
        assert walk.lanes.shape == (1, 128, 2 * 128) and got.shape[-1] == 160
        assert shared / (fetched + shared) > 0.9
        # the decode rows' layers run over their part alone, the chunk's
        # over the tile
        depth = np.asarray(walk.depth)
        narrow = np.asarray(walk.narrow).reshape(2, 128)
        assert depth.tolist() == [4, 2]
        assert narrow[0, :4].tolist() == [-1, 0, 0, 0]
        assert narrow[1, :2].tolist() == [-1, 0]


def test_a_step_below_dense_len_lists_no_pair_and_selects_every_causal_block():
    rows = [(1, 20), (2, 63)] + [(0, p) for p in range(30, 43)] + [PAD]
    rng, _, _, ck = _pools(13)
    slot_tables = rng.permutation(64)[:36].reshape(3, 12)
    tables = jnp.asarray([slot_tables[s] for s, _ in rows], jnp.int32)
    q_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    q = jnp.asarray(rng.randn(16, 4, 16), jnp.float32)
    got, want, walk = _scores_both_ways(q, ck, tables, q_pos)
    assert np.asarray(walk.depth).tolist() == [0]
    assert (np.asarray(walk.blocks) == -1).all()
    assert np.asarray(walk.visits).tolist() == [0, 0]
    assert (got == 0).all()
    sel = _assert_same_selection(got, want, tables, q_pos)
    p = np.asarray(q_pos)[:15]
    assert (sel[:15].sum(-1) == (p // SPEC.block + 1)[:, None]).all()
    assert not sel[15].any()


def test_unmapped_columns_and_columns_beyond_the_row_are_never_fetched():
    """A row at 100 of a table that maps columns 0-6 and 9 (a stale entry
    beyond the row) beside a row at 130 whose table lost column 3: the
    first fetches columns 0-6 (column 6 holds positions 96-111, its first
    kernel ends at 99), the second 0-2 and 4-7 (column 8's first kernel
    ends at 131)."""
    rng, _, _, ck = _pools(14)
    blocks = rng.permutation(64)[:24].reshape(2, 12)
    blocks[0, 7:9] = blocks[0, 10:] = -1
    blocks[1, 3] = blocks[1, 9:] = -1
    tables = jnp.asarray(blocks, jnp.int32)
    q_pos = jnp.asarray([100, 130], jnp.int32)
    q = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
    got, _, walk = _scores_both_ways(q, ck, tables, q_pos)
    pairs, = scored_pairs(tables, q_pos, SPEC, BS, 8)
    assert sorted(c for c, _ in pairs) == sorted(
        list(range(7)) + [0, 1, 2, 4, 5, 6, 7])
    listed = np.asarray(walk.blocks).reshape(8, 12)
    assert {(c, int(b)) for j in range(8) for c, b in enumerate(listed[j])
            if b >= 0} == set(pairs)
    check_score_walk(walk, tables, q_pos, SPEC, BS, 8, 8, 12)
    # what was not fetched reads 0, the rest is the keys' own
    assert (got[0, ..., 7 * 8:] == 0).all()
    assert (got[1, ..., 3 * 8:4 * 8] == 0).all()
    assert (got[1, ..., 4 * 8:8 * 8] != 0).all()
    assert (got[1, ..., 8 * 8:] == 0).all()


def test_two_slots_that_share_a_prefix_block_are_one_pair():
    """Two slots whose tables name the same pool blocks in columns 0 and
    1 (a shared prefix) and their own beyond: the shared columns' keys
    are copied once for both rows, and both rows read them."""
    rng, _, _, ck = _pools(15)
    blocks = rng.permutation(64)[:24].reshape(2, 12)
    blocks[1, :2] = blocks[0, :2]
    tables = jnp.asarray(blocks, jnp.int32)
    q_pos = jnp.asarray([150, 170], jnp.int32)
    q = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
    got, want, walk = _scores_both_ways(q, ck, tables, q_pos)
    _assert_same_selection(got, want, tables, q_pos)
    fetched, shared = check_score_walk(walk, tables, q_pos, SPEC, BS, 8, 8,
                                       12)
    # columns 0-9 of the first row, 0-10 of the second, two of them one
    assert (fetched, shared) == (10 + 11 - 2, 2)
    listed = np.asarray(walk.blocks).reshape(8, 12)
    assert (listed[1, :2] == -1).all() and (listed[1, 2:10] >= 0).all()
    assert (listed[0, :2] == blocks[0, :2]).all()
    np.testing.assert_array_equal(got[0, ..., :16], want[0, ..., :16])


def test_compressed_keys_land_when_their_kernel_is_whole():
    """Rows written a chunk at a time: entry ``i`` of a sequence's
    compressed keys is the mean of its keys ``2i .. 2i + 3``, a kernel
    that straddles two pool blocks included, and nothing else is written."""
    rng = np.random.RandomState(8)
    nb, kv, d, maxb = 6, 2, 16, 4
    keys = jnp.asarray(rng.randn(40, kv, d), jnp.float32)
    table = jnp.asarray([[4, 1, 3, -1]], jnp.int32)
    k_pool = jnp.zeros((1, nb, kv, BS, d), jnp.float32)
    ck = jnp.full((1, nb * 8, kv * d), 7.0, jnp.float32)
    for lo in range(0, 40, 7):
        pos = jnp.arange(lo, min(lo + 7, 40))
        tbl = jnp.broadcast_to(table, (len(pos), maxb))
        idx = paging.flat_write_indices(tbl, pos, BS, nb * BS,
                                        paging.FULL_CACHE)
        k_pool = sp.write_sparse_rows(k_pool, keys[pos], idx, 0)
        ck = sp.write_compressed_keys(ck, k_pool, 0, tbl, pos, SPEC)
    got = sp.gather_compressed_keys(ck, 0, table, SPEC, BS, kv)[0]
    want = np.asarray(sp.compress_keys(keys, SPEC))
    np.testing.assert_allclose(got[:19], want[:19], atol=1e-6)
    assert (np.asarray(got[19:24]) == 7.0).all()     # 19: not whole yet
    assert int(np.asarray(ck != 7.0).all(-1).sum()) == 19
    np.testing.assert_allclose(want[7], np.asarray(keys[14:18]).mean(0),
                               atol=1e-6)            # positions 14..17


# -- through ServingEngine ------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests that cross the dense threshold, one of them preempted
    on the way, through one engine."""
    cfg, _, _, params = _model()
    steps = []

    def record(eng):
        dispatch = eng._dispatch

        def recording(fn, rows, width, *rest):
            # every step's rows as the device sees them: table, position
            steps.append(([eng._tables[r[0].slot].copy() for r in rows]
                          + [np.full(12, -1)] * (width - len(rows)),
                          [r[2] for r in rows]
                          + [PAD_POSITION] * (width - len(rows))))
            return dispatch(fn, rows, width, *rest)

        eng._dispatch = recording

    served = fc.serve_three(cfg, params, (
        "nxd_sparse_columns_total", "nxd_sparse_positions_total",
        "nxd_sparse_block_visits_total", "nxd_sparse_key_visits_total",
        "nxd_state_resets_total", "nxd_engine_rows_total"),
        lengths=[100, 70, 5], new=[40, 12, 4], before=record,
        num_blocks=13, max_slots=2)
    return served._replace(seen=steps)


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_preempted_request_decodes_as_a_fresh_one(served):
    """13 blocks do not hold a and b: b is preempted, re-admitted into a
    slot whose lightning states another request left, and still decodes
    what the reference does (above); the pool is whole at the end."""
    fc.check_preempted_and_whole(served.eng)
    assert float(jnp.abs(served.eng.cache.state).max()) > 0  # never cleared


def test_sparse_and_state_counters(served):
    counters = served.counters
    cols = counters["nxd_sparse_columns_total"]
    assert set(cols) == {"selected", "forced", "dense", "skipped"}
    assert all(v > 0 for v in cols.values())
    rows = counters["nxd_engine_rows_total"]
    width = SPEC.walk_width(BS, 12)
    # rows x groups x walk width x sparse layers, every step
    assert sum(cols.values()) == sum(rows.values()) * 2 * width * 3
    pos = counters["nxd_sparse_positions_total"]
    assert pos["attended"] > 0 and pos["skipped"] > 0
    # every live (row, group, column) is fetched for or shares a fetch;
    # a prefill chunk's rows share their slot's blocks
    visits = counters["nxd_sparse_block_visits_total"]
    assert set(visits) == {"fetched", "shared"}
    assert visits["fetched"] + visits["shared"] == sum(
        cols[k] for k in ("selected", "forced", "dense"))
    assert visits["shared"] > visits["fetched"] > 0
    # the score kernel's copies, by a brute count over every step's
    # tables and positions: one tile of 16 rows, three sparse layers
    keys = counters["nxd_sparse_key_visits_total"]
    want = [0, 0]
    for tables, positions in served.seen:
        for pairs in scored_pairs(np.stack(tables), positions, SPEC, BS, 16):
            want[0] += 3 * len(pairs)
            want[1] += 3 * (sum(map(len, pairs.values())) - len(pairs))
    assert [keys["fetched"], keys["shared"]] == want
    assert keys["shared"] > 0 and keys["fetched"] > 0
    # three admissions and b's second
    assert counters["nxd_state_resets_total"][""] >= 4


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES)
def test_refused_features_raise_by_name(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw})


def test_session_export_is_refused_and_the_cache_is_the_kinds():
    cfg, _, _, params = _model()
    cache = fc.check_session_export_is_refused(
        cfg, params, paging.SparseStatePagedCache,
        paging.SparseStateCache).cache
    assert cache.k.shape == (3, 40, 2, BS, 16) == cache.v.shape
    assert cache.ck.shape == (3, 40 * 8, 2 * 16)
    assert cache.state.shape == (3, 4, 3, 16, 16)
    assert cache.state.dtype == jnp.float32
    assert cache.capacity == 40 * BS and cache.max_slots == 3
    with pytest.raises(ValueError, match="whole selection blocks"):
        ServingEngine(cfg, params,
                      fc.engine_config(block_size=4, token_budget=4))


# -- (f) the constructor and the two old kinds --------------------------------

@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("family", ["llama", "evabyte"])
def test_the_constructor_returns_the_old_kinds_pytrees_leaf_for_leaf(
        family, quantized):
    from neuronx_distributed_tpu.models import evabyte, llama

    cfg = (llama.tiny_config() if family == "llama"
           else evabyte.tiny_config())
    geometry = dict(num_blocks=12, block_size=8, table_rows=3,
                    max_blocks_per_seq=5)
    got = paging.init_serving_cache(cfg, dtype=jnp.float32,
                                    quantized=quantized, **geometry)
    args = (cfg.num_layers, 12, 8, cfg.num_kv_heads, cfg.head_dim_, 3, 5)
    want = (paging.init_quantized_paged_kv_cache(*args) if quantized
            else paging.init_paged_kv_cache(*args, dtype=jnp.float32))
    assert type(got) is type(want)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_attention_kinds_are_checked_by_name():
    from neuronx_distributed_tpu.models import llama

    assert llama.ATTENTION_KINDS == ("full", "eva", "sparse", "lightning",
                                     "mla", "mamba2")
    with pytest.raises(ValueError, match="attention_kind"):
        llama.tiny_config(attention_kind="linear")
    with pytest.raises(ValueError, match="mixer_types"):
        _model(mixer_types=("minicpm4",))
