"""EvaByte (EVA chunked linearized attention) through the model, the paged
forward, the Pallas kernel and ``ServingEngine``, against the benchmark's
plain reference ``benchmarks/reference/evabyte_f32.py``.

Tiny widths: hidden 64, 4 heads of 16, window 32, chunk 4, blocks of 8, so
that a window is still 4 blocks and a window's summaries fill one block.
The weights are seeded with ``phi``, ``mu`` and the norms' offsets of order
one: at the chip's initialisation they are tiny and a wrong pooling would
not show.
"""

import functools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.engine import ServingEngine
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.ops import paged_attention as pa

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from runners import serve  # noqa: E402

sys.path.insert(0, HERE)
from walk_checks import check_paged_runs, check_tile_walk  # noqa: E402

W, C, BS = 32, 4, 8
PUBLISHED = dict(
    vocab_size=320, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    rope_theta=1e5, rms_norm_eps=1e-5, max_position_embeddings=1024,
    window_size=W, chunk_size=C, num_pred_heads=8, fp32_skip_add=True,
    family="evabyte", reference="evabyte_f32")
KIND = paging.WindowSummaryCache(W, C)
#: over ``family_checks.engine_config``: blocks and steps of 8 rows
ENGINE = dict(block_size=BS, max_blocks_per_seq=16, token_budget=BS)
_ecfg = functools.partial(fc.engine_config, **ENGINE)


@fc.once_a_module
def _model(**kw):
    family = harness.load_plugin("families", "evabyte")
    cfg, model, forward = family.build(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))

    def special(name, noise, x, key):
        if "eva_" in name:
            return 0.5 * noise                  # order head_dim ** -0.5

    return cfg, model, forward, fc.seeded_weights(shapes, special)


def _reference(params):
    return (harness.load_plugin("reference", "evabyte_f32"),
            harness.load_plugin("families", "evabyte").published(params,
                                                                 PUBLISHED))


def _reference_logits(params, tokens):
    ref, weights = _reference(params)
    return ref.forward(weights, np.asarray(tokens), PUBLISHED)[0]


# -- (a) the module's full forward ------------------------------------------

def test_full_forward_matches_the_reference_over_three_windows():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(1).randint(0, 320, (2, 3 * W + 11))
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(tokens))
    ref, weights = _reference(params)
    want = ref.forward_all_heads(weights, tokens, PUBLISHED)
    assert got.shape == want.shape == (2, 3 * W + 11, 8, 320)
    assert got.dtype == jnp.float32                          # fp32_logits
    np.testing.assert_allclose(got, want, atol=2e-5 * float(np.std(want)))
    # the next-byte head is what serving samples
    np.testing.assert_array_equal(
        np.asarray(ref.forward(weights, tokens, PUBLISHED)[0]),
        np.asarray(want[:, :, 0]))


def test_a_short_sequence_pads_to_chunks_not_to_a_window():
    cfg, model, _, params = _model()
    tokens = np.random.RandomState(2).randint(0, 320, (1, 7))
    ref, weights = _reference(params)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(tokens))
    want = ref.forward_all_heads(weights, tokens, PUBLISHED)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(np.std(want)))


# -- (b), (c) the paged forward, XLA path and Pallas kernel ------------------

@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_paged_forward_matches_the_reference_across_window_ends(impl):
    """The harness's own probe: the engine's own cache, and before a row
    runs the columns the cache kind names for its position
    (``columns_to_map``: the ring's column and, where the row completes a
    window, that window's summary column) mapped to fresh blocks in order,
    as ``_ensure_block`` maps them; prefill in 8-row and then unaligned
    7-row chunks beside a decode row and pad rows, then decode; 105
    positions cross three window ends, the second sequence's inside a
    chunk."""
    cfg, _, forward, params = _model(
        attn_force_pallas=impl == "pallas-interpret")
    assert pa.paged_attention_impl(cfg.head_dim_, BS,
                                   cfg.attn_force_pallas) == impl
    chk = dict(prompt_tokens=75, decode_steps=30)
    schedule = serve.probe_schedule(75, 30, 8)
    assert any(len(rows) < 8 for rows in schedule)           # pad rows
    assert any({s for s, _ in rows} == {0, 1} and len(rows) == 8
               and (rows[1][1] + 1) % W not in (0, 1)
               and any((p + 1) % W == 0 for _, p in rows[1:-1])
               for rows in schedule)            # a window ends inside a chunk
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params, _ecfg(), chk)
    ref, weights = _reference(params)
    want = np.asarray(ref.forward(weights, seqs, PUBLISHED)[0])
    assert got.shape == want.shape == (2, 105, 320)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(np.std(want)))


@pytest.mark.parametrize("run", [1, 4], ids=["pairs", "runs_of_4"])
@pytest.mark.parametrize("heads", [4, 64], ids=["one_tile", "two_tiles"])
def test_pallas_kernel_in_interpret_mode_equals_the_xla_path(monkeypatch,
                                                             heads, run):
    """The kernel a pair a turn (what a block of 32 heads takes on the
    chip) and with a narrow group's blocks in runs of 4, exact and
    summary columns side by side in one run. Both masks and the walk: a
    ring that has wrapped (stale rows of two windows ago in a live
    column), summaries of two earlier windows and a
    later window's summary column that must be skipped, unmapped columns,
    a pad row. With 64 query heads over the 4 K/V heads a tile is 8 rows
    (``two_tiles``): the chunk of rows behind the first six then crosses
    a window's end inside the second tile, whose rows share the ring's
    blocks but not the summaries."""
    rng = np.random.RandomState(3)
    nb, kv, d, maxb = 24, 4, 16, 10
    k_pool = jnp.asarray(rng.randn(nb, BS, kv, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, BS, kv, d), jnp.float32)
    length = 2 * W + 13                     # window 2, 13 positions into it
    pos = np.full((nb, BS), PAD_POSITION, np.int32)
    table = np.full((maxb,), -1, np.int32)
    table[:KIND.ring] = [3, 7, 1, 12, 9]
    for p in range(length):                 # later positions overwrite
        pos[table[KIND.column_of(p, BS)], p % BS] = p
    table[KIND.ring:KIND.ring + 3] = [15, 4, 20]    # windows 0, 1 and "2"
    q_pos = np.array([length - 1, length - 3, W + 5, 2 * W + 5, 2 * W,
                      PAD_POSITION] + list(range(2 * W - 4, 2 * W + 5)),
                     np.int32)
    t = len(q_pos)
    # row 0 is another slot's: the same rows in blocks of its own, which
    # no other row of its tile names
    twin = dict(zip([3, 7, 1, 12, 9, 15, 4, 20], [0, 2, 5, 6, 8, 10, 11, 13]))
    old, new = (np.array(x) for x in zip(*twin.items()))
    k_pool, v_pool = k_pool.at[new].set(k_pool[old]), v_pool.at[new].set(
        v_pool[old])
    pos[new] = pos[old]
    tables = np.tile(table, (t, 1))
    tables[0] = [twin.get(b, -1) for b in table]
    args = (jnp.asarray(rng.randn(t, heads, d), jnp.float32), k_pool[None],
            v_pool[None], jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(q_pos), 0)
    kinds = KIND.column_kinds(tables, np.arange(maxb), q_pos[:, None], BS)
    assert kinds[0].tolist() == [0, 0, 0, 1, 1, 2, 2, 0, 0, 0]
    assert kinds[2].tolist() == [0, 0, 0, 0, 1, 2, 0, 0, 0, 0]
    assert kinds[3].tolist() == [0, 0, 0, 1, 0, 2, 2, 0, 0, 0]
    assert not kinds[5].any()               # a pad row walks nothing
    monkeypatch.setattr(pa, "run_blocks", lambda *_: run)
    want = pa.paged_attention(*args, force_pallas=False, scale=0.25,
                              window=(W, KIND.ring))
    got = pa.paged_attention(*args, force_pallas=True, scale=0.25,
                             window=(W, KIND.ring))
    real = q_pos != PAD_POSITION
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(got)[~real].any()
    # the walk: every (row, column) of either kind by one pair of its tile
    n_rep = heads // kv
    rows = pa.tile_rows(n_rep, t)
    assert -(-t // rows) == (1 if heads == 4 else 2)
    walk = jax.tree_util.tree_map(np.asarray, pa.tile_walk(
        args[4], args[5], BS, nb, n_rep, (W, KIND.ring)))
    check_tile_walk(walk, kinds > 0, tables, rows, n_rep)
    # one slot's rows: a tile fetches a column once, however many attend
    # it; row 0's blocks are its own
    shared = kinds.copy()
    shared[0] = 0
    assert walk.count.tolist() == [
        int((shared[i:i + rows] > 0).any(0).sum()) + (i == 0) * 4
        for i in range(0, t, rows)]
    if run > 1:
        # row 0's two exact and two summary columns are one unit
        fetches = check_paged_runs(tables, q_pos, kinds > 0, BS, nb, n_rep,
                                   run, window=(W, KIND.ring))
        assert fetches[0] == 4


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_window_summaries_land_in_one_layer_and_the_sentinel_in_none(layer):
    """``write_window_summaries`` on the stacks: a slot whose window
    completes writes block ``(layer, dst)`` with what the pooling of
    ``(layer, src)`` gives and changes no other layer; ``dst ==
    num_blocks`` (every other slot) changes no byte of any layer."""
    from neuronx_distributed_tpu.ops import eva_attention as eva

    rng = np.random.RandomState(4)
    layers, nb, kv, d = 3, 6, 2, 16
    k, v = (jnp.asarray(rng.randn(layers, nb, BS, kv, d), jnp.float32)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.randn(kv, d), jnp.float32) for _ in range(2))
    src = jnp.asarray([[4, 1, 3, 0], [0, 0, 0, 0]], jnp.int32)
    write = jax.jit(lambda l, done, dst: eva.write_window_summaries(
        k, v, l, (done, src, dst), phi, mu, C, 0.25))
    new_k, new_v = write(jnp.int32(layer), True,
                         jnp.asarray([5, nb], jnp.int32))
    for new, pool, want in zip(
            (new_k, new_v), (k, v), eva.chunk_summaries(
                k[layer, src[0]].reshape(-1, kv, d),
                v[layer, src[0]].reshape(-1, kv, d), phi, mu, C, 0.25)):
        # the pooling fuses otherwise under jit: close, not bit for bit
        np.testing.assert_allclose(new[layer, 5], want, atol=1e-6)
        expect = np.asarray(pool).copy()
        expect[layer, 5] = new[layer, 5]
        np.testing.assert_array_equal(np.asarray(new), expect)
    for done in (True, False):
        dropped = write(jnp.int32(layer), done, jnp.full((2,), nb, jnp.int32))
        np.testing.assert_array_equal(np.asarray(dropped[0]), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(dropped[1]), np.asarray(v))


# -- (d) through ServingEngine -----------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Three requests that cross windows, one of them preempted on the
    way, through one engine; what each step held."""
    cfg, _, _, params = _model()

    def held(eng):
        return [(r.uid, r.n_cached, len(eng._slot_blocks[r.slot]))
                for r in eng._slots if r is not None]

    return fc.serve_three(cfg, params, (
        "nxd_eva_columns_total", "nxd_eva_windows_total",
        "nxd_paged_block_visits_total"),
        lengths=[70, 37, 5], new=[30, 12, 4], vocab=320, watch=held,
        **dict(ENGINE, num_blocks=11, max_slots=2))


def test_engine_greedy_tokens_equal_the_reference(served):
    fc.check_engine_greedy_tokens_equal_the_reference(served,
                                                      _reference_logits)


def test_a_sequence_holds_the_blocks_its_cache_kind_says(served):
    seen = 0
    for step in served.seen:
        for uid, n, blocks in step:
            assert blocks == KIND.blocks_for(n, BS), (uid, n)
            seen = max(seen, n)
    assert seen >= 3 * W                    # "a" rolled the ring three times
    assert KIND.blocks_for(100, BS) == 5 + 3 < -(-100 // BS)


def test_a_preempted_request_restarts_and_the_pool_is_whole(served):
    fc.check_preempted_and_whole(served.eng)  # 11 blocks: not a and b
    assert served.eng.pool_free_blocks() == 11


def test_roll_span_and_eva_counters(served):
    counters = served.counters
    assert "engine/roll" in served.spans
    cols = counters["nxd_eva_columns_total"]
    assert set(cols) == {"exact", "summary", "skipped"}
    assert cols["exact"] > 0 and cols["summary"] > 0 and cols["skipped"] > 0
    # every window end a packed row held: a's two in its prompt and one
    # while decoding, b's one, and those run again after the preemption
    assert counters["nxd_eva_windows_total"][""] >= 4
    # every exact or summary column a row attends is served by one fetch
    # of its tile; a chunk's rows share theirs
    visits = counters["nxd_paged_block_visits_total"]
    assert sum(visits.values()) == cols["exact"] + cols["summary"]
    assert visits["shared"] > visits["fetched"] > 0


def test_sixteen_windows_hold_the_ring_and_sixteen_summary_blocks():
    cfg, _, _, params = _model()
    eng = ServingEngine(cfg, params, _ecfg(
        num_blocks=24, max_slots=1, max_blocks_per_seq=22))
    n = 16 * W
    # from the cache kind and max_position_embeddings, not 22 * 8
    assert eng.max_model_len() == min(cfg.max_seq_len,
                                      (22 - KIND.ring + 1) * W - 1) == 575
    assert eng.fits(n, 1) and not eng.fits(575, 1)
    uid = eng.submit(np.random.RandomState(5).randint(0, 320, (n,)).tolist(),
                     2)
    while eng.has_work():
        if eng._slots[0] is not None:
            peak = len(eng._slot_blocks[0])
        eng.step()
    assert peak == KIND.ring + 16 == KIND.blocks_for(n, BS)
    assert peak < -(-n // BS)
    assert eng.results[uid].status == "completed"


@pytest.mark.parametrize("feature,kw", fc.REFUSED_FEATURES)
def test_refused_features_raise_by_name(feature, kw):
    cfg, _, _, params = _model()
    fc.check_refused_features(cfg, params, {feature: kw}, **ENGINE)


def test_session_export_and_wide_steps_are_refused_by_name():
    cfg, _, _, params = _model()
    fc.check_session_export_is_refused(
        cfg, params, paging.PagedKVCache, paging.WindowSummaryCache,
        **ENGINE)
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(cfg, params, _ecfg(token_budget=16))
    with pytest.raises(ValueError, match="block_size"):
        ServingEngine(cfg, params, _ecfg(block_size=4, token_budget=4))


def test_cache_kinds_answer_for_their_layouts():
    full = paging.FULL_CACHE
    assert full.columns_to_map(37, 8) == (4,)
    assert full.blocks_for(37, 8) == 5 and full.max_positions(6, 8) == 48
    assert KIND.ring == W // BS + 1 == 5
    assert KIND.columns_to_map(30, BS) == (3,)
    assert KIND.columns_to_map(31, BS) == (3, 5)    # ends window 0
    assert KIND.columns_to_map(32, BS) == (4,)      # the spare column
    assert KIND.columns_to_map(40, BS) == (0,)      # the ring wraps
    assert KIND.columns_to_map(63, BS) == (2, 6)
    assert [KIND.blocks_for(n, BS) for n in (1, 8, 9, 31, 32, 40, 41, 64)
            ] == [1, 1, 2, 4, 5, 6, 6, 7]
    assert KIND.max_positions(4, BS) == 31 and KIND.max_positions(6, BS) == 63
    big = paging.WindowSummaryCache(2048, 16).geometry(128, 128)
    assert big.ring == 17 and big.blocks_for(32768, 128) == 17 + 16
    assert big.blocks_for(8576, 128) == 21


# -- (e) llama and Mixtral through the changed engine ------------------------

#: what ``engine_parity.py`` records of both families, once for the cases
_RECORDED = {}


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_llama_and_mixtral_serve_as_the_parent_did(family, tmp_path):
    with open(os.path.join(HERE, "fixtures", "engine_parity_pr28.json")) as f:
        want = json.load(f)[family]
    if not _RECORDED:
        # as the fixture was written: the script in an interpreter of its
        # own, at the CPU compiler's default effort. The logits are held to
        # the bit, and ``conftest.py``'s optimisation level 0 rounds a
        # product's sum in another order (1.4e-6 apart).
        out, env = str(tmp_path / "recorded.json"), dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(filter(None, [
            os.path.dirname(HERE), env.get("PYTHONPATH")]))
        env["XLA_FLAGS"] = re.sub(r"--xla_backend_optimization_level=\d+",
                                  "", env.get("XLA_FLAGS", ""))
        subprocess.run([sys.executable,
                        os.path.join(HERE, "engine_parity.py"), out],
                       check=True, env=env)
        with open(out) as f:
            _RECORDED.update(json.load(f))
    got = _RECORDED[family]
    assert got["max_model_len"] == want["max_model_len"]
    assert got["allocated"] == want["allocated"]
    assert got["tables"] == want["tables"]
    assert got["tokens"] == want["tokens"]
    np.testing.assert_array_equal(np.asarray(got["logits"], np.float32),
                                  np.asarray(want["logits"], np.float32))
