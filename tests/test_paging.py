"""Paged KV cache tests: block allocator, pool write/gather plumbing,
paged attention (XLA reference + Pallas interpret) and full-model
paged-vs-contiguous decode parity (fp32 bit-exact, int8 within tolerance).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.inference import kv_cache as kvc
from neuronx_distributed_tpu.inference.kv_cache import (PAD_POSITION,
                                                        quantize_kv)
from neuronx_distributed_tpu.inference.paging import (
    BLOCK_COUNTERS, BlockAllocator, CacheExhaustedError, flat_write_indices,
    init_paged_kv_cache, init_quantized_paged_kv_cache, step_counter,
    write_pool_rows)
from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                  tiny_config)
from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                    tiny_moe_config)
from neuronx_distributed_tpu.ops import paged_attention as pa
from neuronx_distributed_tpu.ops.paged_attention import (column_live,
                                                          paged_attention,
                                                          pair_kinds,
                                                          tile_pairs,
                                                          tile_rows,
                                                          tile_walk)
from neuronx_distributed_tpu.parallel import mesh as ps
from counter_checks import declared_anywhere
from walk_checks import (check_paged_runs, check_tile_walk, group_start,
                         narrow_group)


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(4)
    first = a.alloc(2)
    assert len(first) == 2 and a.num_free == 2 and a.num_allocated == 2
    rest = a.alloc(2)
    assert sorted(first + rest) == [0, 1, 2, 3]
    a.free(first)
    assert a.num_free == 2
    again = a.alloc(2)
    assert sorted(again) == sorted(first)  # freed blocks come back


def test_allocator_oom_allocates_nothing():
    a = BlockAllocator(3)
    a.alloc(2)
    with pytest.raises(CacheExhaustedError):
        a.alloc(2)
    # the failed alloc must not leak partial allocations
    assert a.num_free == 1
    assert len(a.alloc(1)) == 1


def test_allocator_double_free_rejected():
    a = BlockAllocator(2)
    blks = a.alloc(1)
    a.free(blks)
    with pytest.raises(ValueError):
        a.free(blks)


def test_allocator_reset():
    a = BlockAllocator(4)
    a.alloc(3)
    a.reset()
    assert a.num_free == 4 and a.num_allocated == 0
    assert len(a.alloc(4)) == 4


# ---------------------------------------------------------------------------
# pool write plumbing
# ---------------------------------------------------------------------------

def test_flat_write_indices_routes_pads_out_of_range():
    bs, maxb, nb = 4, 3, 8
    tables = jnp.asarray([[2, 5, -1]] * 3, jnp.int32)
    positions = jnp.asarray([1, 6, PAD_POSITION], jnp.int32)
    idx = flat_write_indices(tables, positions, bs, nb * bs)
    # pos 1 -> block 2 offset 1; pos 6 -> block 5 offset 2
    assert idx.tolist()[:2] == [2 * bs + 1, 5 * bs + 2]
    assert idx.tolist()[2] == nb * bs  # pad routed past the pool


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_write_pool_rows_writes_one_layer_and_drops_the_sentinel(layer):
    """The stack is addressed by (layer, flat index): a kept row changes
    its layer only, and the drop sentinel (``flat_idx == capacity``, one
    layer's row count) changes no byte of any layer — as one run of
    ``L * capacity`` rows it would be the next layer's first row."""
    layers, nb, bs = 3, 2, 2
    capacity = nb * bs
    pool = jnp.arange(layers * capacity * 3, dtype=jnp.float32).reshape(
        layers, nb, bs, 3)
    rows = -jnp.ones((2, 3), jnp.float32)
    write = jax.jit(write_pool_rows)         # the layer traced, as in a scan
    out = np.asarray(write(pool, rows, jnp.asarray([1, capacity], jnp.int32),
                           jnp.int32(layer)))
    want = np.asarray(pool).copy()
    want[layer, 0, 1] = -1
    np.testing.assert_array_equal(out, want)
    dropped = write(pool, rows, jnp.full((2,), capacity, jnp.int32),
                    jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(dropped), np.asarray(pool))


# ---------------------------------------------------------------------------
# paged attention op
# ---------------------------------------------------------------------------

# Three sequences in one pool laid out as the engine writes it: position
# ``p`` of a sequence sits in its table's column ``p // BS``, slot
# ``p % BS``; every other slot holds PAD_POSITION. "a" fills all four
# columns; "b" has 6 tokens and a third block mapped ahead of its chunks,
# still empty; "c" shares a's first two blocks (a forked prefix) and goes
# on in a block of its own; "hole" is b with its second column unmapped.
_BS, _MAXB, _NB = 4, 4, 12
_TABLES = {"a": [1, 2, 3, 4], "b": [5, 6, 7, -1], "c": [1, 2, 8, -1],
           "hole": [5, -1, 7, -1], "unmapped": [-1] * _MAXB}
_FILLED = {1: 4, 2: 4, 3: 4, 4: 4, 5: 4, 6: 2, 8: 3}   # block -> tokens

#: case -> rows of (table, q_pos)
_RAGGED_CASES = {
    "block_first_position": [("a", 4), ("a", 8), ("b", 4), ("c", 8)],
    "block_last_position": [("a", 3), ("a", 7), ("b", 3), ("c", 7)],
    "one_live_column": [("a", 0), ("b", 2), ("c", 3)],
    "all_columns_live": [("a", 15), ("a", 12)],
    "shared_table": [("a", 9), ("a", 9), ("c", 9), ("a", 5), ("c", 5)],
    "pad_row": [("a", 6), ("unmapped", PAD_POSITION), ("b", 5)],
    "beyond_table": [("a", 16), ("a", 4 * _BS * _MAXB), ("b", 16)],
    "ragged": [("a", 13), ("b", 5), ("hole", 5), ("c", 10), ("b", 0),
               ("hole", 1), ("a", 7)],
}
_PAD = ("unmapped", PAD_POSITION)
#: the same, at 32 query heads over 2 K/V heads: tiles of 8 rows
#: (:func:`tile_rows`), so that a few rows are several tiles
_TILED_CASES = {
    "chunk_spans_two_tiles": [("a", p) for p in range(3, 15)],
    "decode_rows_of_different_slots": [
        ("a", 13), ("b", 5), ("c", 10), ("hole", 5), ("a", 7), ("b", 3),
        ("c", 9), ("hole", 1), ("c", 3)],
    "chunk_beside_decode_rows": [("b", 5), ("c", 10), ("hole", 4)] + [
        ("a", p) for p in range(6, 14)],
    "pad_rows_in_the_middle_and_at_the_end": [
        ("a", 6), _PAD, ("b", 5), _PAD, _PAD, ("a", 9), ("c", 10), _PAD,
        ("c", 8), ("a", 15), _PAD, _PAD],
    "shared_prefix_block_in_one_tile": [
        ("a", 5), ("c", 5), ("a", 9), ("c", 9), ("c", 10), ("a", 7)],
}


def _paged_case(rows, quantized=False, seed=1, heads=(4, 2)):
    rng = np.random.RandomState(seed)
    (N, KV), D = heads, 16
    q = jnp.asarray(rng.randn(len(rows), N, D).astype(np.float32))
    # the pools are stacks of one layer
    k = jnp.asarray(rng.randn(1, _NB, _BS, KV, D).astype(np.float32))
    v = jnp.asarray(rng.randn(1, _NB, _BS, KV, D).astype(np.float32))
    pool_pos = np.full((_NB, _BS), PAD_POSITION, np.int32)
    for table in _TABLES.values():
        for col, blk in enumerate(table):
            n = _FILLED.get(blk, 0)
            pool_pos[blk, :n] = col * _BS + np.arange(n)
    tables = jnp.asarray([_TABLES[name] for name, _ in rows], jnp.int32)
    q_pos = jnp.asarray([pos for _, pos in rows], jnp.int32)
    pool_pos = jnp.asarray(pool_pos)
    if not quantized:
        return q, k, v, pool_pos, tables, q_pos, None, None
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return q, kq, vq, pool_pos, tables, q_pos, ks, vs


@pytest.mark.parametrize("case", list(_RAGGED_CASES))
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_attention_pallas_interpret_matches_xla(quantized, case):
    rows = _RAGGED_CASES[case]
    q, k, v, pp, tb, qp, ks, vs = _paged_case(rows, quantized)
    ref = paged_attention(q, k, v, pp, tb, qp, 0, k_scale=ks, v_scale=vs,
                          force_pallas=False)
    ker = paged_attention(q, k, v, pp, tb, qp, 0, k_scale=ks, v_scale=vs,
                          force_pallas=True)
    # a row with no mapped column: the kernel writes zeros, the reference
    # a uniform average over whatever block 0 holds; the engine drops both
    real = np.asarray([name != "unmapped" for name, _ in rows])
    np.testing.assert_allclose(np.asarray(ker)[real], np.asarray(ref)[real],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(ker)[~real].any()


@pytest.mark.parametrize("case", list(_TILED_CASES))
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_kernel_tiles_match_xla(monkeypatch, quantized, case):
    """The kernel's unit is (a tile of rows, a pool block some row of it
    attends): the cases put a chunk across two tiles, a tile of rows that
    share nothing, a chunk beside decode rows, pad rows between real ones
    and two slots' shared prefix block in one tile. A pad row's output is
    zero, and takes nothing from and adds nothing to the real rows: with
    the pad rows left out the real rows' outputs are the same, to the
    bit where the kernel takes a pair a turn (a run's rounding follows
    the units its tile is cut into, and those follow the rows' places)."""
    rows = _TILED_CASES[case]
    assert tile_rows(16, len(rows)) == 8
    q, k, v, pp, tb, qp, ks, vs = _paged_case(rows, quantized, heads=(32, 2))
    kw = dict(k_scale=ks, v_scale=vs)
    ref = paged_attention(q, k, v, pp, tb, qp, 0, force_pallas=False, **kw)
    ker = np.asarray(paged_attention(q, k, v, pp, tb, qp, 0,
                                     force_pallas=True, **kw))
    real = np.asarray([r != _PAD for r in rows])
    np.testing.assert_allclose(ker[real], np.asarray(ref)[real], rtol=1e-5,
                               atol=1e-5)
    assert not ker[~real].any()
    if not real.all():
        def alone():
            return np.asarray(paged_attention(
                q[real], k, v, pp, tb[real], qp[real], 0, force_pallas=True,
                **kw))

        np.testing.assert_allclose(ker[real], alone(), rtol=1e-6, atol=1e-6)
        monkeypatch.setattr(pa, "run_blocks", lambda *_: 1)
        np.testing.assert_array_equal(
            np.asarray(paged_attention(q, k, v, pp, tb, qp, 0,
                                       force_pallas=True, **kw))[real],
            alone())


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_kernel_reads_heads_that_share_a_word(pool):
    """bf16 and int8 rows lie two and four to a 32-bit sublane of the
    block: with four K/V heads the kernel reads them as words by a strided
    load and takes them apart (``_head_rows``), widened exactly: what
    is left against the reference is the bf16 output's rounding."""
    rng = np.random.RandomState(0)
    T, N, KV, D, NB, BS = 10, 8, 4, 16, 6, 8
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for shape in
               ((T, N, D), (1, NB, BS, KV, D), (1, NB, BS, KV, D)))
    ks = vs = None
    if pool == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    pos = np.tile(np.arange(BS), (NB, 1))
    pos[[1, 4]] += BS
    pos[2] += 2 * BS
    tables = jnp.asarray([[0, 1, 2]] * 5 + [[3, 4, -1]] * 5, jnp.int32)
    q_pos = jnp.asarray([3, 9, 17, 23, 12, 2, 8, 15, 11, PAD_POSITION],
                        jnp.int32)
    ref, ker = (np.asarray(paged_attention(
        q, k, v, jnp.asarray(pos, jnp.int32), tables, q_pos, 0, k_scale=ks,
        v_scale=vs, force_pallas=force).astype(jnp.float32))
        for force in (False, True))
    np.testing.assert_allclose(ker[:9], ref[:9],
                               atol=4e-3 if pool == "bf16" else 1e-2)
    assert not ker[9].any()


@pytest.mark.parametrize("kind", ["full", "int8", "window"])
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_paged_attention_on_a_stack_equals_the_layers_own_pool(impl, kind):
    """``paged_attention`` reads the stacks at (layer, block): with the
    traced layer ``l`` it gives what the same call gives on ``pool[l]``
    alone (a stack of that one layer), bit for bit, for every layer, from
    the XLA reference and from the kernel (interpret mode) alike."""
    layers = 3
    rows = _RAGGED_CASES["ragged"] + _RAGGED_CASES["pad_row"]
    cases = [_paged_case(rows, kind == "int8", seed=5 + l)
             for l in range(layers)]
    q, _, _, pp, tb, qp, _, _ = cases[0]
    k, v, ks, vs = (
        None if cases[0][i] is None else jnp.concatenate(
            [c[i] for c in cases]) for i in (1, 2, 6, 7))
    # a window of two blocks and a ring of three columns: the table's
    # fourth column then holds summaries
    kw = dict(force_pallas=impl == "kernel",
              window=(2 * _BS, 3) if kind == "window" else None)
    on_stack = jax.jit(lambda l: paged_attention(
        q, k, v, pp, tb, qp, l, k_scale=ks, v_scale=vs, **kw))
    for l in range(layers):
        alone = paged_attention(
            q, k[l:l + 1], v[l:l + 1], pp, tb, qp, 0,
            k_scale=None if ks is None else ks[l:l + 1],
            v_scale=None if vs is None else vs[l:l + 1], **kw)
        np.testing.assert_array_equal(np.asarray(on_stack(jnp.int32(l))),
                                      np.asarray(alone))
    assert not np.array_equal(np.asarray(on_stack(jnp.int32(0))),
                              np.asarray(on_stack(jnp.int32(2))))


@pytest.mark.parametrize("rewire", ["other_block", "unmapped"])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_attention_ignores_columns_behind_the_row(quantized, rewire):
    """What the walk skips cannot matter: every column beyond
    ``q_pos // block_size`` rewired to a block full of another sequence's
    live positions, or to -1, leaves the output bitwise the same."""
    rows = [r for case in _RAGGED_CASES.values() for r in case]
    q, k, v, pp, tb, qp, ks, vs = _paged_case(rows, quantized, seed=3)
    behind = np.arange(_MAXB)[None, :] > (np.asarray(qp) // _BS)[:, None]
    assert behind.any() and not behind.all()
    rewired = np.where(behind, 1 if rewire == "other_block" else -1,
                       np.asarray(tb))
    assert (rewired != np.asarray(tb)).any()
    out, out_rewired = (
        paged_attention(q, k, v, pp, jnp.asarray(t, jnp.int32), qp, 0,
                        k_scale=ks, v_scale=vs, force_pallas=True)
        for t in (tb, rewired))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_rewired))


def test_column_live_matches_brute_count():
    """A column is live iff it is mapped and holds at least one position
    the row may attend to, counted position by position; a pad row
    attends none."""
    rng = np.random.RandomState(4)
    bs, maxb, rows = 4, 5, 64
    tables = rng.randint(-1, 6, (rows, maxb))
    q_pos = rng.randint(0, bs * (maxb + 2), (rows,))
    q_pos[:4] = [0, bs - 1, bs, PAD_POSITION]
    got = column_live(tables, np.arange(maxb), q_pos[:, None], bs)
    want = np.zeros((rows, maxb), bool)
    for r in range(rows):
        for c in range(maxb):
            want[r, c] = (tables[r, c] >= 0 and q_pos[r] != PAD_POSITION
                          and any(p <= q_pos[r]
                                  for p in range(c * bs, (c + 1) * bs)))
    np.testing.assert_array_equal(got, want)
    assert tables[3].max() >= 0 and not got[3].any()
    # with the mapped columns a prefix, as the engine maps them, the count
    # has a closed form
    prefix = np.sort(tables >= 0, axis=1)[:, ::-1]
    np.testing.assert_array_equal(
        column_live(np.where(prefix, 0, -1), np.arange(maxb),
                    q_pos[:, None], bs).sum(axis=1),
        np.where(q_pos == PAD_POSITION, 0,
                 np.minimum(q_pos // bs + 1, prefix.sum(axis=1))))


@pytest.mark.parametrize("case", ["random_tables", "shared_prefix",
                                  "pad_rows", "one_slot_chunk"])
def test_tile_walk_serves_every_live_column_by_one_pair_of_its_tile(case):
    rng = np.random.RandomState(6)
    bs, maxb, nb, t, n_rep = 4, 5, 9, 21, 16
    rows = tile_rows(n_rep, t)
    assert rows == 8
    tables = rng.randint(-1, nb, (t, maxb))
    q_pos = rng.randint(0, bs * (maxb + 1), (t,))
    if case == "shared_prefix":
        tables[:, :2] = [2, 7]              # every slot's first two blocks
    elif case == "pad_rows":
        q_pos[[0, 3, 4, 11, 19, 20]] = PAD_POSITION
    elif case == "one_slot_chunk":
        tables[2:19] = [1, 3, 5, 8, -1]
        q_pos[2:19] = 2 + np.arange(17)
    walk = jax.tree_util.tree_map(np.asarray, tile_walk(
        jnp.asarray(tables, jnp.int32), jnp.asarray(q_pos, jnp.int32), bs,
        nb, n_rep))
    live = column_live(tables, np.arange(maxb), q_pos[:, None], bs)
    check_tile_walk(walk, live, tables, rows, n_rep)
    # the engine counts with the same function over NumPy arrays
    count, blocks, cols = tile_pairs(np.where(live, tables, -1), rows, nb,
                                     xp=np)
    np.testing.assert_array_equal(count, walk.count)
    for i, n in enumerate(count):
        np.testing.assert_array_equal(
            blocks[i, :n], walk.blocks.reshape(len(count), -1)[i, :n])
        np.testing.assert_array_equal(
            cols[i, :n], walk.cols.reshape(len(count), -1)[i, :n])
    if case == "one_slot_chunk":
        # a tile of one slot's rows fetches each of its blocks once
        assert count[1] == int(live[8:16].any(0).sum())
        assert int(live[8:16].sum()) > 2 * count[1]


def _decode_rows_beside_a_chunk(n_rep, bs=4, maxb=5, seed=8, slot_rows=1):
    """A packed step as the engine packs it, two tiles of rows: decode
    rows of slots that share nothing, a whole tile of them and more (so
    every place of a tile holds one, its last among them), then a chunk
    of one slot; rows 1 and 2, and rows 4 and 5, have forked from one
    prefix block. With ``slot_rows`` a decoding slot packs that many rows
    on a multiple of it, all at one position (a block family's, which
    attend through their block's last), and nothing is forked. ``(tables,
    q_pos, num_blocks, rows)``."""
    rng = np.random.RandomState(seed)
    rows = tile_rows(n_rep, 2 * 128)
    t = 2 * rows
    chunk = t - (t - min(rows // 2, 12)) // slot_rows * slot_rows
    slots = (t - chunk) // slot_rows + 1
    tables = np.full((t, maxb), -1, np.int64)
    q_pos = np.zeros((t,), np.int64)
    for r in range(t):
        slot = min(r // slot_rows, slots - 1)
        if slot < slots - 1:
            q_pos[r] = (rng.randint(0, bs * maxb) if r % slot_rows == 0
                        else q_pos[r - 1])
        else:
            q_pos[r] = bs * maxb - chunk + (r - slot * slot_rows)
        held = q_pos[r] // bs + 1
        tables[r, :held] = slot * maxb + np.arange(held)
    if slot_rows == 1:
        tables[2, 0] = tables[1, 0]
        tables[5, 0] = tables[4, 0]
    return tables, q_pos, slots * maxb, rows


def _pairs_by_brute_count(live, tables, rows, n_rep, group, slot_rows=1):
    """``[narrow, one_row_whole, shared]`` over a step's tiles by loops:
    a pair is narrow where ``group`` rows from the sublane of the first
    head that names it (from the tile's last group, if earlier) hold the
    last one too; with ``slot_rows``, where the slot's group that holds
    the first does (``walk_checks.group_start``)."""
    kinds = [0, 0, 0]
    for i in range(-(-len(tables) // rows)):
        want = {}
        for r in range(i * rows, min((i + 1) * rows, len(tables))):
            for c in np.flatnonzero(live[r]):
                want.setdefault((int(c), int(tables[r, c])), []).append(
                    r - i * rows)
        for namers in want.values():
            first, last = namers[0] * n_rep, (namers[-1] + 1) * n_rep
            begin = min(first // 8 * 8, rows * n_rep - group)
            if (last <= begin + group if slot_rows == 1 else group_start(
                    first, last, n_rep, rows * n_rep, slot_rows) >= 0):
                kinds[0] += 1
            else:
                kinds[1 if len(namers) == 1 else 2] += 1
    return kinds


@pytest.mark.parametrize("n_rep", [1, 4, 6, 8, 9, 10])
def test_every_pair_that_one_packed_row_names_is_narrow(n_rep):
    """Whatever the head count, and wherever in its tile the row lies
    (the last row, whose group ends with the tile, included): a decode
    row's pair runs over one group of :func:`narrow_rows` rows. Two
    neighbours that share a block stay narrow where one group holds
    both."""
    bs = 4
    tables, q_pos, nb, rows = _decode_rows_beside_a_chunk(n_rep)
    group = narrow_group(n_rep)
    assert pa.narrow_rows(n_rep) == group == {
        1: 8, 4: 8, 6: 16, 8: 8, 9: 16, 10: 16}[n_rep]
    assert group <= rows * n_rep and rows * n_rep % 8 == 0
    walk = jax.tree_util.tree_map(np.asarray, tile_walk(
        jnp.asarray(tables, jnp.int32), jnp.asarray(q_pos, jnp.int32), bs,
        nb, n_rep))
    live = column_live(tables, np.arange(tables.shape[1]), q_pos[:, None],
                       bs)
    check_tile_walk(walk, live, tables, rows, n_rep)
    per = walk.blocks.size // len(walk.count)
    seen = set()
    for i, n in enumerate(walk.count):
        for j in range(n):
            col, blk = walk.cols[i * per + j], walk.blocks[i * per + j]
            namers = np.flatnonzero(
                live[i * rows:(i + 1) * rows, col]
                & (tables[i * rows:(i + 1) * rows, col] == blk))
            start = walk.narrow[i * per + j]
            if len(namers) == 1:
                seen.add(int(namers[0]))
                first = namers[0] * n_rep
                assert 0 <= start <= first
                assert first + n_rep <= start + group <= rows * n_rep
            elif blk == tables[1, 0]:
                # rows 1 and 2: n_rep .. 3 n_rep, from sublane n_rep // 8
                assert (start >= 0) == (3 * n_rep <= n_rep // 8 * 8 + group)
    assert set(range(rows)) <= seen                 # every place of a tile
    kinds = pair_kinds(np.where(live, tables, -1), n_rep, nb)
    assert kinds.tolist() == _pairs_by_brute_count(live, tables, rows,
                                                   n_rep, group)
    assert kinds[0] > 0 and kinds[1] == 0 and kinds[2] > 0
    assert kinds.sum() == walk.count.sum()


@pytest.mark.parametrize("n_rep,group", [(6, 8), (6, 16), (9, 16), (4, 8)])
def test_pair_kinds_are_the_brute_count_of_a_steps_tables(monkeypatch, n_rep,
                                                          group):
    """The engine's ``nxd_paged_pairs_total`` and
    ``nxd_paged_shared_pairs_total`` by loops over the tables, at the
    kernel's group and, at 6 heads, at the 8 rows the group was before
    PR 43 (rows whose heads cross a multiple of 8 ran over the whole
    tile)."""
    monkeypatch.setattr(pa, "narrow_rows", lambda n, slot_rows=1: group)
    tables, q_pos, nb, rows = _decode_rows_beside_a_chunk(n_rep, seed=9)
    live = column_live(tables, np.arange(tables.shape[1]), q_pos[:, None],
                       4)
    kinds = pair_kinds(np.where(live, tables, -1), n_rep, nb)
    assert kinds.tolist() == _pairs_by_brute_count(live, tables, rows,
                                                   n_rep, group)
    assert kinds.sum() == tile_pairs(np.where(live, tables, -1), rows, nb,
                                     xp=np)[0].sum()
    straddles = (n_rep, group) == (6, 8)
    assert (kinds[1] > 0) == straddles
    if straddles:
        # about every second decode row's heads cross a multiple
        assert 0.3 < kinds[1] / (kinds[0] + kinds[1]) < 0.7


@pytest.mark.parametrize("sliding", [None, 8], ids=["full", "sliding"])
@pytest.mark.parametrize("n_rep", [6, 9])
def test_paged_kernel_at_heads_that_cross_sublanes_matches_xla(n_rep,
                                                               sliding):
    """The kernel (interpret mode) against the gather reference at 6 and
    9 query heads a K/V head, whose rows begin at every offset of a
    sublane: decode rows in every place of a tile, its last rows among
    them, a chunk beside them, with and without a causal window over a
    ring."""
    bs, kv, d = 4, 2, 16
    rng = np.random.RandomState(n_rep)
    tables, q_pos, nb, rows = _decode_rows_beside_a_chunk(n_rep)
    t, maxb = tables.shape
    pool_pos = np.full((nb, bs), PAD_POSITION, np.int32)
    if sliding is None:
        for blk in range(nb):
            pool_pos[blk] = blk % maxb * bs + np.arange(bs)
    else:
        # a slot's ring of 3 blocks, its own alone: position p in ring
        # column (p // bs) % 3, later laps overwrite
        ring = sliding // bs + 1
        slot = np.minimum(np.arange(t), nb // maxb - 1)
        tables = slot[:, None] * maxb + np.arange(ring)
        for s in range(nb // maxb):
            for p in range(q_pos[slot == s].max() + 1):
                pool_pos[s * maxb + p // bs % ring, p % bs] = p
    q = jnp.asarray(rng.randn(t, kv * n_rep, d).astype(np.float32))
    k = jnp.asarray(rng.randn(2, nb, bs, kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(2, nb, bs, kv, d).astype(np.float32))
    args = (q, k, v, jnp.asarray(pool_pos), jnp.asarray(tables, jnp.int32),
            jnp.asarray(q_pos, jnp.int32), 1)
    ref = paged_attention(*args, force_pallas=False, sliding=sliding)
    ker = paged_attention(*args, force_pallas=True, sliding=sliding)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    # the walk the kernel ran: no one-row pair over the whole tile
    walk = tile_walk(args[4], args[5], bs, nb, n_rep, sliding=sliding)
    narrow = np.asarray(walk.narrow).reshape(len(walk.count), -1)
    assert sum((narrow[i, :n] >= 0).sum()
               for i, n in enumerate(np.asarray(walk.count))) >= rows


# ---------------------------------------------------------------------------
# The kernel's units: the blocks of one narrow group ride in runs, up to
# ``run_blocks`` of them side by side and one step of the online softmax
# over all their positions. Tiny pools would all take 8: the cases patch
# the length (no option chooses it), 1 being the kernel without runs.
# ---------------------------------------------------------------------------

_UNMAPPED = -1      # a real row whose table maps nothing: attends nothing


def _run_scene(rows, heads, d=16, dv=None, bs=4, maxb=8, sliding=None,
               quantized=False, sink=False, seed=0, slot_rows=1,
               dtype=np.float32):
    """A pool in which every slot holds its own blocks in a scrambled
    order and ``rows`` (``(slot, position)``, ``None`` a pad row,
    ``(_UNMAPPED, position)`` a row that attends nothing) are one packed
    step: ``(args, kwargs, tables, q_pos, live)`` for ``paged_attention``.
    ``sliding``: a slot's ring of ``sliding // bs + 1`` blocks, later laps
    overwriting earlier ones. ``dv``: values narrower than the keys, the K
    pool in whole lanes. ``slot_rows``: the rows a slot packs side by side
    (a block family's), handed to the kernel; ``dtype``: the queries' and
    the pools'."""
    n, kv = heads
    dv = dv or d
    rng = np.random.RandomState(seed)
    last = {}
    for slot, p in filter(None, rows):
        if slot != _UNMAPPED:
            last[slot] = max(last.get(slot, 0), p)
    ring = None if sliding is None else sliding // bs + 1
    maxb = ring or maxb
    held = {s: ring or p // bs + 1 for s, p in last.items()}
    nb = sum(held.values()) + 1
    free = rng.permutation(nb).tolist()
    table = {_UNMAPPED: np.full((maxb,), -1, np.int64)}
    pos = np.full((nb, bs), PAD_POSITION, np.int32)
    for s, many in held.items():
        table[s] = np.full((maxb,), -1, np.int64)
        table[s][:many] = [free.pop() for _ in range(many)]
        for p in range(last[s] + 1):
            pos[table[s][p // bs % maxb], p % bs] = p
    tables = np.stack([table[r[0] if r else _UNMAPPED] for r in rows])
    q_pos = np.array([r[1] if r else PAD_POSITION for r in rows], np.int64)
    q = jnp.asarray(rng.randn(len(rows), n, d), dtype)
    k = jnp.asarray(rng.randn(2, nb, bs, kv, d), dtype)
    v = jnp.asarray(rng.randn(2, nb, bs, kv, dv), dtype)
    kw = dict(sliding=sliding)
    if slot_rows != 1:
        kw["slot_rows"] = slot_rows
    if quantized:
        (k, kw["k_scale"]), (v, kw["v_scale"]) = quantize_kv(k), quantize_kv(v)
    if dv != d:
        k = pa.keys_to_lanes(k)
    if sink:
        kw["sink"] = jnp.asarray(rng.randn(n).astype(np.float32))
    cols = np.arange(maxb)
    live = (column_live(tables, cols, q_pos[:, None], bs) if sliding is None
            else pa.sliding_column_live(tables, cols, q_pos[:, None], bs,
                                        sliding, maxb))
    args = (q, k, v, jnp.asarray(pos), jnp.asarray(tables, jnp.int32),
            jnp.asarray(q_pos, jnp.int32), 1)
    return args, kw, tables, q_pos, np.asarray(live)


def _decode_rows(lengths, first=0):
    return [(first + s, n - 1) for s, n in enumerate(lengths)]


def _block_step(lengths, chunk, b=4, dead=1):
    """A step as ``inference/block_serving.py`` packs it for a family that
    decodes blocks of ``b``: a slot of each of ``lengths`` packs its
    block's ``b`` rows on a multiple of ``b``, every one attending through
    the block's last position; ``dead`` groups of pad rows among them (a
    slot that finished); then a prefill chunk of ``chunk`` rows of one
    more slot, from its position 8."""
    rows = []
    for s, n in enumerate(lengths):
        rows += [(s, (n - 1) // b * b + b - 1)] * b
        if s < dead:
            rows += [None] * b
    return rows + [(len(lengths), p // b * b + b - 1)
                   for p in range(8, 8 + chunk)]


def _scattered(rows, seed=3):
    """The rows in an order no engine packs: the kernel's contract holds
    whatever the order, a scattered one shares less."""
    order = np.random.RandomState(seed).permutation(len(rows))
    return [rows[i] for i in order]


_BLOCK_STEP = _block_step([30, 9, 14, 32, 5, 21, 27], 20)


#: name -> (the scene's arguments, ``[in_run, alone, whole]`` under runs
#: of 4 where the case fixes them)
_RUN_KERNEL_CASES = {
    # 27 positions are 7 blocks, a run of four and one of three; a row of
    # one block is a unit by itself
    "a_short_last_run": (dict(rows=[(0, 26), (1, 2)], heads=(32, 2)),
                         (7, 1, 0)),
    # a chunk of five rows (3 blocks, the tile's) beside three decode rows
    "a_chunk_beside_decode_rows": (dict(
        rows=[(0, p) for p in range(7, 12)] + _decode_rows([23, 4, 30], 1),
        heads=(32, 2)), (14, 1, 3)),
    # GQA-4: rows 0 and 1 share a sublane, so one group; their blocks lie
    # column by column in one sequence of runs, each block under the mask
    # of the row that names it
    "two_packed_rows_in_one_group": (dict(
        rows=_decode_rows([30, 9, 14, 27, 5, 21]), heads=(8, 2)),
        (30, 0, 0)),
    # GQA-6, a tile of 20 rows filled: the last rows' groups begin where
    # the tile's last group does, and neighbouring rows' groups overlap
    "overlapping_groups_at_the_tiles_end": (dict(
        rows=_decode_rows([5 + 3 * (r % 7) for r in range(20)]),
        heads=(12, 2)), None),
    # keys of 192 beside values of 128, a sink a head
    "wide_keys_with_a_sink": (dict(
        rows=_decode_rows([30, 9, 18]) + [(3, p) for p in range(20, 25)],
        heads=(16, 2), d=192, dv=128, sink=True), None),
    # a window of one block over a ring of two columns, a sink a head
    "a_sliding_ring_of_two_columns": (dict(
        rows=_decode_rows([30, 3, 9, 18]) + [(4, p) for p in range(9, 13)],
        heads=(16, 2), sliding=4, sink=True), None),
    "an_int8_pool": (dict(
        rows=_decode_rows([30, 9, 14]) + [(3, p) for p in range(14, 19)],
        heads=(8, 2), quantized=True), None),
    # a pad row between real ones, and a real row whose table maps nothing
    "a_pad_row_and_a_row_that_attends_nothing": (dict(
        rows=[(0, 22), None, (1, 9), (_UNMAPPED, 5), (2, 17), None],
        heads=(32, 2)), (13, 1, 0)),
    # a block family's step at GQA-8, tiles of 16 rows: a slot's four rows
    # are one group of 32 stacked rows and its 8, 3, 4, 8, 2, 6 and 7
    # blocks are that group's runs, a dead group attends nothing, and the
    # chunk's blocks, which more rows name than a group holds, stay whole-
    # tile pairs of the two tiles it lies in
    "a_block_familys_step": (dict(
        rows=_BLOCK_STEP, heads=(16, 2), slot_rows=4), None),
    "a_block_familys_step_scattered": (dict(
        rows=_scattered(_BLOCK_STEP), heads=(16, 2), slot_rows=4), None),
    "a_block_familys_step_over_bf16_pools": (dict(
        rows=_BLOCK_STEP, heads=(16, 2), slot_rows=4, dtype=jnp.bfloat16),
        None),
    # GQA-2 at blocks of 4 rows (the toy model's): the group is 8 rows
    "a_block_familys_step_at_two_heads": (dict(
        rows=_BLOCK_STEP, heads=(4, 2), slot_rows=4), None),
}


#: the gather reference's answer to a scene: its three lengths of run
#: share the (seeded) operands, and the reference takes no runs
_XLA_ANSWERS = {}


@pytest.mark.parametrize("run", [1, 2, 4])
@pytest.mark.parametrize("case", list(_RUN_KERNEL_CASES))
def test_paged_kernel_in_runs_matches_xla(monkeypatch, case, run):
    """The kernel (interpret mode) with a narrow group's blocks in runs of
    ``run`` (1: a pair a turn, the kernel of a pool whose blocks are too
    large to ride in runs) against the gather reference; a pad row and a
    row that attends nothing give zeros; the walk's cut into units holds
    and the host's count of the fetches is the walk's."""
    scene, counts = _RUN_KERNEL_CASES[case]
    args, kw, tables, q_pos, live = _run_scene(**scene)
    n_rep = scene["heads"][0] // scene["heads"][1]
    run = min(run, 1 << tables.shape[1].bit_length() - 1)
    monkeypatch.setattr(pa, "run_blocks", lambda *_: run)
    if case not in _XLA_ANSWERS:
        # in float32 over the operands' own values, bf16 ones too
        exact = tuple(x.astype(jnp.float32) if x.dtype == jnp.bfloat16
                      else x for x in args[:3]) + args[3:]
        _XLA_ANSWERS[case] = np.asarray(paged_attention(
            *exact, force_pallas=False, **kw))
    ref = _XLA_ANSWERS[case]
    ker = np.asarray(paged_attention(*args, force_pallas=True, **kw)
                     .astype(jnp.float32))
    assert ker.shape == ref.shape
    real = live.any(axis=1)
    # a bf16 output is rounded once, at the kernel's end
    tol = 1e-5 if args[0].dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(ker[real], ref[real], rtol=tol, atol=tol)
    assert not ker[~real].any()
    if run == 1:
        return
    nb = args[3].shape[0]
    slot_rows = scene.get("slot_rows", 1)
    kinds = check_paged_runs(tables, q_pos, live, 4, nb, n_rep, run,
                             sliding=scene.get("sliding"),
                             slot_rows=slot_rows)
    if run == 4 and counts is not None:
        assert tuple(kinds) == counts
    assert kinds[0] > 0
    if slot_rows > 1 and "scattered" not in case:
        # the decoding slots' blocks are their groups' and ride in runs:
        # what is whole is the chunk's, and with one row a group (the
        # parent's rule) every block of a slot of four rows was
        served = np.where(live, tables, -1)
        slots = len({r[0] for r in scene["rows"] if r}) - 1
        assert kinds[2] <= 2 * 8 and kinds[1] <= slots
        if narrow_group(n_rep) < narrow_group(n_rep, slot_rows):
            assert pa.block_fetches(served, n_rep, run)[2] == kinds.sum()


@pytest.mark.parametrize("run", [2, 4, 8])
@pytest.mark.parametrize("n_rep,slot_rows", [
    (1, 1), (4, 1), (6, 1), (8, 1), (9, 1), (16, 1),
    (8, 4), (4, 4), (16, 2), (6, 8), (9, 4)],
    ids=lambda x: str(x))
def test_a_narrow_groups_pairs_are_cut_into_runs(n_rep, slot_rows, run):
    """:func:`pair_runs` keyed by a group's first row, at every kind of
    group: one packed row's heads (8, 16), two rows' in one sublane (4),
    eight rows' (1), neighbours whose groups overlap (6, 9): decode rows
    in every place of two tiles beside a chunk. And a slot's rows' heads,
    where a slot packs several (a block family's four rows of 8 heads: 32
    stacked rows on a multiple of 32; four rows of 4, two of 16; eight
    rows of 6, 48 of a tile of 120, whose end cuts the third group: that
    slot's pairs run over the whole tile; four rows of 9 heads fill no
    whole sublanes, and the group stays one row's 16)."""
    tables, q_pos, nb, rows = _decode_rows_beside_a_chunk(
        n_rep, slot_rows=slot_rows)
    live = column_live(tables, np.arange(tables.shape[1]), q_pos[:, None],
                       4)
    kinds = check_paged_runs(tables, q_pos, live, 4, nb, n_rep, run,
                             slot_rows=slot_rows)
    served = np.where(live, tables, -1)
    narrow, one_row_whole, shared = pair_kinds(served, n_rep, nb,
                                               slot_rows=slot_rows)
    assert (kinds[0] + kinds[1], kinds[2]) == (narrow + one_row_whole,
                                               shared)
    group = narrow_group(n_rep, slot_rows)
    assert pa.narrow_rows(n_rep, slot_rows) == group
    if group < slot_rows * n_rep:
        # four rows of 9 heads: no group holds a slot's rows, as before
        assert kinds[0] == kinds[1] == 0 < kinds[2]
        return
    assert kinds[0] > kinds[1] > 0
    if slot_rows == 1:
        return
    assert group == {(8, 4): 32, (4, 4): 16, (16, 2): 32,
                     (6, 8): 48}[n_rep, slot_rows]
    assert [narrow, one_row_whole, shared] == _pairs_by_brute_count(
        live, tables, rows, n_rep, group, slot_rows)
    # a decoding slot's blocks are narrow pairs of its own group, on a
    # multiple of the group, where the parent's rule (one row a group) ran
    # every one of them over the whole tile
    cut = rows * n_rep % group > 0
    assert (shared > tables.shape[1]) == cut
    old = pair_kinds(served, n_rep, nb)
    assert old[0] == 0 and old[2] == narrow + shared
    first, last, start, _ = pa.host_pairs(served, n_rep, nb, slot_rows)
    assert (start[start >= 0] % group == 0).all()
    assert (first[start >= 0] * n_rep >= start[start >= 0]).all()
    assert ((last[start >= 0] + 1) * n_rep
            <= start[start >= 0] + group).all()


@pytest.mark.parametrize("first,last,n_rep,wide,slot_rows,start", [
    (0, 32, 8, 128, 4, 0),          # a slot's four rows, the tile's first
    (96, 128, 8, 128, 4, 96),       # and its last
    (40, 48, 8, 128, 4, 32),        # one row inside a group: the group's
    (40, 64, 8, 128, 4, 32),        # first named off the boundary, inside
    (40, 72, 8, 128, 4, -1),        # ... and past the group's end: whole
    (8, 40, 8, 128, 4, -1),         # four rows across two groups: whole
    (0, 128, 8, 128, 4, -1),        # a chunk's tile
    (40, 48, 8, 128, 1, 40),        # one row a group: the row's own
    (40, 72, 8, 128, 1, -1),
    (16, 48, 16, 128, 2, -1),       # GQA-16, two rows: off the boundary
    (32, 64, 16, 128, 2, 32),
    (48, 96, 6, 120, 8, 48),        # GQA-6, eight rows: 48 of 120
    (96, 102, 6, 120, 8, -1),       # the group the tile's end cuts
    (36, 72, 9, 72, 4, -1),         # 36 heads fill no whole sublanes: one
    (36, 45, 9, 72, 4, 32),         # row a group, as without slot_rows
    (0, 128, 8, 128, 16, 0),        # a group as tall as the tile
    (0, 64, 8, 64, 16, -1),         # and one taller than a short tile
], ids=lambda x: str(x))
def test_a_slots_group_begins_on_a_multiple_of_its_height(first, last, n_rep,
                                                          wide, slot_rows,
                                                          start):
    """:func:`narrow_start` by cases: a slot's group is floored to the
    group and not to the sublane, so a pair first named off a group's
    boundary is its group's if the group holds its last namer and the
    whole tile's if not, never the next group's."""
    for xp in (np, jnp):
        got = pa.narrow_start(xp.asarray(first), xp.asarray(last), n_rep,
                              wide, xp=xp, slot_rows=slot_rows)
        assert int(got) == start
    assert group_start(first, last, n_rep, wide, slot_rows) == start
    if start >= 0:
        group = pa.narrow_rows(n_rep, slot_rows)
        assert start % pa.run_stride(n_rep, slot_rows) == 0
        assert start <= first and last <= start + group <= wide


#: ``_paged_attention_pallas``'s static arguments and the digest of
#: ``step_walk``'s jaxpr at the serving cells' shapes with one row a slot,
#: recorded on the parent of PR 70 (a5a0aa2): the proof that the cells of
#: the other families run the programs they ran
_PARENTS_PROGRAMS = {
    "gqa4_runs_of_4": (dict(n=32, kv=8, t=256, maxb=20), "e1b4d6a4161bea7b",
                       dict(kernel="_paged_run_kernel", grid=(8,), run=4,
                            whole_named=False, pairs=640, group=8)),
    "gqa8_runs_of_8": (dict(n=32, kv=4, t=640, maxb=32), "a52fc1b4b5100410",
                       dict(kernel="_paged_run_kernel", grid=(40,), run=8,
                            whole_named=True, pairs=512, group=8)),
    "mha_a_pair_a_turn": (dict(n=32, kv=32, t=128, maxb=40),
                          "329e3bd1d0d4457b",
                          dict(kernel="_paged_kernel", grid=(1,), pairs=5120,
                               group=8)),
    "gqa6_runs_of_4": (dict(n=48, kv=8, t=256, maxb=20), "07f1f2a5f6103244",
                       dict(kernel="_paged_run_kernel", grid=(13,), run=4,
                            whole_named=False, pairs=400, group=16)),
    # the block family's pools are ``gqa8_runs_of_8``'s: what its four
    # rows a slot change is the group, and with it the comparison by name
    "gqa8_four_rows_a_slot": (dict(n=32, kv=4, t=640, maxb=32, slot_rows=4),
                              None,
                              dict(kernel="_paged_run_kernel", grid=(40,),
                                   run=8, whole_named=False, pairs=512,
                                   group=32)),
}


def _traced_program(monkeypatch, n, kv, t, maxb, slot_rows=None, d=128,
                    nb=64, bs=128):
    """``(digest of step_walk's jaxpr, the kernel's static arguments)`` at
    bf16 pools ``[2, nb, bs, kv, d]``; ``slot_rows`` None: not handed."""
    import hashlib

    from jax.experimental import pallas as pl

    kw = {} if slot_rows is None else dict(slot_rows=slot_rows)
    pool = jax.ShapeDtypeStruct((2, nb, bs, kv, d), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((t, maxb), jnp.int32)
    q_pos = jax.ShapeDtypeStruct((t,), jnp.int32)
    text = str(jax.make_jaxpr(lambda tb, qp: pa.step_walk(
        tb, qp, bs, nb, d, n // kv, force_pallas=True, pools=(pool, pool),
        **kw))(tables, q_pos))
    seen = {}

    def spy(kernel, **call):
        seen.update(kernel=kernel.func.__name__, name=call["name"],
                    grid=call["grid_spec"].grid, **kernel.keywords)
        raise StopIteration

    monkeypatch.setattr(pl, "pallas_call", spy)
    with pytest.raises(StopIteration):
        jax.eval_shape(
            lambda q, k, v, pos, tb, qp: pa._paged_attention_pallas(
                q, k, v, pos, tb, qp, 1, None, None, 0.1, interpret=True,
                **kw),
            jax.ShapeDtypeStruct((t, n, d), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((nb, bs), jnp.int32), tables, q_pos)
    return hashlib.sha256(text.encode()).hexdigest()[:16], seen


@pytest.mark.parametrize("case", list(_PARENTS_PROGRAMS))
def test_one_row_a_slot_is_the_parents_program(monkeypatch, case):
    """With one row a slot (``slot_rows`` 1, or not handed at all) the
    walk's arrays are computed by the parent's operations and the kernel
    is built with the parent's static arguments, at the cells' shapes:
    Mistral's and Mixtral's, Granite's, EvaByte's, Laguna's. The block
    family's own program differs from Granite's by its group alone."""
    shape, digest, static = _PARENTS_PROGRAMS[case]
    got, seen = _traced_program(monkeypatch, **shape)
    assert seen.pop("name") == "paged_attention"
    assert {k: seen[k] for k in static} == static
    assert (seen["quantized"], seen["window"], seen["key_at"],
            seen["sink"]) == (False, None, None, False)
    if digest is None:
        plain = _traced_program(monkeypatch, **dict(shape, slot_rows=None))
        assert got != plain[0] and plain[1]["group"] == 8
        return
    assert got == digest
    assert _traced_program(monkeypatch, **shape, slot_rows=1)[0] == digest


def test_the_hosts_count_takes_a_block_familys_rows_a_slot():
    """``StepGeometry`` learns the rows a slot packs from the family
    (``serving_family().block``) and from nothing else, and the host's
    ``nxd_paged_pairs_total`` and ``nxd_paged_block_fetches_total`` then
    count a decoding slot's blocks as its group's runs, as the kernel
    takes them."""
    from neuronx_distributed_tpu.models import sdar

    cfg = sdar.tiny_config(num_heads=16, num_kv_heads=2)
    kind = cfg.serving_family().cache_kind
    bound = step_counter(kind, cfg, block_size=4, pool_blocks=64, itemsize=4)
    assert bound.args[0].slot_rows == cfg.block_decoding.block_length == 4
    assert bound.args[0].n_rep == 8
    plain = step_counter(kind, tiny_config(num_heads=16, num_kv_heads=2),
                         block_size=4, pool_blocks=64, itemsize=4)
    assert plain.args[0].slot_rows == 1
    # three slots of 9, 3 and 5 blocks, four rows each, and a dead group
    held = [9, 3, 5]
    tables = np.full((4, 12), -1, np.int32)
    for s, many in enumerate(held):
        tables[s, :many] = 20 * s + np.arange(many)
    slot_ids = np.repeat([0, 1, 3, 2], 4).astype(np.int32)
    positions = np.repeat([35, 11, PAD_POSITION, 19], 4).astype(np.int32)
    counts = bound(positions, slot_ids, tables, [9, 3, 5], 0)
    assert list(counts["nxd_paged_pairs_total"]) == [17, 0]
    assert list(counts["nxd_paged_shared_pairs_total"]) == [0]
    # runs of 8: the slot of nine leaves one alone
    assert list(counts["nxd_paged_block_fetches_total"]) == [16, 1, 0]
    counts = plain(positions, slot_ids, tables, [9, 3, 5], 0)
    assert list(counts["nxd_paged_pairs_total"]) == [0, 0]
    assert list(counts["nxd_paged_block_fetches_total"]) == [0, 0, 17]


def test_run_blocks_follow_the_pools_shapes():
    """The run's length from shapes alone: the cells' pools."""
    def pools(kv, d, dv=None, dtype=jnp.bfloat16):
        k = (2, 64, 128, kv, d) if dv is None else (2, 64, 128, kv * d)
        return (jax.ShapeDtypeStruct(k, dtype),
                jax.ShapeDtypeStruct((2, 64, 128, kv, dv or d), dtype))

    assert pa.run_blocks(*pools(4, 192, 128), 16, 256) == 8   # MiMo, full
    assert pa.run_blocks(*pools(8, 192, 128), 8, 2) == 2      # its rings
    assert pa.run_blocks(*pools(8, 128), 4, 20) == 4          # Mistral
    assert pa.run_blocks(*pools(8, 128), 6, 160) == 4         # Laguna, full
    assert pa.run_blocks(*pools(8, 128), 9, 5) == 4           # its rings
    assert pa.run_blocks(*pools(4, 128), 8, 20) == 8          # Granite
    assert pa.run_blocks(*pools(32, 128), 1, 40) == 1         # EvaByte
    assert pa.run_blocks(*pools(8, 128, dtype=jnp.int8), 4, 20) == 8
    assert pa.run_blocks(*pools(4, 128), 8, 32, 4) == 8       # SDAR: 32 rows
    assert pa.unit_blocks(32, 256 << 10, 128) == 8


def test_a_walk_of_the_other_form_is_refused(monkeypatch):
    """The kernel computes its run from the pools it is handed; a walk
    built for other pools (a tile walk where it takes runs) raises."""
    args, kw, *_ = _run_scene(rows=_decode_rows([9, 5]), heads=(8, 2))
    walk = tile_walk(args[4], args[5], 4, args[3].shape[0], 4)
    with pytest.raises(ValueError, match="runs of"):
        paged_attention(*args, force_pallas=True, walk=walk)
    monkeypatch.setattr(pa, "run_blocks", lambda *_: 1)
    out = paged_attention(*args, force_pallas=True, walk=walk)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(paged_attention(
            *args, force_pallas=False)), rtol=1e-5, atol=1e-5)


def test_paged_attention_validates_scales_and_heads():
    q, k, v, pp, tb, qp, ks, vs = _paged_case(_RAGGED_CASES["ragged"], True,
                                              seed=2)
    with pytest.raises(ValueError):
        paged_attention(q, k, v, pp, tb, qp, 0, k_scale=ks)  # no v_scale
    with pytest.raises(ValueError):
        paged_attention(q[:, :3], k, v, pp, tb, qp, 0)  # 3 heads vs 2 kv


# ---------------------------------------------------------------------------
# full-model parity vs the contiguous cache
# ---------------------------------------------------------------------------

@pytest.fixture(params=["llama", "mixtral"])
def tiny_model(request):
    """Both feed-forwards through the one cached forward, each by the
    name its family serves under. Mixtral at ``capacity_factor`` 4.0 (as
    ``engine_parity.py``): no token is dropped at any chunk width, so a
    chunked prefill and a token-by-token decode route alike."""
    ps.initialize_model_parallel()
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32, num_layers=2)
    if request.param == "mixtral":
        cfg = tiny_moe_config(capacity_factor=4.0, **kw)
        module = MixtralForCausalLM(cfg)
    else:
        cfg = tiny_config(**kw)
        module = LlamaForCausalLM(cfg)
    params = meta.unbox(module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return cfg, params


def _forward(cfg, *args, **kw):
    return cfg.serving_family().forward(cfg, *args, **kw)


def _contiguous_decode(cfg, params, toks, quantized=False):
    init = (kvc.init_quantized_kv_cache if quantized else
            lambda *a, **k: kvc.init_kv_cache(*a, dtype=jnp.float32, **k))
    cache = init(cfg.num_layers, 1, 16, cfg.num_kv_heads, cfg.head_dim_)
    out = []
    for i in range(toks.shape[1]):
        lg, cache = _forward(
            cfg, params, toks[:, i:i + 1], jnp.array([[i]], jnp.int32),
            cache)
        out.append(lg[0, 0])
    return jnp.stack(out)


def _paged_cache(cfg, quantized=False):
    """Pool with a deliberately scrambled block order for slot 0."""
    if quantized:
        cache = init_quantized_paged_kv_cache(
            cfg.num_layers, 8, 4, cfg.num_kv_heads, cfg.head_dim_, 2, 4)
    else:
        cache = init_paged_kv_cache(
            cfg.num_layers, 8, 4, cfg.num_kv_heads, cfg.head_dim_, 2, 4,
            dtype=jnp.float32)
    tables = np.full((2, 4), -1, np.int32)
    tables[0, :4] = [5, 2, 7, 0]
    return cache.replace(block_tables=jnp.asarray(tables))


def test_paged_decode_bitwise_matches_contiguous_fp32(tiny_model):
    cfg, params = tiny_model
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 10)), jnp.int32)
    ref = _contiguous_decode(cfg, params, toks)

    cache = _paged_cache(cfg)
    out = []
    for i in range(10):
        lg, cache = _forward(
            cfg, params, toks[:, i:i + 1], jnp.array([[i]], jnp.int32),
            cache, slot_ids=jnp.array([0], jnp.int32))
        out.append(lg[0, 0])
    got = jnp.stack(out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=1e-5)
    assert bool(jnp.all(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))


def test_paged_chunked_prefill_matches_token_by_token(tiny_model):
    """Chunk boundaries are invisible: prefilling 4+3+3 tokens produces
    the same logits as 10 single-token steps (the engine relies on
    this to pack partial prompts)."""
    cfg, params = tiny_model
    rng = np.random.RandomState(1)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 10)), jnp.int32)
    ref = _contiguous_decode(cfg, params, toks)

    cache = _paged_cache(cfg)
    out = []
    for a, b in ((0, 4), (4, 7), (7, 10)):
        pos = jnp.arange(a, b, dtype=jnp.int32)[None]
        lg, cache = _forward(
            cfg, params, toks[:, a:b], pos, cache,
            slot_ids=jnp.full((b - a,), 0, jnp.int32))
        out.append(lg[0])
    got = jnp.concatenate(out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_paged_decode_int8_pool_close_to_contiguous(tiny_model):
    """int8 pools: the contiguous path attends the current step's K/V in
    fresh fp precision and quantizes after, the paged pool quantizes on
    write — so parity is tolerance-based, with greedy tokens equal."""
    cfg, params = tiny_model
    rng = np.random.RandomState(2)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 10)), jnp.int32)
    ref = _contiguous_decode(cfg, params, toks, quantized=True)

    cache = _paged_cache(cfg, quantized=True)
    out = []
    for i in range(10):
        lg, cache = _forward(
            cfg, params, toks[:, i:i + 1], jnp.array([[i]], jnp.int32),
            cache, slot_ids=jnp.array([0], jnp.int32))
        out.append(lg[0, 0])
    got = jnp.stack(out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=0.15)
    assert bool(jnp.all(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))


def test_paged_forward_requires_slot_ids(tiny_model):
    cfg, params = tiny_model
    cache = _paged_cache(cfg)
    with pytest.raises(ValueError, match="slot_ids"):
        _forward(cfg, params, jnp.zeros((1, 1), jnp.int32),
                                 jnp.zeros((1, 1), jnp.int32), cache)


def test_two_slots_are_isolated(tiny_model):
    """A second sequence interleaved into other pool blocks never leaks
    into slot 0's attention."""
    cfg, params = tiny_model
    rng = np.random.RandomState(3)
    ta = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 6)), jnp.int32)
    tb = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 6)), jnp.int32)
    ref = _contiguous_decode(cfg, params, ta)

    cache = init_paged_kv_cache(cfg.num_layers, 8, 4, cfg.num_kv_heads,
                                cfg.head_dim_, 2, 4, dtype=jnp.float32)
    tables = np.full((2, 4), -1, np.int32)
    tables[0, :2] = [3, 6]
    tables[1, :2] = [1, 4]
    cache = cache.replace(block_tables=jnp.asarray(tables))
    out = []
    for i in range(6):
        lg, cache = _forward(
            cfg, params, ta[:, i:i + 1], jnp.array([[i]], jnp.int32),
            cache, slot_ids=jnp.array([0], jnp.int32))
        out.append(lg[0, 0])
        _, cache = _forward(
            cfg, params, tb[:, i:i + 1], jnp.array([[i]], jnp.int32),
            cache, slot_ids=jnp.array([1], jnp.int32))
    np.testing.assert_allclose(np.asarray(jnp.stack(out)), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_model_builder_init_state_paged_kind():
    from neuronx_distributed_tpu.inference.model_builder import NxDModel
    from neuronx_distributed_tpu.inference.paging import (
        PagedKVCache, QuantizedPagedKVCache)

    spec = dict(kind="paged", num_layers=2, num_blocks=8, block_size=4,
                num_kv_heads=2, head_dim=16, max_slots=2,
                max_blocks_per_seq=4, dtype="float32")
    m = NxDModel.__new__(NxDModel)
    m.state_spec = spec
    cache = m.init_state()
    assert isinstance(cache, PagedKVCache)
    assert cache.k.shape == (2, 8, 4, 2, 16)

    m.state_spec = dict(spec, quantized=True)
    qcache = m.init_state()
    assert isinstance(qcache, QuantizedPagedKVCache)
    assert qcache.k.dtype == jnp.int8


# ---------------------------------------------------------------------------
# A step's counters: what a family's cache kind and its serving_family()
# declare. The names and labels are what the benchmark's readers, the
# documents and the dashboards find them by.
# ---------------------------------------------------------------------------

_PAGED = {"nxd_paged_columns_total": ("skipped", "live"),
          "nxd_paged_block_visits_total": ("fetched", "shared"),
          "nxd_paged_pairs_total": ("narrow", "one_row_whole"),
          "nxd_paged_shared_pairs_total": (),
          "nxd_paged_block_fetches_total": ("in_run", "alone", "whole")}
_STATES = {"nxd_state_resets_total": (),
           "nxd_state_slot_steps_total": ("advanced", "held")}
_MOE = {"nxd_moe_assignments_total": ("kept", "dropped")}
_HELD = {"nxd_state_bytes_held_total": ("state", "tail", "kv"),
         "nxd_state_segment_rows_total": ("first", "later")}
DECLARED = {
    "llama": _PAGED,
    "mixtral": _PAGED,
    "evabyte": {
        "nxd_eva_columns_total": ("skipped", "exact", "summary"),
        "nxd_eva_windows_total": (),
        **{n: _PAGED[n] for n in list(_PAGED)[1:]}},
    "minicpm_sala": {
        **_STATES,
        "nxd_sparse_columns_total": ("selected", "forced", "dense",
                                     "skipped"),
        "nxd_sparse_positions_total": ("attended", "skipped"),
        "nxd_sparse_block_visits_total": ("fetched", "shared"),
        "nxd_sparse_key_visits_total": ("fetched", "shared")},
    "glm_moe_lite": {
        "nxd_paged_columns_total": ("skipped", "live"),
        "nxd_paged_block_visits_total": ("fetched", "shared"),
        "nxd_mla_block_fetches_total": ("in_run", "alone", "whole"),
        "nxd_mla_shared_blocks_total": ("in_unit", "alone"),
        "nxd_step_rows_by_context_total": ("to_2k", "to_8k", "past_8k"),
        **_MOE},
    "granite_hybrid": {**_PAGED, **_STATES, **_HELD},
    "laguna": {
        **_PAGED,
        "nxd_window_columns_total": ("live", "behind"),
        "nxd_kv_blocks_held_total": ("full", "window"),
        "nxd_kv_bytes_held_total": ("full_k", "full_v", "window_k",
                                    "window_v"),
        "nxd_step_rows_by_context_total": ("to_2k", "to_8k", "past_8k"),
        **_MOE,
        "nxd_moe_held_total": ("held", "elsewhere")},
}
#: the second window-pool family declares what the first does
DECLARED["mimo_v2"] = DECLARED["laguna"]
#: the state-pool family with held experts: the first's, and the routed
#: assignments its step counts into the kind's ``moe_counts``
DECLARED["solar_open2"] = {**DECLARED["granite_hybrid"], **_MOE,
                           "nxd_moe_held_total": ("held", "elsewhere")}
#: Granite with routed experts (granite-4.0-h-small) declares what Solar
#: does: the dense models' and the routed assignments
DECLARED["granite_moe_hybrid"] = DECLARED["solar_open2"]
#: the family of one-block layers (models/nemotron_h.py): Granite's with
#: routed experts, and the held experts a step hit and left idle
DECLARED["nemotron_h"] = {**DECLARED["granite_moe_hybrid"],
                          "nxd_moe_experts_hit_total": ("hit", "idle")}
#: the second latent family: GLM's, and what a router that also scores
#: identity experts over a share of the real ones counts
DECLARED["longcat_flash"] = {**DECLARED["glm_moe_lite"],
                             "nxd_moe_held_total": ("held", "elsewhere"),
                             "nxd_moe_identity_total": ("identity", "routed")}
#: the family that decodes blocks (models/sdar.py): the full cache's, the
#: routed assignments of a bank held whole, and the blocks' own counters,
#: which the step leaves behind its tokens
DECLARED["sdar"] = {
    **_PAGED, **_MOE,
    "nxd_block_passes_total": ("denoise", "store"),
    "nxd_block_rows_total": ("by_threshold", "by_quota", "left_masked",
                             "already_uncovered", "stored"),
    "nxd_blocks_finished_total": ()}
#: the latent family whose rows attend a selection (models/deepseek_v32.py):
#: no kernel walks its table's blocks on the host's count; what the
#: selection did is the device's, beside a share's routed assignments
DECLARED["deepseek_v32"] = {
    "nxd_step_rows_by_context_total": ("to_2k", "to_8k", "past_8k"),
    "nxd_dsa_positions_total": ("selected", "passed_over"),
    "nxd_dsa_rows_total": ("selecting", "whole"),
    "nxd_dsa_blocks_total": ("named", "unnamed"),
    "nxd_dsa_selected_total": ("shared_with_previous_row", "new"),
    **_MOE, "nxd_moe_held_total": ("held", "elsewhere")}
#: the leaves a family's step counts into on the device, and their lengths
ON_DEVICE = {"deepseek_v32": {"counts": 8, "moe_counts": 3},
             "sdar": {"moe_counts": 2}, "minicpm_sala": {"counts": 10}, "glm_moe_lite": {"moe_counts": 2},
             "laguna": {"moe_counts": 3}, "mimo_v2": {"moe_counts": 3},
             "solar_open2": {"moe_counts": 3},
             "granite_moe_hybrid": {"moe_counts": 3},
             "nemotron_h": {"moe_counts": 5},
             "longcat_flash": {"moe_counts": 4}}


def _tiny_family(which):
    import importlib

    module = importlib.import_module(
        "neuronx_distributed_tpu.models."
        + {"granite_moe_hybrid": "granite_hybrid"}.get(which, which))
    if which == "glm_moe_lite":     # a config alone: nothing is built
        return module.GlmMoeLiteConfig().serving_family()
    if which == "longcat_flash":
        return module.LongcatFlashConfig().serving_family()
    if which == "granite_moe_hybrid":
        return module.tiny_config(
            num_experts=8, top_k=3, expert_intermediate_size=32,
            experts_held=(0, 4)).serving_family()
    make = tiny_moe_config if which == "mixtral" else module.tiny_config
    return make().serving_family()


@pytest.mark.parametrize("which", list(DECLARED))
def test_a_family_declares_its_steps_counters(which):
    family = _tiny_family(which)
    declared = family.counters()
    assert [(c.name, c.kinds) for c in declared] \
        == list(DECLARED[which].items())
    assert all(c.help and declared_anywhere()[c.name] is c
               for c in declared)
    on_host = {c.name for c in family.cache_kind.counters}
    leaves = family.device_counts()
    assert {leaf.leaf: leaf.entries for leaf in leaves} \
        == ON_DEVICE.get(which, {})
    fed = [c for leaf in leaves for c, _ in leaf.reads]
    fed += list(BLOCK_COUNTERS) if family.block is not None else []
    assert on_host | {c.name for c in fed} == set(DECLARED[which])
    assert not on_host & {c.name for c in fed}
    for leaf in leaves:
        read = leaf.read(np.arange(1, leaf.entries + 1))
        assert {n: len(v) for n, v in read.items()} \
            == {c.name: max(1, len(c.kinds)) for c, _ in leaf.reads}
        # every entry of the leaf is read, each kind of a family from
        # entries of its own
        used = [i for _, entries in leaf.reads for e in entries for i in e]
        assert set(used) == set(range(leaf.entries))
        for _, entries in leaf.reads:
            own = [i for e in entries for i in e]
            assert len(own) == len(set(own))
    if which in ("laguna", "mimo_v2", "solar_open2", "granite_moe_hybrid"):
        assert leaves[0].read(np.array([5, 2, 4])) == {
            "nxd_moe_assignments_total": [5, 2],
            "nxd_moe_held_total": [7, 4]}
    if which == "nemotron_h":
        # [kept, dropped, elsewhere, hit, idle]: assignments, then experts
        assert leaves[0].read(np.array([5, 2, 4, 3, 1])) == {
            "nxd_moe_assignments_total": [5, 2],
            "nxd_moe_held_total": [7, 4],
            "nxd_moe_experts_hit_total": [3, 1]}
    if which == "longcat_flash":
        # [kept, dropped, elsewhere, identity]: the first three are of the
        # real experts, and together the routed choices
        assert leaves[0].read(np.array([5, 2, 4, 6])) == {
            "nxd_moe_assignments_total": [5, 2],
            "nxd_moe_held_total": [7, 4],
            "nxd_moe_identity_total": [6, 11]}


@pytest.mark.parametrize("which", list(DECLARED))
def test_a_kind_counts_a_step_under_the_names_it_declares(which):
    """The hook's keys are the kind's declared families, each with a count
    a kind (one with none): of a step of two rows of one slot and a pad
    row."""
    positions = np.array([0, 1, PAD_POSITION], np.int32)
    slot_ids = np.array([0, 0, 2], np.int32)
    tables = np.array([[3, -1, -1, -1, -1, -1], [-1] * 6], np.int32)
    kind = _tiny_family(which).cache_kind
    cfg = tiny_config(num_heads=4, num_kv_heads=2)
    counts = step_counter(kind, cfg, block_size=8, pool_blocks=4,
                          itemsize=4)(positions, slot_ids, tables, [1], 1)
    assert {n: len(v) for n, v in counts.items()} == {
        c.name: max(1, len(c.kinds)) for c in kind.counters}
    if "nxd_paged_columns_total" in counts:
        assert list(counts["nxd_paged_columns_total"]) == [16, 2]
        assert list(counts["nxd_paged_block_visits_total"]) == [1, 1]
    if "nxd_state_slot_steps_total" in counts:
        assert list(counts["nxd_state_slot_steps_total"]) == [1, 0]
        assert list(counts["nxd_state_resets_total"]) == [1]
    if "nxd_state_bytes_held_total" in counts:
        # one occupied slot's leaves, by what each is counted as, and its
        # one block of 8 positions over the pool's layers
        state, tail = (sum(leaf.slot_bytes(4) for leaf in kind.leaves
                           if leaf.counted_as == name)
                       for name in ("state", "tail"))
        assert state > 0 < tail
        assert list(counts["nxd_state_bytes_held_total"]) == [
            state, tail, kind.pool_layers * 8 * 2 * 2 * 16 * 4]
        # the slot's two rows are one segment: its first row, and one more
        assert list(counts["nxd_state_segment_rows_total"]) == [1, 1]


def test_segment_rows_are_counted_as_step_segments_cuts_them():
    """A decode row, a chunk of three, a pad row between two rows of one
    slot (two segments), and pad rows: by hand, and by ``ops/ssd.py``."""
    from neuronx_distributed_tpu.inference.paging import _count_segment_rows
    from neuronx_distributed_tpu.ops import ssd

    slot_ids = np.array([2, 0, 0, 0, 9, 4, 9, 4, 4, 9], np.int32)
    positions = np.array([20, 0, 1, 2, PAD_POSITION, 7, PAD_POSITION, 8, 9,
                          PAD_POSITION], np.int32)
    assert _count_segment_rows(positions, slot_ids) == (4, 3)
    seg = ssd.step_segments(jnp.asarray(slot_ids), jnp.asarray(positions), 5)
    assert int(seg.count[0]) == 4
    assert int(np.sum(np.asarray(seg.since) > 0)) == 3
    assert _count_segment_rows(positions[4:5], slot_ids[4:5]) == (0, 0)


def test_the_benchmarks_counters_are_declared_and_documented():
    """Read from the files, none edited: every counter a metric of the
    benchmark reads is declared by a kind, by a family, or is one of the
    engine's own three; every counter read or declared has its row in the
    catalog."""
    import glob
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    read = set()

    def collect(node):
        if isinstance(node, dict):
            if isinstance(node.get("counter"), str):
                read.add(node["counter"])
            for value in node.values():
                collect(value)
        elif isinstance(node, list):
            for value in node:
                collect(value)

    for path in [os.path.join(root, "BENCHMARK.json")] + sorted(glob.glob(
            os.path.join(root, "benchmarks", "layer_metrics", "*.json"))):
        with open(path) as f:
            collect(json.load(f))
    assert len(read) >= 16
    by_families = {n for names in DECLARED.values() for n in names}
    assert by_families == set(declared_anywhere())
    assert read <= by_families | {"nxd_engine_rows_total",
                                  "nxd_engine_steps_total",
                                  "nxd_engine_step_wall_seconds_total",
                                  "nxd_engine_stall_cause_seconds_total"}
    with open(os.path.join(root, "docs", "observability.md")) as f:
        catalog = {line.split("`")[1] for line in f
                   if line.startswith("| `nxd_")}
    assert by_families | read <= catalog


def test_a_state_pool_has_the_routed_counts_only_where_declared():
    """``StatePoolCache`` lays out ``moe_counts [kept, dropped,
    elsewhere]`` for a family that declares it and builds no such leaf
    for one that does not: Granite's cache is the leaves it was, to the
    byte, and the bytes an occupied slot holds are counted for both by
    what each leaf is."""
    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.models import granite_hybrid, solar_open2

    def cache_of(cfg):
        return paging.init_serving_cache(
            cfg, num_blocks=6, block_size=8, table_rows=3,
            max_blocks_per_seq=4, dtype=jnp.bfloat16)

    granite, solar = granite_hybrid.tiny_config(), solar_open2.tiny_config()
    assert paging.StatePoolCache.moe_leaf is paging.MOE_KEPT_DROPPED_ELSEWHERE
    plain, counted = cache_of(granite), cache_of(solar)
    assert plain.moe_counts is None and not granite.serving_family(
    ).moe_counts
    assert sorted(jax.tree_util.keystr(path) for path, _ in
                  jax.tree_util.tree_flatten_with_path(plain)[0]) == [
        ".block_tables", ".k", ".lengths", ".pos", ".states['conv']",
        ".states['ssm']", ".v"]
    assert sum(x.nbytes for x in jax.tree_util.tree_leaves(plain)) == (
        2 * 2 * 6 * 8 * 1 * 32 * 2            # K and V, two heads a row
        + 3 * 3 * 8 * 64 * 4 + 3 * 3 * 3 * 80 * 2     # ssm, conv
        + 6 * 8 * 4 + 3 * 4 * 4 + 3 * 4)      # pos, tables, lengths
    assert counted.moe_counts.shape == (3,)
    assert counted.moe_counts.dtype == jnp.int32
    kinds = {leaf.name: leaf.counted_as for cfg in (granite, solar)
             for leaf in cfg.serving_family().cache_kind.leaves}
    assert kinds == {"ssm": "state", "kda": "state", "conv": "tail"}
    (leaf,) = [leaf for leaf in solar.serving_family().cache_kind.leaves
               if leaf.name == "kda"]
    assert leaf.slot_bytes(2) == 5 * 2 * 128 * 128 * 4      # float32 stays
    assert paging.StateLeaf("x", (2,), (3,)).slot_bytes(2) == 12


# ---------------------------------------------------------------------------
# A window-pool cache whose two pools have unlike rows
# ---------------------------------------------------------------------------

def test_a_window_pool_of_two_head_counts_and_two_row_widths():
    """The full layers' pool holds 2 K/V heads a position and the rings 4,
    the keys 192 values a head in whole lanes beside values of 128: the
    leaves' shapes, a ring's write indices (they follow the slot and the
    position, not the rows) and the bytes ``count_step`` counts by pool
    and operand. A family of one head count and one head size has both
    operands by head in both pools."""
    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.models import laguna, mimo_v2

    bs, rows = 4, 3
    cfg = mimo_v2.tiny_config(dtype=jnp.float32)
    kind = cfg.serving_family().cache_kind
    assert (kind.full_rows, kind.window_rows) == ((2, 192, 128),
                                                  (4, 192, 128))
    cache = paging.init_serving_cache(
        cfg, num_blocks=10, block_size=bs, table_rows=rows,
        max_blocks_per_seq=6, dtype=jnp.float32)
    ring = kind.window_ring(bs)
    assert ring == 3 and cache.window_ring == ring
    assert cache.k.shape == (2, 10, bs, 2 * 192)
    assert cache.v.shape == (2, 10, bs, 2, 128)
    assert cache.wk.shape == (3, rows * ring, bs, 4 * 192)
    assert cache.wv.shape == (3, rows * ring, bs, 4, 128)
    assert (cache.num_blocks, cache.capacity) == (10, 40)
    # position 13 of slot 2 lies in ring column 3 % 3 = 0, row 1
    tables, flat = paging.ring_write_indices(
        jnp.asarray([2, 0, 5]), jnp.asarray([13, 6, PAD_POSITION]), bs,
        ring, rows)
    assert tables.tolist() == [[6, 7, 8], [0, 1, 2], [-1, -1, -1]]
    assert flat.tolist() == [6 * bs + 1, 1 * bs + 2, rows * ring * bs]
    # a row of 4 heads of 192 lands whole in its ring row's lanes
    k = jnp.arange(4 * 192, dtype=jnp.float32).reshape(1, 4, 192)
    wk = paging.write_pool_rows(cache.wk, pa.keys_to_lanes(k), flat[:1], 1)
    got = pa.keys_of_lanes(wk[1, 6, 1], 4, 192)
    assert (np.asarray(got) == np.asarray(k[0])).all()
    assert float(jnp.abs(wk).sum()) == float(jnp.abs(k).sum())

    # two occupied slots hold 5 and 2 blocks of the full pool; rows at
    # positions 17 (slot 0), 5 and 9,000 (slot 1; its table is short: the
    # host's counts follow the positions)
    positions = np.array([17, 5, 9000, PAD_POSITION], np.int32)
    slot_ids = np.array([0, 1, 1, rows], np.int32)
    tables = np.full((rows, 6), -1, np.int32)
    tables[0, :5], tables[1, :2] = np.arange(5), [5, 6]
    counts = step_counter(kind, cfg, block_size=bs, pool_blocks=10,
                          itemsize=2)(positions, slot_ids, tables, [5, 2], 0)
    full, window = 2 * (5 + 2), 3 * (3 + 2)       # layers x blocks held
    assert list(counts["nxd_kv_blocks_held_total"]) == [full, window]
    assert list(counts["nxd_kv_bytes_held_total"]) == [
        full * bs * 2 * 192 * 2, full * bs * 2 * 128 * 2,
        window * bs * 4 * 192 * 2, window * bs * 4 * 128 * 2]
    assert list(counts["nxd_step_rows_by_context_total"]) == [2, 0, 1]

    one = laguna.tiny_config(dtype=jnp.float32)
    plain = one.serving_family().cache_kind
    assert plain.full_rows == plain.window_rows == (2, 16, 16)
    cache = paging.init_serving_cache(
        one, num_blocks=10, block_size=bs, table_rows=rows,
        max_blocks_per_seq=6, dtype=jnp.float32)
    assert cache.k.shape == cache.v.shape == (2, 10, bs, 2, 16)
    assert cache.wk.shape == cache.wv.shape == (3, rows * 3, bs, 2, 16)
    counts = step_counter(plain, one, block_size=bs, pool_blocks=10,
                          itemsize=4)(positions, slot_ids, tables, [5, 2], 0)
    assert list(counts["nxd_kv_bytes_held_total"]) == [
        n * bs * 2 * 16 * 4 for n in (full, full, window, window)]
