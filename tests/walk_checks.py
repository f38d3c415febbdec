"""What every walk of the paged kernels must hold, whatever the cache kind:
shared by ``test_paging.py`` (a full cache), ``test_evabyte.py`` (a
window-summary cache), ``test_prefix_sharing.py`` and
``test_minicpm_sala.py`` (a sparse-state cache, whose walk is a layer's
selection)."""

import numpy as np

from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION


def narrow_group(n_rep, slot_rows=1):
    """Stacked rows of the kernel's narrow product, from ``n_rep`` alone:
    a packed row's ``n_rep`` heads begin a multiple of ``gcd(n_rep, 8)``
    past a whole sublane, and the group is the whole sublanes that hold
    them from the furthest such offset (1, 4, 8 -> 8; 6, 9, 10 -> 16).
    Where a slot packs ``slot_rows`` rows side by side and their heads
    are whole sublanes, the group is all of them."""
    if slot_rows > 1 and slot_rows * n_rep % 8 == 0:
        return slot_rows * n_rep
    furthest = max(r * n_rep % 8 for r in range(8))
    return -(-(furthest + n_rep) // 8) * 8


def group_start(first, last, n_rep, wide, slot_rows=1):
    """Where the narrow product of a pair begins that the stacked rows
    ``first`` to ``last`` (one past) of a tile of ``wide`` name, -1 over
    the whole tile, by the rule in words: a slot's group begins on the
    multiple of its height that ``first`` lies in and is the pair's if it
    holds ``last`` and lies in the tile; any other group begins on
    ``first``'s sublane, or where the tile's last group does if that is
    earlier."""
    group = narrow_group(n_rep, slot_rows)
    if group == slot_rows * n_rep and slot_rows > 1:
        begin = first // group * group
        return begin if last <= begin + group <= wide else -1
    begin = min(first // 8 * 8, wide - group)
    return begin if last <= begin + group else -1


def check_tile_walk(walk, live, tables, rows, n_rep, runs=None,
                    shared_units=None, slot_rows=1):
    """What every walk of the kernel must hold (a full cache's and a
    window-summary cache's alike, ``tests/test_evabyte.py``): each live
    (row, column) is served by exactly one pair of its tile, no pair is
    listed twice, a tile's count is the brute count of its distinct
    pairs, and a pair is narrow exactly if one group of rows holds the
    heads that name it: :func:`narrow_group` rows from the whole sublane
    the first of them lies in (from the tile's last group, if that is
    earlier), so every pair that one packed row names is narrow; with
    ``slot_rows``, the slot's group that holds the first of them
    (:func:`group_start`).
    ``runs``, a kernel's cut of this walk into units (``(run_walk, run,
    whole_run)``), is held to :func:`check_pair_runs`,
    whose count of the fetches by kind is returned (``shared_units``: its
    tally of the shared pairs by their unit)."""
    t, maxb = tables.shape
    tiles = len(walk.count)
    per = walk.blocks.size // tiles
    assert per == rows * maxb and tiles * rows >= t
    group = narrow_group(n_rep, slot_rows)
    for i in range(tiles):
        mine = range(i * rows, min((i + 1) * rows, t))
        want = {}           # (column, block) -> the tile's rows that name it
        for r in mine:
            for c in np.flatnonzero(live[r]):
                want.setdefault((int(c), int(tables[r, c])), []).append(r)
        n = int(walk.count[i])
        got = list(zip(walk.cols[i * per:i * per + n].tolist(),
                       walk.blocks[i * per:i * per + n].tolist()))
        assert len(set(got)) == len(got) == n          # none twice
        assert set(got) == set(want)                   # each served, once
        for j, pair in enumerate(got):
            first = (want[pair][0] - i * rows) * n_rep
            last = (want[pair][-1] - i * rows + 1) * n_rep
            start = int(walk.narrow[i * per + j])
            assert start == group_start(first, last, n_rep, rows * n_rep,
                                        slot_rows)
            if start >= 0:
                assert start % 8 == 0
            else:
                # several rows' pair, or a slot's group that the tile's
                # end cuts
                assert len(want[pair]) > 1 or (
                    first // group * group + group > rows * n_rep)
        # what the kernel masks by: a row's entry where it attends
        served = walk.served[i].reshape(rows, n_rep, maxb)
        assert (served == served[:, :1]).all()
        for r in mine:
            np.testing.assert_array_equal(
                served[r - i * rows, 0], np.where(live[r], tables[r], -1))
    if runs is not None:
        return check_pair_runs(walk, live, tables, rows, n_rep, *runs,
                               shared_units=shared_units,
                               slot_rows=slot_rows)


def check_pair_runs(walk, live, tables, rows, n_rep, runs, run, whole_run,
                    shared_units=None, slot_rows=1):
    """What :func:`..ops.paged_attention.pair_runs` must hold of a tile
    walk, by brute count: following a tile's units from its first pair,
    every pair of the walk (so every live (row, column)) lies in exactly
    one unit, no block is in two, a unit is no longer than its kind may
    be and holds either pairs of one narrow group (the walk's own
    ``narrow`` for each of them, the group's first row: the rows that
    name them have their heads inside it; one packed row under 8 or 16
    heads, the two that share a sublane under 4, the neighbours whose
    groups begin on one under 6 and 9, a slot's ``slot_rows`` rows on a
    multiple of their heads), in order of column and block, or
    pairs that no group holds; a group's units are full but its last;
    and ``lens`` is 0 off a unit's first pair. Returns the walk's fetches
    by kind, ``[in_run, alone, whole]``, which the caller holds the
    host's count to (:func:`..ops.paged_attention.block_fetches`, NumPy
    over the tables themselves). ``shared_units``, an array of two, has
    the shared pairs added by the unit ``lens`` gives them, ``[in_unit,
    alone]``: what the latent cache's ``nxd_mla_shared_blocks_total``
    counts on the host (:func:`..ops.mla_attention.shared_blocks`); a
    tile's shared units are full but its last."""
    t, maxb = tables.shape
    tiles = len(walk.count)
    per = rows * maxb
    group = narrow_group(n_rep, slot_rows)
    units, blocks, cols, narrow, lens = (np.asarray(x) for x in runs[:5])
    kinds = np.zeros((3,), np.int64)        # in_run, alone, whole
    for i in range(tiles):
        mine = range(i * rows, min((i + 1) * rows, t))
        want = {}           # (column, block) -> the tile's rows that name it
        for r in mine:
            for c in np.flatnonzero(live[r]):
                want.setdefault((int(c), int(tables[r, c])), []).append(r)
        # where the tile walk has each pair run
        count = int(walk.count[i])
        at_walk = dict(zip(
            zip(walk.cols[i * per:i * per + count].tolist(),
                walk.blocks[i * per:i * per + count].tolist()),
            walk.narrow[i * per:i * per + count].tolist()))
        seen, heads_at, first = [], set(), 0
        last_of = {}                # a group's units, in order
        for _ in range(int(units[i])):
            at = i * per + first
            n = int(lens[at])
            heads_at.add(first)
            pairs = list(zip(cols[at:at + n].tolist(),
                             blocks[at:at + n].tolist()))
            starts = set(narrow[at:at + n].tolist())
            assert len(starts) == 1     # one group's pairs, or shared ones
            start = starts.pop()
            assert all(at_walk[p] == start for p in pairs)
            kinds[2 if start < 0 else int(n == 1)] += n
            if start < 0:
                assert 1 <= n <= whole_run
                assert all(len(want[p]) > 1 for p in pairs)
                # the shared pairs come first, in full units but the last
                assert not last_of and (n == whole_run or first + n == sum(
                    s < 0 for s in at_walk.values()))
                if shared_units is not None:
                    shared_units[int(n == 1)] += n
            else:
                assert 1 <= n <= run and start % 8 == 0
                if slot_rows > 1 and group == slot_rows * n_rep:
                    assert start % group == 0
                for p in pairs:         # the group holds its namers' heads
                    assert start <= (want[p][0] - i * rows) * n_rep
                    assert ((want[p][-1] - i * rows + 1) * n_rep
                            <= start + group)
                assert pairs == sorted(pairs)
                if start in last_of:    # after its group's earlier pairs
                    assert last_of[start][-1][1] < pairs[0]
                    assert last_of[start][-1][0] == run  # which were full
                last_of.setdefault(start, []).append((n, pairs[-1]))
            seen += pairs
            first += n
        assert first == count
        assert len(set(seen)) == len(seen) and set(seen) == set(want)
        off = np.setdiff1d(np.arange(per), sorted(heads_at))
        assert (lens[i * per + off] == 0).all()
    return kinds


def check_paged_runs(tables, q_pos, live, block_size, num_blocks, n_rep, run,
                     window=None, sliding=None, slot_rows=1):
    """The paged kernel's walk of a step in runs of ``run``: the tile walk
    and its cut into units hold (:func:`check_tile_walk`,
    :func:`check_pair_runs`), and the host's count of the fetches by kind
    is the walk's (``slot_rows``: a slot's rows are one group, in the
    walk and in the host's count alike). Returns ``[in_run, alone,
    whole]``."""
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops import paged_attention as pa

    tables, q_pos = np.asarray(tables), np.asarray(q_pos)
    rows = pa.tile_rows(n_rep, len(q_pos))
    walk = pa.tile_walk(jnp.asarray(tables, jnp.int32),
                        jnp.asarray(q_pos, jnp.int32), block_size,
                        num_blocks, n_rep, window, sliding, slot_rows)
    runs = pa.run_walk(walk, num_blocks, n_rep, run, 1, slot_rows=slot_rows)
    assert runs.q_lo is walk.q_lo and runs.served is walk.served
    kinds = check_tile_walk(
        type(walk)(*(None if x is None else np.asarray(x) for x in walk)),
        live, tables, rows, n_rep, runs=(runs, run, 1), slot_rows=slot_rows)
    fetches = pa.block_fetches(np.where(live, tables, -1), n_rep, run,
                               slot_rows=slot_rows)
    assert tuple(fetches) == tuple(kinds)
    assert fetches.sum() == int(np.asarray(walk.count).sum())
    return kinds


def check_sparse_walk(walk, parts, tables, rows, part_rows, width, per):
    """What :func:`..ops.sparse_attention.sparse_tile_walk` must hold, by
    brute count: following a (tile, group)'s lists from ``start`` through
    ``after``, every live (row, group, column) is served by exactly one
    pair of its tile (the pair of the column and the row's own table
    entry), no pair is listed twice, a row lists only pairs no earlier row
    of the tile attends, in order of column, ``total`` is the count of
    the tile's distinct pairs, a pair is narrow exactly if every row that
    attends it lies in its lister's part, and ``keys`` hold what each row
    attends of each column. Returns the pairs counted."""
    parts, tables = np.asarray(parts), np.asarray(tables)
    t, groups, maxb = parts.shape
    walk = type(walk)(*(np.asarray(x) for x in walk))
    tiles = walk.keys.shape[0]
    assert tiles * rows >= t and walk.keys.shape == (tiles, groups, rows,
                                                     maxb)
    fetched = 0
    for i in range(tiles):
        mine = range(i * rows, min((i + 1) * rows, t))
        for g in range(groups):
            want = {}       # (column, block) -> the tile's rows that name it
            for r in mine:
                for c in np.flatnonzero(parts[r, g]):
                    want.setdefault((int(c), int(tables[r, c])),
                                    []).append(r - i * rows)
            got = {}
            r, seen = int(walk.start[i * groups + g]), 0
            while r < rows:
                at = (i * rows + r) * groups + g
                n = int(walk.count[at])
                assert 0 < n <= width
                cols = []
                for k in range(n):
                    mark = int(walk.marks[at * width + k])
                    pair = (mark >> 1, int(walk.blocks[at * width + k]))
                    assert pair not in got                  # none twice
                    got[pair] = (r, mark & 1)
                    cols.append(pair[0])
                assert cols == sorted(cols)
                seen += n
                assert int(walk.after[at]) > r
                r = int(walk.after[at])
            assert r == rows
            assert set(got) == set(want)                # each served, once
            assert seen == int(walk.total[i * groups + g]) == len(want)
            for pair, (lister, narrow) in got.items():
                namers = want[pair]
                assert lister == namers[0]              # its first namer
                end = (lister // part_rows + 1) * part_rows
                assert narrow == int(namers[-1] < end)
            for r in range(rows):
                row = i * rows + r
                for c in range(maxb):
                    live = row < t and parts[row, g, c] > 0
                    assert int(walk.keys[i, g, r, c]) == (
                        (int(tables[row, c]) << per | int(parts[row, g, c]))
                        if live else 0)
            fetched += len(want)
    return fetched


def scored_pairs(tables, q_pos, spec, block_size, rows):
    """By loops over a step's rows: for every tile of ``rows`` rows, the
    (column, block) pairs whose compressed keys the selection reads (a
    real row at or past ``dense_len``, a mapped column whose first
    kernel is whole at the row's position) -> the rows of the tile that
    name each. A list of dicts, one a tile."""
    tables, q_pos = np.asarray(tables), np.asarray(q_pos)
    tiles = [{} for _ in range(-(-len(q_pos) // rows))]
    for r, p in enumerate(q_pos):
        if p >= PAD_POSITION or p < spec.dense_len:
            continue
        for c, b in enumerate(tables[r]):
            if b >= 0 and c * block_size + spec.kernel - 1 <= p:
                tiles[r // rows].setdefault((c, int(b)), []).append(
                    r % rows)
    return tiles


def check_score_walk(walk, tables, q_pos, spec, block_size, rows, part_rows,
                     ucols):
    """What :func:`..ops.sparse_attention.score_walk` must hold, by brute
    count: a unit's layers list every scored pair of the unit's columns
    once and nothing else, a column's in the order of their first namers
    (those that several parts of the tile name first),
    ``depth`` is the most pairs a column of the unit has, ``lanes`` send
    every scoring (row, column) to the layer that holds its own block and
    every other to none, and a layer is narrow exactly if one part of the
    tile holds all its namers. Returns ``(fetched, shared)``."""
    want = scored_pairs(tables, q_pos, spec, block_size, rows)
    maxb = np.asarray(tables).shape[1]
    per = block_size // spec.stride
    units = -(-maxb // ucols)
    depth = np.asarray(walk.depth).reshape(len(want), units)
    blocks = np.asarray(walk.blocks).reshape(len(want), units, rows, ucols)
    narrow = np.asarray(walk.narrow).reshape(len(want), units, rows)
    lanes = np.asarray(walk.lanes)
    assert lanes.shape == (len(want), rows, units * ucols * per)
    fetched = shared = 0
    for i, pairs in enumerate(want):
        fetched += len(pairs)
        shared += sum(len(v) for v in pairs.values()) - len(pairs)
        serves = np.full((rows, units * ucols), -1)
        for u in range(units):
            by_col = {}
            for (c, b), namers in pairs.items():
                if c // ucols == u:
                    lone = len({r // part_rows for r in namers}) == 1
                    by_col.setdefault(c, []).append((lone, namers[0], b,
                                                     namers))
            assert depth[i, u] == max(map(len, by_col.values()), default=0)
            for j in range(rows):
                namers = []
                for k in range(ucols):
                    mine = sorted(by_col.get(u * ucols + k, []))
                    if j < len(mine):
                        assert blocks[i, u, j, k] == mine[j][2]
                        namers += mine[j][3]
                        serves[mine[j][3], u * ucols + k] = j
                    else:
                        assert blocks[i, u, j, k] == -1
                if j < depth[i, u]:
                    where = {r // part_rows for r in namers}
                    assert narrow[i, u, j] == (where.pop() if len(where) == 1
                                               else -1)
        np.testing.assert_array_equal(lanes[i], np.repeat(serves, per, -1))
    assert np.asarray(walk.visits).tolist() == [fetched, shared]
    return fetched, shared
