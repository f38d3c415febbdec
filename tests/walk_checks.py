"""What every walk of the paged kernel must hold, whatever the cache kind:
shared by ``test_paging.py`` (a full cache), ``test_evabyte.py`` (a
window-summary cache) and ``test_prefix_sharing.py``."""

import numpy as np


def check_tile_walk(walk, live, tables, rows, n_rep):
    """What every walk of the kernel must hold (a full cache's and a
    window-summary cache's alike, ``tests/test_evabyte.py``): each live
    (row, column) is served by exactly one pair of its tile, no pair is
    listed twice, a tile's count is the brute count of its distinct
    pairs, and a pair is narrow only if one group of rows names it."""
    t, maxb = tables.shape
    tiles = len(walk.count)
    per = walk.blocks.size // tiles
    assert per == rows * maxb and tiles * rows >= t
    group = -(-n_rep // 8) * 8
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
            if last <= first // group * group + group:
                assert start == first // group * group
            else:
                assert start == -1
        # what the kernel masks by: a row's entry where it attends
        served = walk.served[i].reshape(rows, n_rep, maxb)
        assert (served == served[:, :1]).all()
        for r in mine:
            np.testing.assert_array_equal(
                served[r - i * rows, 0], np.where(live[r], tables[r], -1))
