"""The banded attention kernels' block schedule against ``_visible``, the
definition of which position sees which: plain numpy, no device."""

import numpy as np
import pytest

from paddle_tpu.ops.pallas import attention as att

# (T, block, window): the Mellum2 cell's full and window layers; a window
# that is no multiple of the block; one wider than T; one block; a window
# equal to the block
GEOMETRIES = [(8192, 512, 0), (8192, 512, 1024), (1024, 256, 300),
              (512, 128, 4096), (128, 128, 0), (2048, 512, 512)]
GROUP = 3


def _band(T, block, window):
    """The (query block, key block) pairs in which some position sees
    some other, from ``_visible`` over each block's positions."""
    n, pos = T // block, np.arange(block)
    return {(i, j) for i in range(n) for j in range(n)
            if att._visible(i * block + pos[:, None],
                            j * block + pos[None, :], window).any()}


def _runs(sched, key):
    """The schedule cut at its FIRST flags; every run must end in its one
    LAST and keep one accumulator (``key`` of an entry) throughout."""
    starts = np.flatnonzero(sched.flags & att.FIRST)
    assert starts[0] == 0
    runs = np.split(np.arange(len(sched.flags)), starts[1:])
    for run in runs:
        last = np.flatnonzero(sched.flags[run] & att.LAST)
        assert last.tolist() == [len(run) - 1]
        assert len({key(e) for e in run}) == 1
    owners = [key(run[0]) for run in runs]
    assert len(set(owners)) == len(owners)          # contiguous: one run each
    return runs


@pytest.mark.parametrize("T,block,window", GEOMETRIES)
def test_schedule_is_the_band(T, block, window):
    by_query = att.band_schedule(T, block, window)
    pairs = list(zip(by_query.q.tolist(), by_query.k.tolist()))
    assert len(pairs) == len(set(pairs))
    assert set(pairs) == _band(T, block, window)
    assert not by_query.head.any()
    # a query block's keys ascend, so the diagonal comes last
    runs = _runs(by_query, key=lambda e: by_query.q[e])
    for run in runs:
        keys = by_query.k[run]
        assert (np.diff(keys) == 1).all()
        assert keys[-1] == by_query.q[run[0]]
    assert by_query.skipped \
        == (T // block) * max(map(len, runs)) - len(pairs)

    by_key = att.band_schedule(T, block, window, by="key", group=GROUP)
    assert sorted(zip(by_key.q.tolist(), by_key.k.tolist())) \
        == sorted(pairs * GROUP)
    # a key block's run: each head of the group in turn, queries ascending
    for run in _runs(by_key, key=lambda e: by_key.k[e]):
        per_head = len(run) // GROUP
        assert by_key.head[run].tolist() == sorted(
            list(range(GROUP)) * per_head)
        queries = by_key.q[run].reshape(GROUP, per_head)
        assert (queries == queries[0]).all()
        assert (np.diff(queries[0]) == 1).all()
