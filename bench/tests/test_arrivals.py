"""Every seed gets the same work: the same number of tasks, and in
every block the same set of gaps and sizes."""
import numpy as np
import pytest

from bench import arrivals


@pytest.mark.parametrize("block_s", [0.5, 1.0])
def test_same_work_for_every_seed(block_s):
    runs = [arrivals.open_loop(12.0, 25.0, 0.0025, 0.01,
                               np.random.default_rng(s), block_s)
            for s in (1, 2, 2**40 + 3)]
    sizes = [np.sort(f) for _, f in runs]
    gaps = [np.sort(np.diff(t)) for t, _ in runs]
    assert all(t.size == 300 for t, _ in runs)
    assert all(np.all((t >= 0) & (t < 25.0)) for t, _ in runs)
    assert all(np.all(np.diff(t) > 0) for t, _ in runs)
    np.testing.assert_allclose(sizes[0], sizes[1])
    np.testing.assert_allclose(sizes[0], sizes[2])
    assert not np.array_equal(runs[0][0], runs[1][0])
    # the same set of gaps; only the one after the last task differs
    for g in gaps[1:]:
        assert np.isin(g.round(12), gaps[0].round(12)).sum() >= g.size - 1


def test_blocks_carry_the_same_tasks_and_work():
    t, f = arrivals.open_loop(12.0, 25.0, 0.0025, 0.01,
                              np.random.default_rng(5), 0.5)
    block = np.floor(t / 0.5 + 1e-9).astype(int)
    assert np.all(np.bincount(block) == 6)
    per_block = [np.sort(f[block == b]) for b in range(50)]
    for b in per_block[1:]:
        np.testing.assert_allclose(b, per_block[0])
    # the gaps keep the exponential law's mean
    assert abs(np.diff(t).mean() - 1 / 12.0) < 2e-3


def test_only_whole_blocks_are_kept():
    """At 11.2 tasks/s a block of 6 spans 0.536 s; the 93 that end
    inside 50 s are kept for every seed, and no task falls past them."""
    for s in (3, 4, 2**33 + 5):
        t, f = arrivals.open_loop(11.2, 50.0, 0.0025, 0.01,
                                  np.random.default_rng(s), 0.5)
        assert t.size == f.size == 558
        assert t[-1] < 93 * 6 / 11.2 <= 50.0
