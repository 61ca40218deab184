"""The benchmark's dataset generators: deterministic per seed, at the
paper's sizes."""
import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import datagen


def _same(a, b):
    return (all(np.array_equal(a.cols[c], b.cols[c])
                for c in datagen.COLUMNS)
            and np.array_equal(a.traj_id, b.traj_id)
            and np.array_equal(a.seg_id, b.seg_id))


@pytest.mark.parametrize("make", [
    lambda s: datagen.galaxy(s, num_traj=30, num_segments=20),
    lambda s: datagen.galaxy(s, num_traj=3, num_segments=200),
])
def test_same_seed_same_data_other_seed_other_data(make):
    big = 2 ** 33 + 17                   # seeds beyond 32 signed bits
    assert _same(make(big), make(big))
    assert not _same(make(big), make(big + 1))


def test_galaxy_at_the_papers_size():
    data = datagen.galaxy(3)
    assert len(data) == 10 ** 6 and data.num_traj == 2500
    assert np.all(data.cols["te"] - data.cols["ts"] == 1.0)
    assert data.cols["ts"].min() == 0.0 and data.cols["te"].max() == 400.0
    rows = data.rows_of([7])
    assert np.all(data.traj_id[rows] == 7)
    assert np.array_equal(data.seg_id[rows], np.arange(400))
    # Consecutive segments of a trajectory join end to start.
    assert np.array_equal(data.cols["xe"][rows[:-1]],
                          data.cols["xs"][rows[1:]])


def test_every_seed_has_the_same_time_grid():
    a, b = datagen.galaxy(1, num_traj=50), datagen.galaxy(2, num_traj=50)
    # Same segment time extents, so every seed plans the same batch
    # shapes; different orbits.
    assert np.array_equal(a.cols["ts"], b.cols["ts"])
    assert np.array_equal(a.cols["te"], b.cols["te"])
    assert not np.array_equal(a.cols["xs"], b.cols["xs"])


def test_make_reads_the_configuration():
    cfg = {"dataset": {"generator": "galaxy", "num_traj": 5,
                       "num_segments": 4}}
    data = datagen.make(cfg, 9)
    assert len(data) == 20 and _same(data, datagen.galaxy(
        9, num_traj=5, num_segments=4))


def test_stratified_draws_one_value_in_each_stratum():
    u = datagen.stratified(np.random.default_rng(2 ** 33 + 5), 4.0, 12.0, 50)
    assert np.all((u >= 4.0) & (u < 12.0))
    assert np.array_equal(np.sort(np.floor((u - 4.0) / 8.0 * 50)),
                          np.arange(50))


def test_every_seed_has_the_same_radii_per_stratum():
    def radii(seed):
        data = datagen.galaxy(seed, num_traj=40, num_segments=8)
        r = np.hypot(data.cols["xs"], data.cols["ys"]).reshape(40, 8)
        return np.sort(r.mean(axis=1))

    # r0 is stratified: the k-th smallest star of any seed lies within a
    # stratum (0.2) and the epicycle (0.6) of any other's.
    assert np.all(np.abs(radii(1) - radii(2)) < 1.4)
