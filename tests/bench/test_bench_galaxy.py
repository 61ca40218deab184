"""GALAXY's generator and strata (``bench/datasets/galaxy.py``):
deterministic per seed, at the paper's sizes, and the same bytes and query
sets as before the generator became a module of its own."""
import hashlib
import types

import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells, datagen
from bench.traffic import closed_sets

GALAXY = cells.dataset("galaxy")


def _same(a, b):
    return (all(np.array_equal(a.cols[c], b.cols[c])
                for c in datagen.COLUMNS)
            and np.array_equal(a.traj_id, b.traj_id)
            and np.array_equal(a.seg_id, b.seg_id))


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("make", [
    lambda s: GALAXY.generate(s, num_traj=30, num_segments=20),
    lambda s: GALAXY.generate(s, num_traj=3, num_segments=200),
])
def test_same_seed_same_data_other_seed_other_data(make):
    big = 2 ** 33 + 17                   # seeds beyond 32 signed bits
    assert _same(make(big), make(big))
    assert not _same(make(big), make(big + 1))


#: Digests of the columns, ids and offsets, taken from ``datagen.galaxy``
#: before it moved here.
@pytest.mark.parametrize("size,want", [
    ({"num_traj": 30, "num_segments": 20}, "d9095d76c39dae7f"),
    ({"num_traj": 3, "num_segments": 200}, "b72863f4617b1865"),
])
def test_same_bytes_as_before_the_move(size, want):
    data = GALAXY.generate(2 ** 33 + 17, **size)
    assert _digest(*(data.cols[c] for c in datagen.COLUMNS), data.traj_id,
                   data.seg_id, data.offsets) == want


def test_s2_batch_draws_the_same_sets_as_before_the_move():
    """``closed_sets.prepare``'s sets of the cell ``s2.batch`` at its own
    size, against a digest taken before the strata moved here."""
    seed = 2 ** 33 + 3
    cell = cells.load_cell("s2.batch")
    ctx = types.SimpleNamespace(cell=cell, seed=seed,
                                data=datagen.make(cell.config, seed),
                                segments=lambda rows: rows)
    comps = closed_sets.prepare(ctx, 1.0)["comps"]
    assert [len(c) for c in comps] == [10] * 4
    assert _digest(*(np.asarray(c, np.int64) for c in comps)) == (
        "492e485440a33a44")


def test_galaxy_at_the_papers_size():
    data = GALAXY.generate(3)
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
    a = GALAXY.generate(1, num_traj=50)
    b = GALAXY.generate(2, num_traj=50)
    # Same segment time extents, so every seed plans the same batch
    # shapes; different orbits.
    assert np.array_equal(a.cols["ts"], b.cols["ts"])
    assert np.array_equal(a.cols["te"], b.cols["te"])
    assert not np.array_equal(a.cols["xs"], b.cols["xs"])


def test_every_seed_has_the_same_radii_per_stratum():
    def radii(seed):
        data = GALAXY.generate(seed, num_traj=40, num_segments=8)
        r = np.hypot(data.cols["xs"], data.cols["ys"]).reshape(40, 8)
        return np.sort(r.mean(axis=1))

    # r0 is stratified: the k-th smallest star of any seed lies within a
    # stratum (0.2) and the epicycle (0.6) of any other's.
    assert np.all(np.abs(radii(1) - radii(2)) < 1.4)


def test_strata_split_every_trajectory_by_distance():
    data = GALAXY.generate(7, num_traj=42, num_segments=6)
    groups = GALAXY.strata(data, 4)
    assert [len(g) for g in groups] == [11, 11, 10, 10]
    assert np.array_equal(np.sort(np.concatenate(groups)), np.arange(42))
    r = np.hypot(data.cols["xs"], data.cols["ys"]).reshape(42, 6).mean(1)
    assert r[groups[0]].max() < r[groups[-1]].min()
