"""What the dataset generators share (``bench/datagen.py``): a
configuration's generator found by name, as a file of its own, and the
stratified draws."""
import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells, datagen, harness
from bench.traffic import closed_sets


def _same(a, b):
    return (all(np.array_equal(a.cols[c], b.cols[c])
                for c in datagen.COLUMNS)
            and np.array_equal(a.traj_id, b.traj_id)
            and np.array_equal(a.seg_id, b.seg_id))


def test_make_reads_the_configuration():
    cfg = {"dataset": {"generator": "galaxy", "num_traj": 5,
                       "num_segments": 4}}
    data = datagen.make(cfg, 9)
    assert len(data) == 20 and _same(data, cells.dataset("galaxy").generate(
        9, num_traj=5, num_segments=4))


#: A dataset of a new kind, as a later configuration would bring it: one
#: file, nothing else edited.
WALK = '''
import numpy as np

from bench import datagen


def generate(seed, *, num_traj, num_segments):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(num_traj, num_segments + 1, 3)).cumsum(axis=1)
    times = np.tile(np.arange(num_segments + 1.0), num_traj)
    return datagen.from_points("walk", pts.reshape(-1, 3), times,
                               np.full(num_traj, num_segments))


def strata(data, n):
    return np.array_split(np.arange(data.num_traj), n)
'''


def test_a_new_generator_file_builds_through_the_harness(tmp_path,
                                                         monkeypatch):
    (tmp_path / "datasets").mkdir()
    (tmp_path / "datasets" / "walk.py").write_text(WALK)
    monkeypatch.setattr(cells, "BENCH", str(tmp_path))
    config = {"name": "walk", "d": 1.0, "query_set_trajectories": 3,
              "backend": "jnp", "policy": {},
              "dataset": {"generator": "walk", "num_traj": 9,
                          "num_segments": 5}}
    cell = cells.Cell(name="walk.batch", config_name="walk", config=config,
                      traffic="closed_sets", chips=1, params={"sets": 2})
    ctx = harness.build(cell, 2 ** 33 + 1)
    assert len(ctx.data) == len(ctx.db) == 45
    assert ctx.data.name == "walk"
    # The sets are drawn from the new module's strata.
    state = closed_sets.prepare(ctx, 1.0)
    assert [sorted(c // 3) for c in state["comps"]] == [[0, 1, 2]] * 2


def test_an_unknown_generator_is_refused_with_its_path():
    cfg = {"dataset": {"generator": "no_such_generator"}}
    with pytest.raises(KeyError, match=r"datasets/no_such_generator\.py"):
        datagen.make(cfg, 1)


def test_stratified_draws_one_value_in_each_stratum():
    u = datagen.stratified(np.random.default_rng(2 ** 33 + 5), 4.0, 12.0, 50)
    assert np.all((u >= 4.0) & (u < 12.0))
    assert np.array_equal(np.sort(np.floor((u - 4.0) / 8.0 * 50)),
                          np.arange(50))
