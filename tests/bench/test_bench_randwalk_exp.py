"""RANDWALK-EXP's generator and strata (``bench/datasets/randwalk_exp.py``)
and the cell ``s9.batch`` at a size a test holds: the source's
distributions at the paper's size, the same work on every seed, strata of
walks of like work, and ``correct`` true for the program and false for the
control."""
import hashlib
import math
import types

import jax
import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells, datagen, harness
from bench.traffic import closed_sets
from test_bench_correct import _control_in_place

RW = cells.dataset("randwalk_exp")
BIG = 2 ** 33 + 17                       # seeds beyond 32 signed bits


@pytest.fixture(scope="module")
def full():
    """The paper's size: 10,000 walks, drawn from a large seed."""
    return RW.generate(BIG)


def _same(a, b):
    return (all(np.array_equal(a.cols[c], b.cols[c])
                for c in datagen.COLUMNS)
            and np.array_equal(a.offsets, b.offsets))


def test_same_seed_same_data_other_seed_other_data():
    assert _same(RW.generate(BIG, num_traj=30), RW.generate(BIG, num_traj=30))
    assert not _same(RW.generate(BIG, num_traj=30),
                     RW.generate(BIG + 1, num_traj=30))


def test_digest_of_a_small_draw():
    """Pinned, so that a change to how the draws are made shows."""
    data = RW.generate(BIG, num_traj=40)
    h = hashlib.sha256()
    for a in (*(data.cols[c] for c in datagen.COLUMNS), data.traj_id,
              data.seg_id, data.offsets):
        h.update(np.ascontiguousarray(a).tobytes())
    assert (h.hexdigest()[:16], len(data)) == ("c41a7cdae1f2c417", 2758)


def test_walks_are_joined_unit_steps(full):
    c, k = full.cols, full.offsets
    # float32 times up to about 670: one step to within their rounding
    np.testing.assert_allclose(c["te"] - c["ts"], 1.0, atol=1e-4)
    inner = np.setdiff1d(np.arange(len(full) - 1), k[1:] - 1)
    for a, b in (("xe", "xs"), ("ye", "ys"), ("ze", "zs"), ("te", "ts")):
        np.testing.assert_array_equal(c[a][inner], c[b][inner + 1])
    np.testing.assert_allclose(c["ts"] - c["ts"][k[:-1]][full.traj_id],
                               full.seg_id, atol=1e-4)


def test_the_sources_distributions_at_full_size(full):
    """Exp(mean 70) lengths clipped to [2, 1000] and truncated to whole
    steps; U(0, 20) starts; U(0, 400)^3 first points; N(0, 2) steps.  The
    stratified draws hold the means far tighter than a plain draw, whose
    sampling error the tolerances still allow."""
    lengths = np.diff(full.offsets)
    assert full.num_traj == 10_000
    assert lengths.min() >= 2 and lengths.max() <= 1000
    # E[floor(X)] = 1 / (e^(1/70) - 1) = 69.501; clipping adds 0.042.
    assert lengths.mean() == pytest.approx(69.543, rel=0.01)
    assert len(full) == pytest.approx(695_430, rel=0.01)
    # P(X < 3): the walks clipped up to, or truncated to, 2 steps.
    assert np.mean(lengths == 2) == pytest.approx(1 - math.exp(-3 / 70),
                                                  abs=0.005)
    starts = full.cols["ts"][full.offsets[:-1]]
    assert starts.min() >= 0 and starts.max() < 20
    assert starts.mean() == pytest.approx(10.0, abs=0.1)
    assert np.all(np.abs(np.histogram(starts, 20, (0, 20))[0] - 500) <= 50)
    first = np.stack([full.cols[c][full.offsets[:-1]]
                      for c in ("xs", "ys", "zs")])
    assert first.min() >= 0 and first.max() <= 400
    assert first.mean() == pytest.approx(200.0, rel=0.02)
    step = full.cols["xe"] - full.cols["xs"]
    assert step.mean() == pytest.approx(0.0, abs=0.02)
    assert step.std() == pytest.approx(2.0, rel=0.01)


def test_every_seed_holds_the_same_work():
    """The stratified lengths and starts hold the dataset's pair count
    within 1 % from seed to seed (about 0.01 % as drawn)."""
    totals = [RW.work(RW.generate(s)).sum() for s in (BIG, BIG + 1, 5)]
    assert max(totals) / min(totals) - 1 < 0.01


def test_work_is_the_pair_count_of_each_walk():
    data = RW.generate(BIG, num_traj=25)
    ts, te = data.cols["ts"], data.cols["te"]
    pairs = ((ts[:, None] <= te[None, :])
             & (te[:, None] >= ts[None, :])).sum(axis=1)
    np.testing.assert_array_equal(
        RW.work(data), np.add.reduceat(pairs, data.offsets[:-1]))


def test_strata_hold_walks_of_like_work(full):
    groups = RW.strata(full, 1000)
    work = RW.work(full)
    assert [len(g) for g in groups] == [10] * 1000
    assert np.array_equal(np.sort(np.concatenate(groups)),
                          np.arange(full.num_traj))
    tops = np.array([work[g].max() for g in groups])
    bottoms = np.array([work[g].min() for g in groups])
    assert np.all(tops[:-1] <= bottoms[1:])


def test_s9_sets_carry_the_same_work_on_every_seed():
    """``closed_sets``'s sets of the cell ``s9.batch`` at its own size:
    one walk of each of 500 strata, so every set of every seed holds the
    same pairs within 1 % (a plain draw of 500 walks spreads by several
    %)."""
    cell = cells.load_cell("s9.batch")
    assert cell.config["query_set_trajectories"] == 500
    totals = []
    for seed in (BIG, BIG + 1):
        data = datagen.make(cell.config, seed)
        ctx = types.SimpleNamespace(cell=cell, seed=seed, data=data,
                                    segments=lambda rows: rows)
        work = RW.work(data)
        totals += [work[c].sum() for c in
                   closed_sets.prepare(ctx, 1.0)["comps"]]
    assert len(totals) == 2 * cell.params["sets"]
    assert max(totals) / min(totals) - 1 < 0.01


def _tiny_s9():
    """``s9.batch`` at a size a CPU test run holds, on the XLA path, as
    ``_bench_tiny.shrink`` does for GALAXY's cells."""
    cell = cells.load_cell("s9.batch")
    cell.config["dataset"].update(num_traj=60)
    cell.config.update(query_set_trajectories=3, backend="jnp")
    cell.params.update(sets=2)
    return cell


def test_tiny_s9_run_is_correct_with_the_ladder():
    cell = _tiny_s9()
    assert cell.config["policy"]["dispatch_ladder"]
    out = harness.run(cell, BIG, 1.0, False, jax.devices())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_tiny_s9_control_is_not_correct(monkeypatch):
    _control_in_place(monkeypatch)
    out = harness.run(_tiny_s9(), BIG, 0.5, False, jax.devices())
    assert not out["correct"], out["checks"]
