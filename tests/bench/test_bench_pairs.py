"""The work count and the plain reference against direct counts."""
import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import datagen, reference, roofline


def _random(seed, n, t_span=30.0):
    rng = np.random.default_rng(seed)
    ts = rng.uniform(0.0, t_span, n)
    te = ts + rng.uniform(0.0, 3.0, n)
    te[:3] = ts[:3]                        # zero-length extents
    p0 = rng.uniform(0.0, 10.0, (n, 3))
    p1 = p0 + rng.normal(0.0, 2.0, (n, 3))
    cols = {"xs": p0[:, 0], "ys": p0[:, 1], "zs": p0[:, 2],
            "xe": p1[:, 0], "ye": p1[:, 1], "ze": p1[:, 2],
            "ts": ts, "te": te}
    return {k: v.astype(np.float32) for k, v in cols.items()}


def _dataset(cols):
    n = len(cols["ts"])
    return datagen.Dataset("random", cols, np.arange(n, dtype=np.int32),
                           np.zeros(n, np.int32),
                           np.arange(n + 1, dtype=np.int64))


def _overlaps(e, q):
    return ((e["ts"][:, None] <= q["te"][None, :])
            & (e["te"][:, None] >= q["ts"][None, :]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_overlap_count_is_the_direct_count(seed):
    e, q = _random(seed, 300), _random(seed + 100, 40)
    idx = reference.EntryIndex(_dataset(e))
    assert reference.overlap_count(idx, q["ts"], q["te"]) == int(
        _overlaps(e, q).sum())


@pytest.mark.parametrize("seed", [0, 1])
def test_entries_touched_is_the_direct_count(seed):
    e, q = _random(seed, 300), _random(seed + 7, 5)
    got = roofline.entries_touched(e["ts"], e["te"], q["ts"], q["te"])
    assert got == int(_overlaps(e, q).any(axis=1).sum())


def test_ops_per_pair_and_work():
    assert roofline.OPS_PER_PAIR == 40
    ops, nbytes = roofline.work(pairs=10, entries=3, queries=2, hits=4)
    assert ops == 400 and nbytes == 5 * 32 + 4 * 16


def test_peaks_refuse_an_unknown_chip():
    assert roofline.roofline_share(1.0, 1.0, 1.0, "TPU v5 lite") > 0
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.roofline_share(1.0, 1.0, 0.0, "TPU v5 lite") is None


def _direct(e, q, d):
    """One pair at a time, by sampling the separation finely."""
    hits = {}
    for i in range(len(q["ts"])):
        for j in range(len(e["ts"])):
            lo = max(e["ts"][j], q["ts"][i])
            hi = min(e["te"][j], q["te"][i])
            if lo > hi:
                continue
            t = np.linspace(lo, hi, 2001, dtype=np.float64)

            def pos(s, k):
                dur = float(s["te"][k]) - float(s["ts"][k])
                frac = ((t - s["ts"][k]) / dur if dur > 0
                        else np.zeros_like(t))
                return np.stack([s[a][k] + (s[b][k] - s[a][k]) * frac
                                 for a, b in (("xs", "xe"), ("ys", "ye"),
                                              ("zs", "ze"))])
            sep = np.linalg.norm(pos(e, j) - pos(q, i), axis=0)
            inside = t[sep <= d]
            if inside.size:
                hits[(i, j)] = (inside.min(), inside.max())
    return hits


def test_reference_agrees_with_a_direct_evaluation():
    e, q = _random(5, 120), _random(6, 12)
    d = 2.5
    data = _dataset(e)
    truth = reference.reference(reference.EntryIndex(data), q,
                                np.arange(12), d)
    direct = _direct(e, q, d)
    n = len(e["ts"])
    got = {(int(k // n), int(k % n)): (a, b) for k, h, a, b in zip(
        truth.key, truth.hit, truth.t_enter, truth.t_exit) if h}
    # Sampling misses only pairs that graze d between two samples.
    assert set(direct) <= set(got)
    for key, (a, b) in direct.items():
        assert got[key][0] <= a + 1e-9 and got[key][1] >= b - 1e-9
        step = 3.0 / 2000
        assert a - got[key][0] <= step + 1e-6
        assert got[key][1] - b <= step + 1e-6


def test_compare_reads_each_fault():
    e, q = _random(8, 200), _random(9, 20)
    data = _dataset(e)
    idx = reference.EntryIndex(data)
    truth = reference.reference(idx, q, np.arange(20), 3.0)
    hit = truth.hit
    rows = reference.Rows(truth.key[hit], truth.t_enter[hit],
                          truth.t_exit[hit])
    assert reference.compare(truth, rows) == {
        "miss_depth": 0.0, "extra_depth": 0.0, "t_gap": 0.0}
    half = reference.Rows(rows.key[::2], rows.t_enter[::2],
                          rows.t_exit[::2])
    assert reference.compare(truth, half)["miss_depth"] > 0
    moved = reference.Rows(rows.key, rows.t_enter + 0.25, rows.t_exit)
    assert reference.compare(truth, moved)["t_gap"] >= 0.25 - 1e-9
    twice = reference.Rows(np.concatenate([rows.key, rows.key[:1]]),
                           np.concatenate([rows.t_enter, rows.t_enter[:1]]),
                           np.concatenate([rows.t_exit, rows.t_exit[:1]]))
    assert reference.compare(truth, twice)["extra_depth"] >= 1e9
    alien = reference.Rows(np.concatenate([rows.key, [-1]]),
                           np.concatenate([rows.t_enter, [0.0]]),
                           np.concatenate([rows.t_exit, [0.0]]))
    assert reference.compare(truth, alien)["extra_depth"] >= 1e9
