"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — smoke tests and
benchmarks must see the real single CPU device; anything that needs the
512-device placeholder topology must force it in its own process."""
import numpy as np
import pytest

from repro.core.segments import SegmentArray


@pytest.fixture(scope="session")
def small_scenario():
    """Scaled-down S1 (GALAXY, d=1): (db, queries, d)."""
    from repro.data import trajgen
    return trajgen.make_scenario("S1", scale=0.01)


def random_segments(rng: np.random.Generator, n: int, *, t_span=(0.0, 50.0),
                    box=30.0, max_len=3.0) -> SegmentArray:
    """Random packed segments helper used across tests."""
    ts = rng.uniform(*t_span, n).astype(np.float32)
    # Lengths in [0.1, max_len), or (max_len/2, max_len) when max_len < 0.2.
    te = ts + rng.uniform(min(0.1, max_len / 2), max_len, n).astype(np.float32)
    p0 = rng.uniform(0, box, (n, 3)).astype(np.float32)
    p1 = p0 + rng.normal(0, 2.0, (n, 3)).astype(np.float32)
    order = np.argsort(ts, kind="stable")
    return SegmentArray(
        xs=p0[order, 0], ys=p0[order, 1], zs=p0[order, 2],
        xe=p1[order, 0], ye=p1[order, 1], ze=p1[order, 2],
        ts=ts[order], te=te[order],
        seg_id=np.arange(n, dtype=np.int32),
        traj_id=(np.arange(n, dtype=np.int32) % 7),
    )


#: the (backend, compaction) modes of the single-device device path
DEVICE_MODES = [("pallas", "fused"), ("pallas", "dense"), ("jnp", "dense")]

#: canonical result columns that must equal brute force's exactly
EXACT_COLUMNS = ("entry_idx", "entry_traj", "entry_seg", "query_idx")


def assert_matches_brute(res, brute):
    """``res``'s canonical rows are brute force's: ids exactly, intervals
    to the cross-backend float32 tolerance."""
    assert len(brute) > 0
    for f in EXACT_COLUMNS:
        np.testing.assert_array_equal(getattr(res, f), getattr(brute, f),
                                      err_msg=f)
    for f in ("t_enter", "t_exit"):
        np.testing.assert_allclose(getattr(res, f), getattr(brute, f),
                                   rtol=1e-4, atol=1e-3, err_msg=f)
