"""Every batching algorithm through the device path, on the paper's S2.

S2 (GALAXY, d = 5) is hit-heavy and unprunable: every star is active over
the whole extent.  The first capacity is far below a batch's hits, so
batches overflow and are re-dispatched at the exact count, as every
dispatch of the deployment is.  Each case plans the query set with one of
the six batching algorithms, runs it in one (backend, compaction,
pruning) mode and compares the canonical result with brute force.
"""
import pytest

from conftest import DEVICE_MODES, assert_matches_brute
from repro.api import ExecutionPolicy, TrajectoryDB
from repro.core.batching import ALGORITHMS

#: per-algorithm parameters that split the 400 query segments into
#: several batches
BATCH_PARAMS = {
    "periodic": {"s": 64},
    "setsplit-fixed": {"num_batches": 6},
    "setsplit-max": {"max_size": 96},
    "setsplit-minmax": {"min_size": 32, "max_size": 96},
    "greedysetsplit-min": {"bound": 64},
    "greedysetsplit-max": {"bound": 128},
}


@pytest.fixture(scope="module")
def s2():
    """S2 at scale 0.01 (25 stars, 10,000 segments, one query trajectory
    of 400 segments) and its brute-force result."""
    db = TrajectoryDB.from_scenario(
        "S2", scale=0.01, policy=ExecutionPolicy(num_bins=100, capacity=64))
    return db, db.query(db.scenario_queries, db.scenario_d, backend="brute")


@pytest.mark.parametrize("pruning", ["spatial", "hierarchical", "none"])
@pytest.mark.parametrize("backend,compaction", DEVICE_MODES)
@pytest.mark.parametrize("batching", list(ALGORITHMS))
def test_s2_batching_matches_brute_force(s2, batching, backend, compaction,
                                         pruning):
    db, brute = s2
    res = db.query(db.scenario_queries, db.scenario_d, backend=backend,
                   policy=db.policy.with_(
                       batching=batching, batch_params=BATCH_PARAMS[batching],
                       compaction=compaction, pruning=pruning))
    assert len(res.plan.batches) >= 3
    assert_matches_brute(res, brute)
    assert res.stats.counts["retried_dispatches"] > 0
