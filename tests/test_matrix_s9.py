"""Every batching algorithm through the device path, on the paper's S9.

S9 (RANDWALK-EXP, d = 50) is temporally skewed: a batch's candidate range
varies over orders of magnitude, so batches come in many shapes.  Each
case plans the query set with one of the six batching algorithms, runs it
in one (backend, compaction, pruning) mode with the deployment's dispatch
ladder of 4 steps per octave, and compares the canonical result with
brute force.
"""
import pytest

from conftest import DEVICE_MODES, assert_matches_brute
from repro.api import ExecutionPolicy, TrajectoryDB
from repro.core.batching import ALGORITHMS

#: per-algorithm parameters that split the 891 query segments into
#: several batches
BATCH_PARAMS = {
    "periodic": {"s": 128},
    "setsplit-fixed": {"num_batches": 6},
    "setsplit-max": {"max_size": 192},
    "setsplit-minmax": {"min_size": 64, "max_size": 192},
    "greedysetsplit-min": {"bound": 128},
    "greedysetsplit-max": {"bound": 256},
}


@pytest.fixture(scope="module")
def s9():
    """S9 at scale 0.01 (7,167 segments, 891 query segments) and its
    brute-force result."""
    db = TrajectoryDB.from_scenario(
        "S9", scale=0.01,
        policy=ExecutionPolicy(num_bins=100, dispatch_ladder=4))
    return db, db.query(db.scenario_queries, db.scenario_d, backend="brute")


@pytest.mark.parametrize("pruning", ["spatial", "hierarchical", "none"])
@pytest.mark.parametrize("backend,compaction", DEVICE_MODES)
@pytest.mark.parametrize("batching", list(ALGORITHMS))
def test_s9_batching_matches_brute_force(s9, batching, backend, compaction,
                                         pruning):
    db, brute = s9
    res = db.query(db.scenario_queries, db.scenario_d, backend=backend,
                   policy=db.policy.with_(
                       batching=batching, batch_params=BATCH_PARAMS[batching],
                       compaction=compaction, pruning=pruning))
    assert len(res.plan.batches) >= 3
    assert_matches_brute(res, brute)
    assert res.stats.counts["pad_rows"] > 0
