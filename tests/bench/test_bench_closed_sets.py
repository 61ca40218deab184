"""The closed loop's query sets: one trajectory of each of the dataset's
strata, drawn from the seed, and every set of the window run in the
warm-up."""
import contextlib
import types

import numpy as np

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells
from bench.traffic import closed_sets

GALAXY = cells.dataset("galaxy")


def _ctx(seed, sets=5, n=4):
    data = GALAXY.generate(7, num_traj=40, num_segments=6)
    cell = types.SimpleNamespace(params={"sets": sets},
                                 config={"query_set_trajectories": n,
                                         "dataset": {"generator": "galaxy"}})
    return types.SimpleNamespace(cell=cell, seed=seed, data=data,
                                 segments=lambda rows: rows)


def test_every_set_takes_one_trajectory_of_each_stratum():
    big = 2 ** 33 + 3
    state = closed_sets.prepare(_ctx(big), 1.0)
    groups = GALAXY.strata(_ctx(big).data, 4)
    assert len(state["comps"]) == 5
    for comp in state["comps"]:
        assert all(t in g for t, g in zip(comp, groups))
    again = closed_sets.prepare(_ctx(big), 1.0)["comps"]
    other = closed_sets.prepare(_ctx(big + 1), 1.0)["comps"]
    assert all(np.array_equal(a, b) for a, b in zip(state["comps"], again))
    assert not all(np.array_equal(a, b)
                   for a, b in zip(state["comps"], other))


def test_warmup_runs_every_set_of_the_window_once():
    ctx = _ctx(11)
    state = closed_sets.prepare(ctx, 1.0)
    seen = []
    ctx.span = lambda name: contextlib.nullcontext()
    ctx.d, ctx.backend = 0.5, "jnp"
    ctx.db = types.SimpleNamespace(
        query=lambda segs, d, backend: seen.append(segs))
    closed_sets.warmup(ctx, state)
    assert len(seen) == 5
    assert all(np.array_equal(a, b) for a, b in zip(seen, state["segs"]))
