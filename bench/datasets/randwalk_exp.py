"""RANDWALK-EXP, the paper's §7.1 dataset of random walks whose lengths
are exponentially distributed.

The distributions and their parameters are the paper's as the program
ships them (``repro.data.trajgen.randwalk_exp``): lengths Exp(mean 70)
clipped to [2, 1000] unit steps and truncated to whole steps, start times
U(0, 20), initial positions U(0, 400)^3 and Brownian steps of standard
deviation 2 per axis per unit step.  Only the way the random numbers are
drawn differs: every array is drawn in bulk (no per-trajectory loop), all
of it from the run's seed, and the length and the start time, which set
the work, are drawn stratified (``datagen.stratified``), so that every
seed's dataset holds the same number of segments and the same temporal
density.  Most walks end within about 70 steps of a start in [0, 20], so
activity decays with time: the temporal skew the paper's SETSPLIT and
GREEDYSETSPLIT were designed for.
"""
from __future__ import annotations

import numpy as np

from bench import datagen

#: Side of the cube the walks start in, and the Brownian step's standard
#: deviation per axis per unit step (the program's ``trajgen`` choices;
#: the paper gives neither).
BOX = 400.0
STEP_SIGMA = 2.0


def generate(seed: int, *, num_traj: int = 10_000, mean_length: float = 70.0,
             min_length: int = 2, max_length: int = 1000,
             start_max: float = 20.0) -> datagen.Dataset:
    """RANDWALK-EXP: ``num_traj`` Brownian walks of Exp(``mean_length``)
    unit steps, clipped to [``min_length``, ``max_length``], starting at
    U(0, ``start_max``)."""
    rng = np.random.default_rng(seed)
    nt = num_traj
    # Exponential lengths through the inverse CDF of a stratified uniform.
    u = datagen.stratified(rng, 0.0, 1.0, nt)
    lengths = np.clip(-mean_length * np.log1p(-u), min_length,
                      max_length).astype(np.int64)
    starts = datagen.stratified(rng, 0.0, start_max, nt)
    p_off = np.concatenate([[0], np.cumsum(lengths + 1)])
    traj = np.repeat(np.arange(nt), lengths + 1)
    local = np.arange(p_off[-1]) - p_off[traj]
    # Each walk's first point is its start position; every later point adds
    # one normal step.  One cumsum over all walks, less each walk's prefix.
    inc = rng.normal(0.0, STEP_SIGMA, size=(p_off[-1], 3))
    inc[p_off[:-1]] = rng.uniform(0.0, BOX, size=(nt, 3))
    cum = np.cumsum(inc, axis=0)
    before = np.concatenate([np.zeros((1, 3)), cum[p_off[1:-1] - 1]])
    pts = cum - before[traj]
    times = starts[traj] + local
    return datagen.from_points("randwalk-exp", pts, times, lengths)


def work(data: datagen.Dataset) -> np.ndarray:
    """Per trajectory, the sum over its segments of the number of database
    segments whose time extents overlap the segment's (itself included):
    the pairs a query set that holds the trajectory evaluates."""
    ts = np.asarray(data.cols["ts"], np.float64)
    te = np.asarray(data.cols["te"], np.float64)
    started = np.searchsorted(np.sort(ts), te, side="right")
    ended = np.searchsorted(np.sort(te), ts, side="left")
    return np.add.reduceat(started - ended, data.offsets[:-1])


def strata(data: datagen.Dataset, n: int) -> list[np.ndarray]:
    """The trajectories in ``n`` strata of (nearly) equal size, by their
    work (:func:`work`): a walk's length and start time decide how many
    pairs it makes, over two orders of magnitude, so one walk from each
    stratum gives every set the work of a uniform draw without its
    spread."""
    return np.array_split(np.argsort(work(data), kind="stable"), n)
