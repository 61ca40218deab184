"""What the benchmark's dataset generators share.

Each generator is a module of its own, ``bench/datasets/<generator>.py``,
found by the name a configuration gives under ``dataset.generator``
(:func:`module`).  It has two functions:

* ``generate(seed, **params)`` — the :class:`Dataset`, every array drawn
  in bulk from ``seed``, the parameters that set the work drawn stratified
  (:func:`stratified`), so that every seed's dataset holds the same work;
* ``strata(data, n)`` — the dataset's trajectories in ``n`` strata of
  equal work, from which the traffic draws one trajectory each for a
  query set, so that every seed's sets hold the same work too.

A dataset is a :class:`Dataset`: struct-of-arrays float32 segment columns
in trajectory order (trajectory ``k``'s segments are rows
``offsets[k]:offsets[k + 1]``), with ``traj_id == k`` and ``seg_id`` the
segment's position within its trajectory.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import cells

#: Segment columns, in the order the reference and the program's packed
#: layout both use: start point, end point, temporal extent.
COLUMNS = ("xs", "ys", "zs", "xe", "ye", "ze", "ts", "te")


@dataclasses.dataclass
class Dataset:
    name: str
    cols: dict            # column name -> (n,) float32
    traj_id: np.ndarray   # (n,) int32
    seg_id: np.ndarray    # (n,) int32
    offsets: np.ndarray   # (num_traj + 1,) int64 row offsets per trajectory

    def __len__(self) -> int:
        return int(self.traj_id.shape[0])

    @property
    def num_traj(self) -> int:
        return int(self.offsets.shape[0] - 1)

    def rows_of(self, trajs) -> np.ndarray:
        """Row indices of the segments of ``trajs``, trajectory by
        trajectory, in the order given."""
        parts = [np.arange(self.offsets[k], self.offsets[k + 1])
                 for k in np.asarray(trajs, np.int64)]
        return (np.concatenate(parts) if parts
                else np.zeros(0, np.int64))


def from_points(name: str, points: np.ndarray, times: np.ndarray,
                lengths: np.ndarray) -> Dataset:
    """Segments between consecutive points of each trajectory.

    ``points`` is (P, 3) and ``times`` (P,), all trajectories' points
    concatenated; trajectory ``k`` has ``lengths[k]`` segments, so
    ``lengths[k] + 1`` points.
    """
    lengths = np.asarray(lengths, np.int64)
    pts = np.asarray(points, np.float32)
    tms = np.asarray(times, np.float32)
    p_off = np.concatenate([[0], np.cumsum(lengths + 1)])
    # Row r of trajectory k joins point p_off[k] + r to the next one.
    traj_id = np.repeat(np.arange(lengths.size), lengths)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    seg_id = np.arange(offsets[-1]) - offsets[traj_id]
    start = p_off[traj_id] + seg_id
    cols = {"xs": pts[start, 0], "ys": pts[start, 1], "zs": pts[start, 2],
            "xe": pts[start + 1, 0], "ye": pts[start + 1, 1],
            "ze": pts[start + 1, 2], "ts": tms[start], "te": tms[start + 1]}
    return Dataset(name, cols, traj_id.astype(np.int32),
                   seg_id.astype(np.int32), offsets.astype(np.int64))


def stratified(rng: np.random.Generator, lo: float, hi: float,
               n: int) -> np.ndarray:
    """``n`` draws of U(lo, hi), one in each of ``n`` equal strata, in a
    random order: the distribution of ``rng.uniform(lo, hi, n)`` without
    the sampling spread of its histogram.  Stars at a small radius have
    the most neighbours, so a plain draw moves a dataset's hit count by
    about 1 % from seed to seed; this keeps it within about 0.1 %."""
    u = (rng.permutation(n) + rng.random(n)) / n
    return lo + (hi - lo) * u


def module(config: dict):
    """The dataset module a configuration names under
    ``dataset.generator``."""
    return cells.dataset(config["dataset"]["generator"])


def make(config: dict, seed: int) -> Dataset:
    """The dataset a configuration names, drawn from ``seed``."""
    params = {k: v for k, v in config["dataset"].items()
              if k != "generator"}
    return module(config).generate(seed, **params)
