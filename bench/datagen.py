"""The benchmark's own copies of the paper's §7.1 dataset generators.

Copied from the program's ``repro.data.trajgen`` (``galaxy``) so that the
yardstick does not move when the program's generators do.  The
distributions and their parameters are the paper's as the program ships
them; only the way the random numbers are drawn differs: every array is
drawn in bulk (no per-trajectory loop), all of it from the run's seed,
and each uniform parameter is drawn stratified (:func:`stratified`), so
that every seed's dataset has the same density and so the same work.
Galaxy's time grid is fixed by the paper (400 unit steps shared by every
star), so every seed plans the same batch shapes.

A dataset is a :class:`Dataset`: struct-of-arrays float32 segment columns
in trajectory order (trajectory ``k``'s segments are rows
``offsets[k]:offsets[k + 1]``), with ``traj_id == k`` and ``seg_id`` the
segment's position within its trajectory.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

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


def _from_points(name: str, points: np.ndarray, times: np.ndarray,
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


def galaxy(seed: int, *, num_traj: int = 2500,
           num_segments: int = 400) -> Dataset:
    """GALAXY: disk-galaxy stellar orbits (flat rotation curve, radial
    epicycles, vertical oscillation), every star on one shared time grid
    of ``num_segments`` unit steps over [0, 400]."""
    rng = np.random.default_rng(seed)
    nt = num_traj
    steps = num_segments + 1
    t = np.linspace(0.0, 400.0, steps, dtype=np.float64)
    r0 = stratified(rng, 4.0, 12.0, nt)
    v0 = 0.22
    omega = v0 / r0
    phi0 = stratified(rng, 0.0, 2 * np.pi, nt)
    a_r = stratified(rng, 0.0, 0.6, nt)
    kappa = np.sqrt(2.0) * omega
    psi0 = stratified(rng, 0.0, 2 * np.pi, nt)
    a_z = stratified(rng, 0.0, 0.3, nt)
    nu = 2.0 * omega
    zeta0 = stratified(rng, 0.0, 2 * np.pi, nt)
    tt = t[None, :]
    r = r0[:, None] + a_r[:, None] * np.cos(kappa[:, None] * tt
                                            + psi0[:, None])
    ang = phi0[:, None] + omega[:, None] * tt
    pts = np.stack([r * np.cos(ang), r * np.sin(ang),
                    a_z[:, None] * np.sin(nu[:, None] * tt + zeta0[:, None])],
                   axis=-1).reshape(-1, 3)
    times = np.tile(t, nt)
    return _from_points("galaxy", pts, times, np.full(nt, num_segments))


GENERATORS = {"galaxy": galaxy}


def make(config: dict, seed: int) -> Dataset:
    """The dataset a configuration names, drawn from ``seed``."""
    spec = config["dataset"]
    params = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](seed, **params)
