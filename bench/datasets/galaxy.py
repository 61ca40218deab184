"""GALAXY, the paper's §7.1 dataset of disk-galaxy stellar orbits.

Copied from the program's ``repro.data.trajgen.galaxy`` so that the
yardstick does not move when the program's generator does.  The
distributions and their parameters are the paper's as the program ships
them; only the way the random numbers are drawn differs: every array is
drawn in bulk (no per-trajectory loop), all of it from the run's seed,
and each uniform parameter is drawn stratified (``datagen.stratified``),
so that every seed's dataset has the same density and so the same work.
The time grid is fixed by the paper (400 unit steps shared by every
star), so every seed plans the same batch shapes.
"""
from __future__ import annotations

import numpy as np

from bench import datagen


def generate(seed: int, *, num_traj: int = 2500,
             num_segments: int = 400) -> datagen.Dataset:
    """GALAXY: disk-galaxy stellar orbits (flat rotation curve, radial
    epicycles, vertical oscillation), every star on one shared time grid
    of ``num_segments`` unit steps over [0, 400]."""
    rng = np.random.default_rng(seed)
    nt = num_traj
    steps = num_segments + 1
    t = np.linspace(0.0, 400.0, steps, dtype=np.float64)
    r0 = datagen.stratified(rng, 4.0, 12.0, nt)
    v0 = 0.22
    omega = v0 / r0
    phi0 = datagen.stratified(rng, 0.0, 2 * np.pi, nt)
    a_r = datagen.stratified(rng, 0.0, 0.6, nt)
    kappa = np.sqrt(2.0) * omega
    psi0 = datagen.stratified(rng, 0.0, 2 * np.pi, nt)
    a_z = datagen.stratified(rng, 0.0, 0.3, nt)
    nu = 2.0 * omega
    zeta0 = datagen.stratified(rng, 0.0, 2 * np.pi, nt)
    tt = t[None, :]
    r = r0[:, None] + a_r[:, None] * np.cos(kappa[:, None] * tt
                                            + psi0[:, None])
    ang = phi0[:, None] + omega[:, None] * tt
    pts = np.stack([r * np.cos(ang), r * np.sin(ang),
                    a_z[:, None] * np.sin(nu[:, None] * tt + zeta0[:, None])],
                   axis=-1).reshape(-1, 3)
    times = np.tile(t, nt)
    return datagen.from_points("galaxy", pts, times,
                               np.full(nt, num_segments))


def strata(data: datagen.Dataset, n: int) -> list[np.ndarray]:
    """The trajectories in ``n`` strata of (nearly) equal size, by the
    mean distance of their segments' start points from the dataset's
    centre.  A star's neighbours, and so a query set's hit rows, follow
    its radius: drawn plainly, GALAXY sets of 10 spread by about 8 % in
    their hit rows; one star from each stratum, by about 1.3 %."""
    xyz = np.stack([data.cols[c] for c in ("xs", "ys", "zs")], axis=1)
    centre = xyz.mean(axis=0)
    dist = np.linalg.norm(xyz - centre, axis=1)
    mean = (np.add.reduceat(dist, data.offsets[:-1])
            / np.diff(data.offsets))
    return np.array_split(np.argsort(mean, kind="stable"), n)
