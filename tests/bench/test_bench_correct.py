"""A run's ``correct`` at a size a test holds, on the CPU: true for the
program, false with the timed path broken underneath, and false with the
control (the reference in bfloat16) in the program's place.

These drive ``bench.harness.run`` directly, past ``bench/run.py``'s look
for a TPU.  The control is planted in the single-device dispatcher that
the batch path (``db.query``) runs.
"""
import jax
import numpy as np
import pytest

from _bench_tiny import tiny_cell
from bench import harness, reference

CELLS = ["s2.batch"]
SEEDS = [2 ** 33 + 101, 2 ** 31 + 5]


def _run(cell, seed, seconds=1.0):
    return harness.run(cell, seed, seconds, False, jax.devices())


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, seed):
    out = _run(tiny_cell(name), seed)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


def _control_in_place(monkeypatch):
    """The dispatcher's rows replaced by the reference in bfloat16 over
    the same batch: the program's timed path with the control put in the
    kernel's place."""
    from repro.core import engine

    def marshal(self, dp, count):
        b, eng = dp.batch, self.engine
        e = eng._packed[b.cand_first:b.cand_last + 1]
        q = self.q_packed[b.q_first:b.q_last + 1]
        ei, qi = np.meshgrid(np.arange(len(e)), np.arange(len(q)),
                             indexing="ij")
        ei, qi = ei.ravel(), qi.ravel()
        names = ("xs", "ys", "zs", "xe", "ye", "ze", "ts", "te")
        hit, t_in, t_out, _ = reference.solve(
            {c: e[ei, k] for k, c in enumerate(names)},
            {c: q[qi, k] for k, c in enumerate(names)}, self.d,
            reference.CONTROL_DTYPE)
        eg = b.cand_first + ei[hit].astype(np.int64)
        return engine.ResultSet(
            entry_idx=eg, entry_traj=eng.db.traj_id[eg].astype(np.int64),
            entry_seg=eng.db.seg_id[eg].astype(np.int64),
            query_idx=b.q_first + qi[hit].astype(np.int64),
            t_enter=t_in[hit].astype(np.float32),
            t_exit=t_out[hit].astype(np.float32))

    monkeypatch.setattr(engine._QueryBlockDispatcher, "marshal", marshal)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(monkeypatch, name, seed):
    _control_in_place(monkeypatch)
    out = _run(tiny_cell(name), seed, seconds=0.5)
    assert not out["correct"], out["checks"]
