"""A run's ``correct`` comes out false with the timed path broken
underneath: the harness at a size a test holds, on the CPU, driven past
``bench/run.py``'s look for a TPU.  Each break is planted in the
single-device dispatcher's marshalling, which the batch path
(``db.query``) runs, where the program produces its rows.
"""
import jax
import numpy as np
import pytest

from _bench_tiny import tiny_cell
from bench import harness

SEED = 2 ** 33 + 202


def _break_marshal(monkeypatch, how):
    from repro.core import engine
    real = engine._QueryBlockDispatcher.marshal

    def marshal(self, dp, count):
        part = real(self, dp, count)
        if part is None:
            return None
        if how == "nothing":          # the step returns nothing
            return None
        if how == "half":             # half of each batch left out
            keep = np.arange(len(part)) % 2 == 0
            return engine.ResultSet(*(getattr(part, f)[keep] for f in (
                "entry_idx", "entry_traj", "entry_seg", "query_idx",
                "t_enter", "t_exit")))
        if how == "altered":          # an answer altered where produced
            part.t_enter = part.t_enter + np.float32(0.25)
            return part
        if how == "wrong_entry":      # a row names the wrong segment
            part.entry_seg = part.entry_seg.copy()
            part.entry_seg[0] += 1
            return part
        raise AssertionError(how)

    monkeypatch.setattr(engine._QueryBlockDispatcher, "marshal", marshal)


@pytest.mark.parametrize("how", ["nothing", "half", "altered",
                                 "wrong_entry"])
@pytest.mark.parametrize("name", ["s2.batch"])
def test_broken_path_is_not_correct(monkeypatch, name, how):
    _break_marshal(monkeypatch, how)
    out = harness.run(tiny_cell(name), SEED, 1.0, False, jax.devices())
    assert not out["correct"], out["checks"]
