"""Helpers of the benchmark's tests.

The tests import the benchmark as the package ``bench`` from the root of
the checkout, beside ``src``; importing this module puts that root on
``sys.path``.  (A ``conftest.py`` here would shadow ``tests/conftest.py``,
which other test modules import by name.)"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def shrink(cell, backend: str = "jnp"):
    """``cell`` at a size a CPU test run holds: a few dozen trajectories,
    sets of a few, and the XLA path (the Pallas interpreter is slow on a
    CPU; the harness around it is the same)."""
    cell.config["dataset"].update(num_traj=40, num_segments=60)
    cell.config["query_set_trajectories"] = 3
    cell.config["backend"] = backend
    cell.params.update(sets=2)
    return cell


def tiny_cell(name: str, **kw):
    """The cell ``name``, shrunk."""
    from bench import cells
    return shrink(cells.load_cell(name), **kw)
