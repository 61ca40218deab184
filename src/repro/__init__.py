"""Reproduction of "Parallel Distance Threshold Query Processing for
Spatiotemporal Trajectory Databases on the GPU" (cs.DB 2014), grown into a
jax/Pallas system.

The stable public surface is :mod:`repro.api` — ``TrajectoryDB`` and
friends are re-exported lazily here so that ``import repro`` stays cheap
for subpackages (``repro.data``, ``repro.lint``, …) that never touch the
query engine.
"""
from __future__ import annotations

__version__ = "0.1.0"

_API_NAMES = ("TrajectoryDB", "ExecutionPolicy", "QueryResult",
              "QueryBackend", "BACKENDS", "QueryBroker", "QueryTicket",
              "GroupSlice", "AdmissionError", "DeadlineExceededError",
              "CapacityError", "PodFailedError", "RetryPolicy",
              "TicketHealth", "Degradation", "FaultPlan", "FaultSpec")


def __getattr__(name: str):
    if name in _API_NAMES:
        from repro import api
        return getattr(api, name)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_API_NAMES))
