"""device_idle.batch: share of the traced window in which no operation ran
on the device (a fraction)."""
from bench import readers


def read(run):
    return readers.device_idle(run, "batch")
