"""compile_s.batch: seconds JAX spent tracing, lowering and compiling
inside the measured window (a shape met anew pays its tracing and lowering
in every process, even when the compile cache holds it)."""
from bench import readers


def read(run):
    return readers.compile_s(run, "batch")
