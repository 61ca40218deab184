"""h2d_mb.batch: median over the window's query sets of the bytes of the
host arrays handed to the kernels' jit call, retries included (the
program's ``h2d_bytes`` counter), in MB (10^6 bytes)."""
from bench import spans


def read(run):
    return spans.median(run, "batch",
                        lambda st: st.counts.get("h2d_bytes", 0) / 1e6)
