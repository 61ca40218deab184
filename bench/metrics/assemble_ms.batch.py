"""assemble_ms.batch: median over the window's query sets of the seconds
of result assembly on the host: marshalling less its copies from the
device (``repro.exec.marshal`` less ``repro.engine.fetch``: masks and
id gathers) and the concatenation of the parts (``repro.exec.concat``),
in ms."""
from bench import spans


def read(run):
    return spans.span_ms(run, "batch",
                         plus=("repro.exec.marshal", "repro.exec.concat"),
                         minus=("repro.engine.fetch",))
