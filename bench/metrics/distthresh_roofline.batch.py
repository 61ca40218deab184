"""distthresh_roofline.batch: percent of the roofline of the
distance-threshold kernel's device time in the traced window
(``bench.roofline``)."""
from bench import readers


def read(run):
    return readers.kernel_roofline(run, "batch")
