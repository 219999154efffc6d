"""FLOPs of the requests served in the window over window x chips x bf16
peak, in %: the whole served step's share beside `kernel_roofline.server`."""
from bench import readers


def read(run):
    return readers.mfu_pct(run)
