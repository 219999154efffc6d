"""FLOPs of the images completed over window x chips x bf16 peak, in %."""
from bench import readers


def read(run):
    return readers.mfu_pct(run)
