"""Real requests over batch slots (padding included) of the batches in the window, in %."""
from bench import readers


def read(run):
    return readers.occupancy_pct(run)
