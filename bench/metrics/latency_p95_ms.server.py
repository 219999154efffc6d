"""95th percentile due-to-ready latency of the requests due in the window.

A per-layer reading of the served cells: host stalls of a tenth of a
second, in some runs and not in others, move it by more than the largest
bound an end-to-end metric may have."""
from bench import readers


def read(run):
    return readers.latency_ms(run, 95)
