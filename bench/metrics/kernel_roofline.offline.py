"""Roofline floor of the real rows' conv and FC work (bench/counts.py)
over device busy time, in %."""
from bench import readers


def read(run):
    return readers.roofline_pct(run)
