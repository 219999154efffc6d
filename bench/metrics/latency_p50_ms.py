"""Median due-to-ready latency of the requests due in the window."""
from bench import readers


def read(run):
    return readers.latency_ms(run, 50)
