"""Median over the window's Scheduler.step calls of the host time from the
step's start to the end of its unpack call, in ms (the program's spans)."""
from bench import spans


def read(run):
    return spans.dispatch_ms(run)
