"""Median over the window's Scheduler.step calls of the serve.account span,
the host time after the results are ready, in ms (the program's spans)."""
from bench import spans


def read(run):
    return spans.account_ms(run)
