"""95th percentile of due time to the start of the Scheduler.step that took the request."""
from bench import readers


def read(run):
    return readers.queue_wait_ms(run, 95)
