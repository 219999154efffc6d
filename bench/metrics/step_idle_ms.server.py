"""Device-idle time while the host is in Scheduler.step outside serve.wait,
per step, in ms (the program's spans on the profiler's clock)."""
from bench import spans


def read(run):
    return spans.idle_ms(run)
