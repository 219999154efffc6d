"""Share of the traced window with no op on the device, mean over the chips used, in %."""
from bench import readers


def read(run):
    return readers.idle_pct(run)
