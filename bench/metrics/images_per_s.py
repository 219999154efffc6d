"""Images completed over the window, from its start to the last completion."""
from bench import readers


def read(run):
    return readers.images_per_s(run)
