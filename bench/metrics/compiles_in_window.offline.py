"""Executables compiled or loaded from the cache inside the window."""


def read(run):
    return run.compiles_in_window
