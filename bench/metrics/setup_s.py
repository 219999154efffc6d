"""Set-up seconds: process start to the window, compiles and warm-up included."""


def read(run):
    return run.setup_s
