"""Process start to the window's start: weights, server, compilation or
the compile cache's loads, and the warm-up turns."""


def read(run):
    return run.setup_s
