"""Process start to the window's first step: imports, data, weights, the
startup DPT tune, compilation (or the cache load) and the warm steps."""


def read(run):
    return run.setup_s
