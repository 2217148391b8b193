"""All tokens trained in the window over the window's wall time."""


def read(run):
    steps = run.window[1] - run.window[0]
    return steps * run.tokens_per_step / run.window_s
