"""The window's summed waits for a batch (the trainer's ``data_s``) over
the window, in percent."""


def read(run):
    return 100.0 * sum(run.data_waits) / run.window_s
