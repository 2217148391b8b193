"""90th percentile of the wall time of every step in the window, each
from its entry to the next step's entry: device time, the trainer's
hooks and the wait for the next batch."""
import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.step_times, 90))
