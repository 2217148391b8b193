"""90th percentile of the window's waits for a batch, in ms."""
import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.data_waits, 90))
