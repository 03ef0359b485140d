"""95th percentile of time-to-ε over all panels due in the window."""
import numpy as np

from bench.lib.layers import time_to_eps


def read(record, trace):
    return float(np.percentile(time_to_eps(record), 95))
