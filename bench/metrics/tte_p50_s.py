"""Median time-to-ε over all panels due in the window, in seconds."""
import numpy as np

from bench.lib.layers import time_to_eps


def read(record, trace):
    return float(np.percentile(time_to_eps(record), 50))
