"""95th percentile of the sweep queries' latencies, in ms."""
import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.durations, 95))
