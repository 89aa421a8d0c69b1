"""90th percentile over the completed requests of the time from when a
request was due to when its output was on the host."""

import numpy as np


def read(run):
    return float(np.percentile([r["finish"] - r["due"] for r in run.completed],
                               90))
