"""95th percentile of the window's per-batch latencies (host clock): from
the call into the entry point until all of the batch's results are on the
host.  numpy's linear interpolation between order statistics."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.latency_s), 95)) * 1e3
