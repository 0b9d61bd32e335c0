"""Host entry: the harness's own span from the call into the system's entry
point (for a table `atomics.apply`: kind checks, canonicalization, jit
dispatch) until it returns, before any wait.  Mean over the window."""

import numpy as np


def read(run):
    return float(np.mean(run.dispatch_s)) * 1e3
