"""Device time of the Pallas kernels (`tpu_custom_call` ops) per batch in
the traced window, averaged over the chips used.  Both engine-round tiers'
kernels count; `fast_tier_share` says which tier ran."""

import numpy as np


def read(run):
    if run.trace is None or not run.batches or not any(
            run.trace.kernel_events):
        return None
    return float(np.mean(run.trace.kernel_ns)) / 1e6 / len(run.batches)
