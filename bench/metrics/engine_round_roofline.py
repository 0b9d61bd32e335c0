"""Share of the HBM roofline that the engine-round kernels reach: the bytes
the window's ops need (`bench.roofline.table_bytes`) over kernel time
times the chip's peak HBM bytes per second, in percent.  Bound by bytes:
the ops do no arithmetic to speak of."""

import numpy as np

from bench.roofline import table_bytes


def read(run):
    codes = getattr(run.cell, "codes", {})
    if (run.trace is None or "STORE" not in codes
            or not any(run.trace.kernel_events)):
        return None
    k = run.plan["config"]["k_words"]
    need = sum(table_bytes(run.cell.ops[b].kind, res[1], k, codes)
               for b, res in zip(run.batches, run.results))
    kernel_s = float(np.mean(run.trace.kernel_ns)) / 1e9
    return 100.0 * need / (kernel_s * run.peaks["hbm_bytes_per_s"])
