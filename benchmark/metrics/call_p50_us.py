"""Median call latency over every call of the window (host clock): from
entering ACCL.allreduce to the result being ready where the caller
reads it."""

import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([(t1 - t0) / 1e3 for _, t0, t1 in run.calls], 50))
