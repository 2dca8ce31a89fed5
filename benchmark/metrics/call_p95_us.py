"""95th percentile of call latency over every call of the window (host
clock), timed as call_p50_us is."""

import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([(t1 - t0) / 1e3 for _, t0, t1 in run.calls], 95))
