"""The share of the traced window in which no operation ran on the chip,
averaged over the cell's chips (profiler trace)."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return trace_reduce.idle_share_pct(run.trace, run.device_ids)
