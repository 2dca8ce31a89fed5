"""Device time of the ring kernel per call: its events' durations in the
call's span, averaged over the chips; the median over traced calls."""

import statistics

import kernels


def read(run):
    if run.trace is None:
        return None
    per_call = kernels.per_call(run.trace, run.device_ids)
    return statistics.median(ns for _, ns in per_call) / 1e3 if per_call else None
