"""The ring kernel's share of its roofline: the least time the chip could
take for the traced calls (costs.least_time_s: the larger of the ICI and
the HBM bound) over the kernel's device time for them, in percent."""

import costs
import kernels


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    per_call = kernels.per_call(run.trace, run.device_ids)
    if not per_call:
        return None
    least = sum(costs.least_time_s(n, run.world, run.peaks)[0] for n, _ in per_call)
    return 100.0 * least / (sum(ns for _, ns in per_call) / 1e9)
