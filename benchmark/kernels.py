"""The ring kernel as the device trace names it, and its time per call.

The kernel is `ring_allreduce_pallas_bidir`'s `pallas_call`
(accl_tpu/ops/ring_allreduce.py). The trace names an `XLA Ops` event by
its HLO instruction, and the kernel's `pallas_call` has no name of its
own there: it reads `%tpu_custom_call.N = f32[rows,128]... custom-call(...),
custom_call_target="tpu_custom_call", ...` (read by hand from a v5e
trace, PR 22). In an allreduce program the ring kernel is the only
Mosaic custom call, so KERNEL_EVENT matches the custom-call target.
"""

from __future__ import annotations

import statistics

import trace_reduce

KERNEL_EVENT = r'custom_call_target="tpu_custom_call"'


def per_call(trace: trace_reduce.Trace, device_ids: list[int]):
    """(nbytes per rank, kernel ns averaged over the chips) for each call
    whose program ran the kernel on every chip."""
    return [(int(span.args["nbytes"]), statistics.fmean(ns))
            for span, ns in trace_reduce.per_call_device_ns(
                trace, device_ids, KERNEL_EVENT) if all(ns)]
