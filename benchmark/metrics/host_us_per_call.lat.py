"""Host path per call (facade, device layer, lowering cache, launch,
placement, staging; from outside): the median over the traced calls of
the call's span (host clock) less the time its program kept the busiest
chip busy (device clock)."""

import statistics

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    host = [(span.end - span.start) - max(ns) for span, ns in
            trace_reduce.per_call_device_ns(run.trace, run.device_ids)]
    return statistics.median(host) / 1e3 if host else None
