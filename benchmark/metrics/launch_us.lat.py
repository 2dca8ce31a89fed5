"""JAX dispatch, the output allocation and the enqueue, per call (the
program's `launch` span around the compiled program's call); the median
over the window's calls that launched one."""

import statistics

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    ns = [program_spans.total_ns(ph, "launch") for _, ph in calls or []
          if "launch" in ph]
    return statistics.median(ns) / 1e3 if ns else None
