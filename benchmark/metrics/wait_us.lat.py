"""Completion, per call: the program's `wait` span, from the launch's
return to the host seeing the result ready; the median over the window's
calls that waited."""

import statistics

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    ns = [program_spans.total_ns(ph, "wait") for _, ph in calls or []
          if "wait" in ph]
    return statistics.median(ns) / 1e3 if ns else None
