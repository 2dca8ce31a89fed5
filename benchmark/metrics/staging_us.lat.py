"""Staging host buffers into device memory and results back, per call:
the program's `stage_in` plus `stage_out` spans; the median over the
window's calls. None where no call staged (device buffers)."""

import statistics

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    if not calls or not any("stage_in" in ph or "stage_out" in ph
                            for _, ph in calls):
        return None
    return statistics.median(
        program_spans.total_ns(ph, "stage_in", "stage_out")
        for _, ph in calls) / 1e3
