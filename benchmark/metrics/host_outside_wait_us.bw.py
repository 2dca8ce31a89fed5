"""The host path of a bucket call outside its wait for completion: the
facade's call span less its `wait` span (the facade, plan, lowering
cache, dispatch and placement); the median over the window's calls."""

import statistics

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    if not calls:
        return None
    return statistics.median(
        c["dur_ns"] - program_spans.total_ns(ph, "wait")
        for c, ph in calls) / 1e3
