"""The host path outside dispatch, completion and staging, per call (the
program's own spans): the facade's call span, opened at the public
method's entry, less its launch, wait, stage_in and stage_out children;
what is left is the facade's Python, plan selection, the lowering-cache
lookup and placement. The median over the window's calls."""

import statistics

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    if not calls:
        return None
    return statistics.median(
        c["dur_ns"] - program_spans.total_ns(
            ph, "launch", "wait", "stage_in", "stage_out")
        for c, ph in calls) / 1e3
