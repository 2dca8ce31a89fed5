"""The lowering cache: the share of the window's `lower` spans (the
compiled-program lookup of each call) that found their program, %."""

import program_spans


def read(run):
    calls = program_spans.per_call(run)
    lowers = [s for _, ph in calls or [] for s in ph.get("lower", [])]
    if not lowers:
        return None
    return 100.0 * sum(bool(s["args"].get("hit")) for s in lowers) / len(lowers)
