"""The program's own spans in a traced run: each window call's facade
call span and its child phases (accl_tpu.telemetry's tracer).

While a profiler session collects, the tracer's ring keeps every span;
the traced run starts its profiler after warm-up and stops it before the
metrics are read, so the ring then holds the window's spans. A call span
(`cat` "call") is named by its op and carries a `call_id`; its children
(`cat` "phase": stage_in, plan, lower, launch, wait, place, stage_out)
carry the same `call_id` and lie inside it. The ring's `ts_ns` and the
benchmark's `run.calls` are both on `perf_counter_ns`, so each window
call is paired with the call span that lies inside its (t0, t1).

A program that emits no such spans (one older than them) leaves the ring
empty, and every reader here returns None.
"""

from __future__ import annotations

MIN_PAIRED = 0.9  # share of the window's calls that must have a span


def per_call(run) -> list[tuple[dict, dict[str, list[dict]]]] | None:
    """[(call span, {child name: [child spans]})] for each window call
    that holds exactly one call span. None where the ring dropped spans,
    or where fewer than MIN_PAIRED of the window's calls have one."""
    from accl_tpu.telemetry import get_tracer

    tracer = get_tracer()
    if tracer.drops or not run.calls:
        return None
    ring = tracer.snapshot()
    spans = sorted((s for s in ring if s.get("cat") == "call"
                    and "call_id" in s.get("args", {})),
                   key=lambda s: s["ts_ns"])
    children: dict[int, dict[str, list[dict]]] = {}
    for s in ring:
        cid = s.get("args", {}).get("call_id")
        if s.get("cat") == "phase" and cid is not None:
            children.setdefault(cid, {}).setdefault(s["name"], []).append(s)
    out = []
    j = 0
    for _, t0, t1 in sorted(run.calls, key=lambda c: c[1]):
        while j < len(spans) and spans[j]["ts_ns"] < t0:
            j += 1
        inside = []
        while j < len(spans) and end(spans[j]) <= t1:
            inside.append(spans[j])
            j += 1
        if len(inside) == 1:
            call = inside[0]
            out.append((call, children.get(call["args"]["call_id"], {})))
    if len(out) < MIN_PAIRED * len(run.calls):
        return None
    return out


def end(span: dict) -> int:
    return span["ts_ns"] + span["dur_ns"]


def total_ns(phases: dict[str, list[dict]], *names: str) -> int:
    """The summed duration of a call's children of the given names."""
    return sum(s["dur_ns"] for n in names for s in phases.get(n, []))
