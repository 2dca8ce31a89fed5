"""The traced run's device time and breakdown: the device operations
that took most time (averaged over the chips), and the first chip's idle
time named by what the host was doing in it: inside a call's jit
dispatch (JAX's own host event), elsewhere inside a call (the rest of
the host path, and the wait for the device), or between calls. Naming
leans on the device events' move onto the host clock (trace_reduce),
good to about 0.1 ms."""

from __future__ import annotations

import trace_reduce

TOP = 10


def device_time(trace: trace_reduce.Trace, run) -> dict:
    """busy_s: seconds of the window in which an operation ran, averaged
    over the chips; window_s: the traced window."""
    w = trace.window()
    if w is None:
        return {}
    busy = sum(trace_reduce.busy_in_window(trace, d) for d in run.device_ids)
    return {"busy_s": busy / len(run.device_ids) / 1e9,
            "window_s": (w[1] - w[0]) / 1e9}


def _op_name(hlo: str) -> str:
    """An XLA op's event is named by its whole HLO instruction; keep the
    instruction's name and result shape."""
    return hlo.split("{", 1)[0].strip()


def breakdown(trace: trace_reduce.Trace, run) -> dict:
    w = trace.window()
    if w is None:
        return {}
    per_op: dict[str, float] = {}
    for d in run.device_ids:
        for op in trace.ops.get(d, []):
            if w[0] <= op.start < w[1]:
                name = _op_name(op.name)
                per_op[name] = per_op.get(name, 0.0) + op.end - op.start
    n = len(run.device_ids)
    device_ops = sorted(((k, v / n / 1e9) for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:TOP]
    calls = trace_reduce.union(
        (c.start, c.end) for c in trace.spans_named("bench.call"))
    idle = {"in call: jit dispatch": 0.0, "in call: rest of host path": 0.0,
            "between calls": 0.0}
    for lo, hi in trace_reduce.gaps(trace_reduce.busy(trace, run.device_ids[0]), *w):
        in_call = trace_reduce.covered(calls, lo, hi)
        in_dispatch = sum(trace_reduce.covered(trace.dispatch, a, b)
                          for a, b in trace_reduce.clip(calls, lo, hi))
        idle["in call: jit dispatch"] += in_dispatch / 1e9
        idle["in call: rest of host path"] += (in_call - in_dispatch) / 1e9
        idle["between calls"] += (hi - lo - in_call) / 1e9
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [list(x) for x in device_ops],
            "idle_gaps": [list(x) for x in idle_gaps]}
