"""From a profiler trace to intervals: the one reduction every per-layer
metric reads.

`load` reads the `.xplane.pb` that `jax.profiler` writes. From each TPU
plane it takes the device's operations (the `XLA Ops` line) and its
program executions (the `XLA Modules` line: one per call of a jitted
program). From the host plane it takes the benchmark's own spans
(`jax.profiler.TraceAnnotation` named `bench.*`), JAX's jit dispatch
events, and the runtime's launch and completion events.

The profiler puts the device planes on the host's clock only roughly: on
a v5e the device's events read some 0.4-0.6 ms early against the host's
(PR 22, PERF.md). `load` moves each device's events onto the host clock:
the k-th program execution lies between the host's k-th launch and the
host's k-th read of the completion flag, and the middle of the offsets
that all executions allow is taken. Per-call metrics do not lean on it:
they pair the k-th call span with the k-th program execution and read
each side's durations on its own clock. The interval arithmetic below
works on plain (start_ns, end_ns) pairs.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
# JAX's own host event around a jitted call's dispatch (trace and launch)
DISPATCH_PREFIX = "PjitFunction"
# the runtime's host events that bracket a program's execution
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"
COMPLETION_EVENT = "ReadSyncFlag"


@dataclasses.dataclass
class Op:
    name: str
    start: float  # ns
    end: float


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Op]]  # device id -> its operations
    spans: list[Span]  # the benchmark's host spans
    modules: dict[int, list[Op]] = dataclasses.field(default_factory=dict)
    dispatch: list[tuple[float, float]] = dataclasses.field(
        default_factory=list)  # host intervals inside a jit dispatch
    shift: dict[int, float | None] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for v in list(self.ops.values()) + list(self.modules.values()):
            v.sort(key=lambda o: o.start)
        self.spans.sort(key=lambda s: s.start)
        self._starts = {d: [o.start for o in v] for d, v in self.ops.items()}

    def spans_named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def window(self) -> tuple[float, float] | None:
        """The traced window: the benchmark's `bench.window` span."""
        w = self.spans_named("bench.window")
        return (w[0].start, w[0].end) if w else None


def load(path: str, device_ids: list[int] | None = None) -> Trace:
    """Read one `.xplane.pb`; keep the TPU planes of `device_ids` (all
    TPU planes where None), moved onto the host's clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: dict[int, list[Op]] = {}
    modules: dict[int, list[Op]] = {}
    spans: list[Span] = []
    dispatch, launches, completions = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into.setdefault(dev, []).extend(
                        Op(e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns, e.end_ns,
                                          dict(e.stats)))
                    elif e.name.startswith(DISPATCH_PREFIX):
                        dispatch.append((e.start_ns, e.end_ns))
                    elif e.name == LAUNCH_EVENT:
                        launches.append((e.start_ns, e.end_ns))
                    elif e.name == COMPLETION_EVENT:
                        completions.append((e.start_ns, e.end_ns))
    launches.sort()
    completions.sort()
    shift = {}
    for dev, mods in modules.items():
        shift[dev] = clock_shift(sorted((o.start, o.end) for o in mods),
                                 launches, completions)
        if shift[dev] is not None:
            for o in ops.get(dev, []) + mods:
                o.start -= shift[dev]
                o.end -= shift[dev]
    return Trace(ops, spans, modules, union(dispatch), shift)


def clock_shift(execs, launches, completions) -> float | None:
    """How far a device's clock reads ahead of the host's: the middle of
    the offsets under which every execution starts after its launch and
    ends before the host's last read of the completion flag ahead of the
    next launch. The k-th execution pairs with the (k+j)-th launch, for
    the j within a few that pairs the most executions: a trace can lose a
    few events at its ends. None where no offset fits."""
    ends = [c[1] for c in completions]
    best = None
    for j in range(-3, 4):
        pairs = [(e, k + j) for k, e in enumerate(execs)
                 if 0 <= k + j < len(launches)]
        if not pairs:
            continue
        hi = min(e[0] - launches[i][0] for e, i in pairs)
        lo = float("-inf")
        for e, i in pairs:
            until = launches[i + 1][0] if i + 1 < len(launches) else float("inf")
            c = bisect.bisect_left(ends, until) - 1
            if c < 0 or ends[c] < launches[i][0]:
                lo = float("inf")  # no flag read for this launch
                break
            lo = max(lo, e[1] - ends[c])
        if lo <= hi and (best is None or len(pairs) > best[0]):
            best = (len(pairs), (lo + hi) / 2)
    return None if best is None else best[1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) pairs into disjoint, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """How much of [lo, hi] the disjoint sorted intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def clip(merged, lo: float, hi: float):
    """The parts of disjoint sorted intervals that lie inside [lo, hi]."""
    # the intervals are disjoint and sorted, so their ends are sorted too
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    for s, e in merged[i:]:
        if s >= hi:
            break
        if e > lo:
            yield max(s, lo), min(e, hi)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that the disjoint sorted intervals leave
    uncovered."""
    out = []
    at = lo
    for s, e in merged:
        if e <= at:
            continue
        if s >= hi:
            break
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def busy(trace: Trace, dev: int) -> list[tuple[float, float]]:
    """The device's busy intervals: the union of its operations."""
    return union((o.start, o.end) for o in trace.ops.get(dev, []))


def ops_in(trace: Trace, dev: int, lo: float, hi: float,
           pattern: str | None = None) -> list[Op]:
    """The device's operations that start inside [lo, hi), optionally
    only those whose name matches `pattern`."""
    ops = trace.ops.get(dev, [])
    starts = trace._starts.get(dev, [])
    a, b = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
    rx = re.compile(pattern) if pattern else None
    return [o for o in ops[a:b] if rx is None or rx.search(o.name)]


def call_executions(trace: Trace, dev: int) -> list[tuple[Span, Op]]:
    """Each benchmark call paired with the program execution it caused on
    the device: the k-th call with the k-th execution where the counts
    agree, else by the execution's middle falling inside the call."""
    calls = trace.spans_named("bench.call")
    mods = trace.modules.get(dev, [])
    if len(calls) == len(mods):
        return list(zip(calls, mods))
    starts = [c.start for c in calls]
    out = []
    for m in mods:
        mid = (m.start + m.end) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and calls[i].end > mid:
            out.append((calls[i], m))
    return out


def per_call_device_ns(trace: Trace, device_ids: list[int],
                       pattern: str | None = None):
    """For each call that ran a program on every chip: (span, [ns per
    chip]), the ns being the union of the chip's operations inside the
    call's program execution (only those matching `pattern`, if given).
    Each side is read on its own clock."""
    per_call: dict[int, tuple[Span, list[float]]] = {}
    for dev in device_ids:
        for span, mod in call_executions(trace, dev):
            ns = covered(union((o.start, o.end) for o in ops_in(
                trace, dev, mod.start, mod.end, pattern)),
                mod.start, mod.end)
            per_call.setdefault(id(span), (span, []))[1].append(ns)
    return [v for v in per_call.values() if len(v[1]) == len(device_ids)]


def busy_in_window(trace: Trace, dev: int) -> float:
    """Nanoseconds of the traced window in which the device was busy."""
    w = trace.window()
    return covered(busy(trace, dev), *w) if w else 0.0


def idle_share_pct(trace: Trace, device_ids: list[int]) -> float | None:
    """The share of the traced window, averaged over the chips, in which
    no operation ran on the chip. None where nothing was traced."""
    w = trace.window()
    if w is None or not any(trace.ops.get(d) for d in device_ids):
        return None
    mean_busy = sum(busy_in_window(trace, d) for d in device_ids) / len(device_ids)
    return 100.0 * (1.0 - mean_busy / (w[1] - w[0]))
