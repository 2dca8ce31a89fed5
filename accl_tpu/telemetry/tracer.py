"""In-process span tracer: the host half of the telemetry subsystem.

The CCLO keeps its observability next to the data plane — hardware
performance counters and per-call duration registers the host reads back
after the fact (SURVEY.md L2/L4; the native runtime's trace ring is that
posture rebuilt, runtime.cpp record_span). This module is the HOST side
of the same contract: a thread-safe, bounded, drop-oldest ring of span
events that the facade, the sequence machinery, and the device backends
emit into, and that tools/accl_trace.py exports as
Chrome trace-event JSON (telemetry.export).

One stable event schema (SPAN v1) spans every emitter:

    {"name": str,      # operation / phase label ("allreduce", "lint")
     "cat": str,       # "call" | "step" | "phase" | "sequence" | "native"
     "track": str,     # render track: "facade", "device", "emu/r3", ...
     "ts_ns": int,     # start, perf_counter_ns domain (native spans are
                       #   rebased into it at drain time)
     "dur_ns": int,    # duration (0 = instant marker, e.g. a recorded
                       #   sequence step whose time is inside the fused
                       #   program)
     "args": {...}}    # schema'd detail keys: op, count, bytes, world,
                       #   algorithm, protocol, retcode, detail,
                       #   predicted_s, measured_s, coef_messages,
                       #   coef_bytes, signature, step, rank, d_passes,
                       #   d_parks, d_seek_hit, d_seek_miss, ...

Tracing is OFF by default and costs one predicate per instrumented site
when off (`span()` returns a shared no-op object before any argument
handling).
Enable with ACCL_TELEMETRY=1 in the environment or telemetry.enable().

The tracer is also the ONE emission seam of the always-on observability
layer (telemetry.metrics / telemetry.recorder): observers registered
with `add_observer()` receive every emitted event at span-emission time
— whether or not the ring itself is collecting — so the streaming
metrics registry and the flight recorder stay live without a trace ever
being drained. `span()` returns a live span whenever the tracer is
`active` (ring enabled, observers installed, or a profiler session
collecting); the ring retains events while spans are COLLECTED: the
ring is enabled, or a JAX profiler session collects.

While a profiler session collects, every span is also a
`jax.profiler.TraceAnnotation` named `accl.<name>` carrying its scalar
args, so it lands on the profiler's host plane next to JAX's dispatch
and the runtime's launch/completion events — the clock the device
trace is read on. The ring's `ts_ns` stays on `perf_counter_ns`.

Facade calls open a call span with `call()`: it carries a process-wide
increasing `call_id` (a call opened inside another takes the enclosing
call's id: a served decode step's sequence dispatch is part of the
step) and, while spans are collected, becomes the
thread's `current()` call, under which the device layer opens child
phase spans (`begin()` / `end()`: plan, lower, launch, wait, place;
stage_in / stage_out in the facade). When nothing collects, no child
is built: a child site costs one test of the current call.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from typing import Any

SCHEMA_VERSION = "accl-tpu-trace-v1"

# default host ring capacity (spans); the ring drops OLDEST on overflow
# and counts the drops — mirroring the native ring's contract
DEFAULT_CAPACITY = 65536

# jax.profiler.TraceAnnotation, bound on the first check after JAX is
# imported (the tracer itself never imports JAX: a process that has not
# loaded it has no profiler session to feed)
_Annotation: Any = None


def profiler_collecting() -> bool:
    """True while a JAX profiler session collects host events."""
    global _Annotation
    if _Annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _Annotation = TraceAnnotation
    return _Annotation.is_enabled()


class _NullSpan:
    """Shared no-op span: the disabled-tracing fast path. Reentrant and
    stateless, so one instance serves every call site."""

    __slots__ = ()
    keep = False  # never collected: no children hang off it

    def __bool__(self) -> bool:  # `if sp:` tells a live span from this
        return False

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **_kw) -> "_NullSpan":
        return self

    def begin(self, _name: str, **_kw) -> "_NullSpan":
        return self

    def end(self, **_kw) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    """Context manager measuring one span; emitted into the tracer ring
    on exit. `set()` attaches args discovered mid-span (e.g. the plan a
    device resolved after dispatch). `keep` says whether spans were
    being collected when it opened (the ring retains it, and children
    may hang off it); `annotate` whether a profiler session was (it is
    then also a TraceAnnotation)."""

    __slots__ = ("_tracer", "name", "cat", "track", "args", "_t0", "keep",
                 "_annotate", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, track: str,
                 args: dict, keep: bool = False, annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args
        self._t0 = 0
        self.keep = keep
        self._annotate = annotate
        self._ann = None

    def __enter__(self) -> "_LiveSpan":
        # the span's clock encloses its annotation, as a parent's
        # encloses its children
        self._t0 = time.perf_counter_ns()
        if self._annotate:
            self._ann = _Annotation(f"accl.{self.name}")
            self._ann.__enter__()
        return self

    def set(self, **kw) -> "_LiveSpan":
        self.args.update(kw)
        return self

    def __exit__(self, exc_type, _exc, _tb) -> bool:
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        if self._ann is not None:
            self._ann.set_metadata(**{
                k: v for k, v in self.args.items()
                if isinstance(v, (bool, int, float, str))})
            self._ann.__exit__(None, None, None)
        self._emit(time.perf_counter_ns() - self._t0)
        return False

    def _emit(self, dur: int) -> None:
        self._tracer.emit(self.name, self.cat, self.track,
                          ts_ns=self._t0, dur_ns=dur, args=self.args,
                          keep=self.keep)

    # -- children: the explicit form for hot paths -------------------------

    def begin(self, name: str, **args) -> "_LiveSpan":
        """Open a child phase span now, on this span's track and with
        its `call_id`; close it with `end()`. Only for a span that is
        collected (`keep`)."""
        call_id = self.args.get("call_id")
        if call_id is not None:
            args["call_id"] = call_id
        child = _ChildSpan(self._tracer, name, "phase", self.track, args,
                           True, self._annotate)
        return child.__enter__()

    def end(self, **args) -> None:
        """Close a span opened with `begin()`, attaching `args`."""
        if args:
            self.args.update(args)
        self.__exit__(None, None, None)


class _ChildSpan(_LiveSpan):
    """A child phase span. Built only while spans are collected, so it
    goes to the ring (and the profiler) alone: the observers of the
    always-on layer see the same events whether or not anyone collects."""

    __slots__ = ()

    def _emit(self, dur: int) -> None:
        self._tracer._retain({"name": self.name, "cat": self.cat,
                              "track": self.track, "ts_ns": self._t0,
                              "dur_ns": dur, "args": self.args})


class _CallSpan(_LiveSpan):
    """The span of one facade call: while collected, the thread's
    current call for the duration (children read it with
    `Tracer.current()`)."""

    __slots__ = ("_prev",)

    def __enter__(self) -> "_CallSpan":
        if self.keep:
            local = self._tracer._local
            self._prev = local.call
            local.call = self
        super().__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.keep:
            self._tracer._local.call = self._prev
        return super().__exit__(exc_type, exc, tb)


class _Current(threading.local):
    call: "_CallSpan | None" = None


class Tracer:
    """Thread-safe bounded span ring (drop-oldest, counted drops)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool | None = None):
        if enabled is None:
            enabled = os.environ.get("ACCL_TELEMETRY", "0") not in (
                "", "0", "false", "off")
        self._enabled = bool(enabled)
        self.capacity = int(capacity)
        self._spans: deque = deque()
        self._mu = threading.Lock()
        self.drops = 0
        # observers are stored as an immutable tuple so the hot-path
        # read (`span()`'s predicate, `emit()`'s fan-out) is lock-free;
        # installs/removals copy-on-write under the ring lock
        self._observers: tuple = ()
        self.observer_errors = 0
        self._local = _Current()
        self._call_ids = itertools.count(1)

    # -- switching ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def active(self) -> bool:
        """True when spans are worth building: the ring is enabled, an
        observability observer (metrics registry, flight recorder) is
        installed, or a profiler session collects. Emitters gate arg
        attachment on this, not on `enabled`, so live metrics see the
        plan/prediction keys even when nobody is recording a full
        trace."""
        return self._enabled or bool(self._observers) or profiler_collecting()

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    # -- observers (the always-on observability seam) ----------------------

    def add_observer(self, fn) -> None:
        """Register a callable fed every emitted event (idempotent)."""
        with self._mu:
            if fn not in self._observers:
                self._observers = self._observers + (fn,)

    def remove_observer(self, fn) -> None:
        with self._mu:
            self._observers = tuple(o for o in self._observers if o is not fn)

    def _observe(self, ev: dict) -> None:
        for obs in self._observers:
            try:
                obs(ev)
            except Exception:
                # an observer bug must never take down the data plane;
                # counted so a broken observer is visible, not silent
                self.observer_errors += 1

    # -- emission ----------------------------------------------------------

    def span(self, name: str, cat: str = "call", track: str = "host",
             **args) -> "_NullSpan | _LiveSpan":
        """Start a span context manager. An inactive tracer (ring off,
        no observers, no profiler session) returns the shared no-op
        before touching the arguments."""
        prof = profiler_collecting()
        keep = prof or self._enabled
        if not (keep or self._observers):
            return _NULL_SPAN
        return _LiveSpan(self, name, cat, track, args, keep, prof)

    def call(self, name: str, cat: str = "call",
             track: str = "facade") -> "_NullSpan | _CallSpan":
        """Start the span of one facade call (named by its op): as
        `span()`, plus a `call_id` that increases through the process,
        and, while spans are collected, the thread's `current()` call
        until it closes."""
        prof = profiler_collecting()
        keep = prof or self._enabled
        if not (keep or self._observers):
            return _NULL_SPAN
        # a call made inside another call is part of it: it takes the
        # enclosing call's id, so its children count with that call's
        outer = self._local.call
        cid = (outer.args["call_id"] if outer is not None
               else next(self._call_ids))
        return _CallSpan(self, name, cat, track, {"call_id": cid}, keep,
                         prof)

    def current(self) -> "_CallSpan | None":
        """The call span this thread is inside while spans are being
        collected, else None: the one test a child-span site makes."""
        return self._local.call

    def emit(self, name: str, cat: str, track: str, *, ts_ns: int,
             dur_ns: int, args: dict | None = None,
             keep: bool | None = None) -> None:
        """Record one already-measured span (the direct form used when
        draining native rings or replaying recorded timings). Observers
        see every event at emission; the ring retains it while spans
        are collected (`keep`: whether they were when the span opened;
        by default, whether they are now)."""
        if keep is None:
            keep = self._enabled or profiler_collecting()
        if not (keep or self._observers):
            return
        ev = {
            "name": name,
            "cat": cat,
            "track": track,
            "ts_ns": int(ts_ns),
            "dur_ns": int(dur_ns),
            "args": dict(args or {}),
        }
        if self._observers:
            self._observe(ev)
        if keep:
            self._retain(ev)

    def extend(self, events: list[dict]) -> None:
        """Bulk-append pre-shaped span events (ring discipline applies;
        observers see each event exactly as emit() would feed them)."""
        if not (self._enabled or self._observers):
            return
        if self._observers:
            for ev in events:
                self._observe(ev)
        if self._enabled:
            self._retain(*events)

    def _retain(self, *events: dict) -> None:
        """Append to the ring, dropping (and counting) the oldest when
        full."""
        with self._mu:
            for ev in events:
                if len(self._spans) >= self.capacity:
                    self._spans.popleft()
                    self.drops += 1
                self._spans.append(ev)

    # -- readout -----------------------------------------------------------

    def snapshot(self) -> list[dict]:
        """Non-destructive copy of the current ring contents."""
        with self._mu:
            return list(self._spans)

    def drain(self) -> list[dict]:
        """Remove and return every buffered span."""
        with self._mu:
            out = list(self._spans)
            self._spans.clear()
            return out

    def clear(self) -> None:
        with self._mu:
            self._spans.clear()
            self.drops = 0

    def to_trace(self, meta: dict | None = None) -> dict:
        """Package the current spans as a schema-versioned trace document
        (the on-disk / exchange format every exporter consumes).
        Observers exposing a `trace_meta()` hook (the metrics registry
        snapshot + drift-sentinel report) contribute to the meta, so
        every exported trace carries the live metrics next to its
        spans."""
        m = {"drops": self.drops}
        for obs in self._observers:
            tm = getattr(obs, "trace_meta", None)
            if tm is not None:
                try:
                    m.update(tm())
                except Exception:
                    self.observer_errors += 1
        if meta:
            m.update(meta)
        return {"schema": SCHEMA_VERSION, "meta": m, "spans": self.snapshot()}


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every built-in emitter uses."""
    return _tracer


def enable() -> None:
    _tracer.enable()


def disable() -> None:
    _tracer.disable()
