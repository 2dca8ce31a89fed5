"""Trace export: event-schema validation, Chrome trace-event JSON, and
the predicted-vs-measured residual table.

The trace document (tracer.Tracer.to_trace / accl_log/trace.json) is the
one exchange format; this module turns it into

  - Chrome trace-event JSON (Perfetto / chrome://tracing loadable): one
    named track (tid) per span `track`, complete events with
    microsecond timestamps, span args carried through verbatim;
  - a residual table: every span that carries both a prediction
    (args.predicted_s) and a measurement (dur_ns or args.measured_s)
    contributes |predicted - measured| / measured — the
    mechanically-honest "how wrong is the model" number the r4/r5
    verdicts asked for.

EVENT_SCHEMA is the jsonschema contract the CI telemetry step validates
emitted traces against; tools/accl_trace.py --selftest runs it over the
committed golden trace so the schema and the emitters cannot drift
silently.
"""

from __future__ import annotations

import json
import pathlib

from .tracer import SCHEMA_VERSION

# jsonschema document for one trace file. Span args are an open object
# (emitters attach detail freely) but the keys the residual/feedback
# machinery consumes are typed, so a drifted emitter fails validation
# instead of silently skewing the calibration.
EVENT_SCHEMA: dict = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "accl-tpu trace",
    "type": "object",
    "required": ["schema", "spans"],
    "properties": {
        "schema": {"const": SCHEMA_VERSION},
        # meta stays open, but the observability keys the always-on
        # layer embeds are typed: a drifted registry snapshot or
        # sentinel report fails validation instead of silently shipping
        # a malformed metrics section in every exported trace
        "meta": {
            "type": "object",
            "properties": {
                "metrics": {
                    "type": "object",
                    "required": ["counters", "gauges", "histograms"],
                    "properties": {
                        "counters": {"type": "object"},
                        "gauges": {"type": "object"},
                        # per-series histogram rows are fully typed:
                        # the quantile keys MUST mirror
                        # metrics.QUANTILES via metrics.quantile_key
                        # (test_metrics pins the two against each
                        # other), so adding a quantile without typing
                        # it here fails CI instead of shipping an
                        # untyped tail readout in every trace
                        "histograms": {
                            "type": "object",
                            "additionalProperties": {
                                "type": "array",
                                "items": {
                                    "type": "object",
                                    "required": ["labels", "count",
                                                 "sum", "window"],
                                    "properties": {
                                        "labels": {"type": "object"},
                                        "count": {"type": "integer"},
                                        "sum": {"type": "number"},
                                        "window": {"type": "integer"},
                                        "min": {"type": "number"},
                                        "max": {"type": "number"},
                                        "p50": {"type": "number"},
                                        "p95": {"type": "number"},
                                        "p99": {"type": "number"},
                                        "p99_9": {"type": "number"},
                                    },
                                    "additionalProperties": False,
                                },
                            },
                        },
                    },
                },
                "drift_sentinel": {
                    "type": "object",
                    "required": ["verdict", "flagged"],
                    "properties": {
                        "window": {"type": "integer"},
                        "verdict": {"type": "object"},
                        "flagged": {"type": "array",
                                    "items": {"type": "string"}},
                        "stragglers": {"type": "array"},
                    },
                },
                # per-rank wire-health counter snapshot (the stats2
                # surface: CRC/dup drops, selective-retransmit ack/nack
                # traffic, fault-injection tallies) — the escalation
                # policy's evidence for lossy-link vs dead-rank. Typed
                # so a drifted counter rendering fails validation.
                "wire_health": {
                    "type": "object",
                    "required": ["per_rank", "totals"],
                    "properties": {
                        "per_rank": {
                            "type": "object",
                            "additionalProperties": {
                                "type": "object",
                                "additionalProperties": {
                                    "type": "integer"},
                            },
                        },
                        "totals": {
                            "type": "object",
                            "additionalProperties": {"type": "integer"},
                        },
                    },
                },
            },
        },
        "spans": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["name", "cat", "track", "ts_ns", "dur_ns"],
                "properties": {
                    "name": {"type": "string"},
                    "cat": {
                        "type": "string",
                        # "compute": a timed compute stage next to the
                        # collectives (args.compute_bytes carries the
                        # operand bytes it materializes) — the
                        # ComputeFit calibration samples of the
                        # overlap pipeline (feedback.compute_samples).
                        # "error": the sticky-retcode marker the flight
                        # recorder emits at dump-on-error time
                        # (telemetry.recorder — args.retcode is the
                        # failing call's sticky error word)
                        "enum": ["call", "step", "phase", "sequence",
                                 "native", "compute", "error"],
                    },
                    "track": {"type": "string"},
                    "ts_ns": {"type": "integer", "minimum": 0},
                    "dur_ns": {"type": "integer", "minimum": 0},
                    "args": {
                        "type": "object",
                        "properties": {
                            "op": {"type": "string"},
                            "count": {"type": "integer"},
                            "bytes": {"type": "integer"},
                            "world": {"type": "integer"},
                            "algorithm": {"type": "string"},
                            "protocol": {"type": "string"},
                            "retcode": {"type": "integer"},
                            "detail": {"type": "integer"},
                            "predicted_s": {"type": "number"},
                            "measured_s": {"type": "number"},
                            "coef_messages": {"type": "number"},
                            "coef_bytes": {"type": "number"},
                            "signature": {"type": "string"},
                            "step": {"type": "integer"},
                            "rank": {"type": "integer"},
                            "d_passes": {"type": "integer"},
                            "d_parks": {"type": "integer"},
                            "d_seek_hit": {"type": "integer"},
                            "d_seek_miss": {"type": "integer"},
                            "compute_bytes": {"type": "integer"},
                            # a facade call and its child phases
                            # (plan, lower, launch, wait, place,
                            # stage_in, stage_out) share its call_id
                            "call_id": {"type": "integer"},
                            "hit": {"type": "boolean"},
                            # a lowering-cache miss: the compiler's ring
                            "ring_order": {"type": "array",
                                           "items": {"type": "integer"}},
                            "ring_detours": {"type": "integer"},
                            "copied": {"type": "boolean"},
                            # the deadline-miss marker (resilience
                            # host-side verdicts, recorder
                            # .on_deadline_miss): a cat "error" span
                            # with no sticky retcode carries these
                            "deadline_missed": {"type": "boolean"},
                            "deadline_s": {"type": "number"},
                            "suspect_rank": {"type": "integer"},
                        },
                        "additionalProperties": True,
                    },
                },
            },
        },
    },
}


def validate_trace(trace: dict) -> None:
    """Raise jsonschema.ValidationError when the trace violates the
    event schema (the CI telemetry gate)."""
    import jsonschema

    jsonschema.validate(trace, EVENT_SCHEMA)


def to_chrome(trace: dict) -> dict:
    """Chrome trace-event JSON: one pid, one tid per span track (named
    via thread_name metadata so Perfetto labels the rows), complete (X)
    events in microseconds. Zero-duration spans (recorded sequence
    steps) are stretched to 1 ns so they stay clickable."""
    tracks: list[str] = []
    index: dict[str, int] = {}
    for sp in trace.get("spans", []):
        t = sp["track"]
        if t not in index:
            index[t] = len(tracks)
            tracks.append(t)
    events = [
        {
            "ph": "M",
            "pid": 0,
            "tid": i,
            "name": "thread_name",
            "args": {"name": t},
        }
        for i, t in enumerate(tracks)
    ]
    for sp in trace.get("spans", []):
        events.append({
            "ph": "X",
            "pid": 0,
            "tid": index[sp["track"]],
            "name": sp["name"],
            "cat": sp["cat"],
            "ts": sp["ts_ns"] / 1e3,
            "dur": max(sp["dur_ns"], 1) / 1e3,
            "args": sp.get("args", {}),
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"schema": trace.get("schema", SCHEMA_VERSION),
                      "meta": trace.get("meta", {})},
    }


def measured_seconds(span: dict) -> float:
    """A span's measured wall seconds: explicit args.measured_s when the
    emitter recorded one (native spans), else the span duration.
    Partially-populated spans (hand-built fixtures, truncated dumps)
    degrade to 0.0 — "no measurement" — rather than raising."""
    args = span.get("args") or {}
    try:
        if "measured_s" in args:
            return float(args["measured_s"])
        return float(span.get("dur_ns", 0)) / 1e9
    except (TypeError, ValueError):
        return 0.0


def residual_rows(trace: dict) -> list[dict]:
    """All spans carrying BOTH a prediction and a nonzero measurement,
    as rows of (name, track, predicted_s, measured_s, rel_err). Robust
    against empty and partially-populated traces: a span with no
    `predicted_s`, a non-numeric prediction, or a zero/absent
    measurement contributes no row (it has no residual to claim) —
    never an exception."""
    rows = []
    for sp in trace.get("spans", []):
        if not isinstance(sp, dict):
            continue
        args = sp.get("args") or {}
        if "predicted_s" not in args:
            continue
        if args.get("dispatch_only"):
            # an async span closed at dispatch: its duration is the
            # host seam, not the collective the prediction models —
            # comparing them would corrupt the residual table
            continue
        if sp.get("cat") == "error":
            # dump-on-error markers (sticky retcodes, deadline misses)
            # carry the failing call's predicted/elapsed pair as
            # DIAGNOSTIC detail — a wedged wait's elapsed time is not a
            # measurement of the collective, and one miss would skew
            # every residual median (and any band armed from it)
            continue
        meas = measured_seconds(sp)
        if meas <= 0:
            continue
        try:
            pred = float(args["predicted_s"])
        except (TypeError, ValueError):
            continue
        rows.append({
            "name": sp.get("name", "?"),
            "track": sp.get("track", "?"),
            "predicted_s": pred,
            "measured_s": meas,
            "rel_err": abs(pred - meas) / meas,
        })
    return rows


def median(xs: list[float]) -> float:
    if not xs:
        return float("nan")
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def residual_summary(rows: list[dict]) -> dict:
    """Aggregate the residual table: overall and per-op median relative
    error (|predicted - measured| / measured). An empty table (a trace
    from a run with no predictions, or drained before any call
    completed) yields the well-typed empty summary — `median_rel_err`
    is None, never NaN (NaN round-trips as Infinity-adjacent garbage
    through strict JSON consumers) and never an exception."""
    if not rows:
        return {"rows": 0, "median_rel_err": None,
                "per_op_median_rel_err": {}}
    by_op: dict[str, list[float]] = {}
    for r in rows:
        by_op.setdefault(r["name"], []).append(r["rel_err"])
    return {
        "rows": len(rows),
        "median_rel_err": median([r["rel_err"] for r in rows]),
        "per_op_median_rel_err": {
            op: median(errs) for op, errs in sorted(by_op.items())
        },
    }


# The wire-health counters of the stats2 surface that describe FAULT
# REPAIR activity — damage actually observed and absorbed (corrupt
# frames dropped, duplicates deduped, frames actually resent).  This is
# the resilience manager's lossy-vs-dark evidence, and deliberately
# EXCLUDES the nack/ack traffic counters: a survivor nacks a dead
# rank's silence (and a stalled healthy peer) too, so "someone is
# waiting" counters climb in BOTH cases and cannot distinguish them.
# Kept here — next to the export that renders them — so the exporter
# and the consumer read one list.
WIRE_FAULT_KEYS = (
    "crc_drops", "dup_drops", "retx_sent", "retx_miss",
)


def wire_health_report(stats_by_rank: dict) -> dict:
    """Normalize per-rank wire-health snapshots (EmuRank.wire_stats /
    TPUDevice.wire_stats dicts keyed by rank) into the trace-meta
    `wire_health` shape: string-keyed per-rank rows plus a totals row.
    Non-integer values and unknown keys pass through int-coerced /
    verbatim so a newer native counter never breaks an older exporter;
    an empty input yields the well-typed empty report."""
    per_rank: dict = {}
    totals: dict = {}
    for rank in sorted(stats_by_rank):
        row = {}
        for k, v in (stats_by_rank[rank] or {}).items():
            try:
                iv = int(v)
            except (TypeError, ValueError):
                continue
            row[str(k)] = iv
            totals[str(k)] = totals.get(str(k), 0) + iv
        per_rank[str(rank)] = row
    return {"per_rank": per_rank, "totals": totals}


def write_trace(path, trace: dict) -> None:
    pathlib.Path(path).write_text(json.dumps(trace, indent=1))

