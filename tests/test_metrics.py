"""Always-on observability layer: the streaming metrics registry, the
drift sentinel, and the flight recorder (accl_tpu/telemetry/metrics.py
+ recorder.py), plus the tracer observer seam they ride.

The contract under test (docs/observability.md "Live metrics"):
  - metrics are fed at span-EMISSION time through Tracer observers —
    live with the ring disabled, keyed by (op, algorithm, protocol,
    world), bounded, Prometheus-exposable, snapshot-embeddable;
  - the drift sentinel arms a frozen reference band from the first
    in-regime predicted-vs-measured residuals, flags a regime change
    within one window, stays quiet on a stable run, and attributes
    stragglers from per-rank feeds;
  - the flight recorder keeps the last N spans per track and freezes a
    self-contained post-mortem on a sticky retcode.
"""

import json
import threading

import pytest

from accl_tpu import telemetry
from accl_tpu.telemetry.metrics import (
    DriftSentinel,
    Histogram,
    MetricsObserver,
    MetricsRegistry,
    replay_trace,
)
from accl_tpu.telemetry.recorder import FlightRecorder
from accl_tpu.telemetry.tracer import Tracer


def _call_event(op="allreduce", dur_ns=1_000_000, predicted_s=None,
                retcode=0, cat="call", rank=None, count=1024, world=8,
                measured_s=None):
    args = {"op": op, "count": count, "bytes": count * 4, "world": world,
            "algorithm": "EAGER_RING_RS_AG", "protocol": "EAGER",
            "retcode": retcode}
    if predicted_s is not None:
        args["predicted_s"] = predicted_s
    if measured_s is not None:
        args["measured_s"] = measured_s
    if rank is not None:
        args["rank"] = rank
    return {"name": op, "cat": cat, "track": "facade", "ts_ns": 0,
            "dur_ns": dur_ns, "args": args}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_series_keyed_by_labels():
    reg = MetricsRegistry()
    reg.counter("accl_calls_total", op="allreduce", world=8).inc()
    reg.counter("accl_calls_total", op="allreduce", world=8).inc()
    reg.counter("accl_calls_total", op="bcast", world=8).inc()
    snap = reg.snapshot()
    rows = snap["counters"]["accl_calls_total"]
    by_op = {r["labels"]["op"]: r["value"] for r in rows}
    assert by_op == {"allreduce": 2.0, "bcast": 1.0}


def test_histogram_bounded_window_quantiles_and_cumulative():
    h = Histogram(window=10)
    for i in range(100):
        h.observe(float(i))
    snap = h.snapshot()
    # cumulative stats are exact over ALL observations...
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(sum(range(100)))
    assert snap["min"] == 0.0 and snap["max"] == 99.0
    # ...while the quantiles stream over the bounded window (last 10)
    assert snap["window"] == 10
    assert 90.0 <= snap["p50"] <= 99.0
    assert snap["p95"] >= snap["p50"]
    assert snap["p99"] >= snap["p95"]


def test_histogram_empty_snapshot_is_well_typed():
    snap = Histogram().snapshot()
    assert snap == {"count": 0, "sum": 0.0, "window": 0}


def test_p99_9_is_window_max_nearest_rank():
    """The serving-SLO tail row: over the 512-sample default window,
    nearest-rank p99.9 (ceil(0.999 * 512) = 512) IS the window max —
    the honest worst-observed-step readout, keyed p99_9 so it can
    never collide with p99 (int(q*100) maps both to 99)."""
    from accl_tpu.telemetry.metrics import quantile_key

    assert quantile_key(0.999) == "p99_9"
    assert quantile_key(0.99) == "p99"
    h = Histogram()  # default window: 512
    for i in range(1000):
        h.observe(float(i))
    snap = h.snapshot()
    assert snap["window"] == 512
    assert snap["p99_9"] == 999.0 == snap["max"]
    assert snap["p99"] <= snap["p99_9"]
    # exposed in Prometheus text as quantile="0.999"
    reg = MetricsRegistry()
    reg.histogram("accl_serve_step_seconds", mode="fused").observe(0.25)
    assert ('accl_serve_step_seconds{mode="fused",quantile="0.999"} 0.25'
            in reg.expose_text().splitlines())


def test_event_schema_pins_registry_quantile_keys():
    """The embedded-trace-meta schema and the live registry must agree
    on the histogram row shape: every QUANTILES key (via quantile_key)
    appears as a typed schema property, the schema admits a real
    snapshot row, and additionalProperties=False means a quantile
    added to one side without the other fails here."""
    from accl_tpu.telemetry.export import EVENT_SCHEMA
    from accl_tpu.telemetry.metrics import QUANTILES, quantile_key

    row_schema = (EVENT_SCHEMA["properties"]["meta"]["properties"]
                  ["metrics"]["properties"]["histograms"]
                  ["additionalProperties"]["items"])
    props = set(row_schema["properties"])
    qkeys = {quantile_key(q) for q in QUANTILES}
    assert qkeys <= props, f"schema missing {qkeys - props}"
    assert row_schema["additionalProperties"] is False
    extra = props - qkeys - {"labels", "count", "sum", "window",
                             "min", "max"}
    assert not extra, f"schema rows carry unpinned keys {extra}"
    h = Histogram()
    h.observe(1.0)
    row = {"labels": {"op": "allreduce"}, **h.snapshot()}
    assert set(row) <= props


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("accl_calls_total", op="allreduce",
                algorithm="RING", protocol="EAGER", world=8).inc(3)
    reg.gauge("accl_ring_drops", track="host").set(2)
    reg.histogram("accl_call_seconds", op="allreduce").observe(0.5)
    text = reg.expose_text()
    lines = text.splitlines()
    assert "# TYPE accl_calls_total counter" in lines
    assert ('accl_calls_total{algorithm="RING",op="allreduce",'
            'protocol="EAGER",world="8"} 3') in lines
    assert "# TYPE accl_ring_drops gauge" in lines
    assert "# TYPE accl_call_seconds summary" in lines
    assert 'accl_call_seconds{op="allreduce",quantile="0.5"} 0.5' in lines
    assert 'accl_call_seconds_count{op="allreduce"} 1' in lines
    # label values escape quotes/backslashes/newlines
    reg.counter("x", detail='say "hi"\n').inc()
    assert 'x{detail="say \\"hi\\"\\n"} 1' in reg.expose_text()


def test_registry_thread_safety_smoke():
    reg = MetricsRegistry()

    def worker():
        for _ in range(1000):
            reg.counter("n", op="allreduce").inc()
            reg.histogram("h", op="allreduce").observe(1.0)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("n", op="allreduce").value == 4000
    assert reg.histogram("h", op="allreduce").count == 4000


# ---------------------------------------------------------------------------
# label-cardinality guard (the tenant-label satellite)
# ---------------------------------------------------------------------------


def test_guarded_label_overflows_into_other_bucket():
    """First-come admission up to the cap; later tenant ids collapse
    into `other` (observations still counted — attribution is what
    saturates), and the overflow is itself a visible series."""
    reg = MetricsRegistry(label_value_cap=2)
    reg.counter("accl_tenant_dispatches_total", tenant="a").inc()
    reg.counter("accl_tenant_dispatches_total", tenant="b").inc()
    reg.counter("accl_tenant_dispatches_total", tenant="c").inc()
    reg.counter("accl_tenant_dispatches_total", tenant="d").inc(2)
    snap = reg.snapshot()
    by_tenant = {r["labels"]["tenant"]: r["value"]
                 for r in snap["counters"]["accl_tenant_dispatches_total"]}
    assert by_tenant == {"a": 1.0, "b": 1.0, "other": 3.0}
    (ovf,) = snap["counters"]["accl_label_overflow_total"]
    assert ovf["labels"] == {"label": "tenant"} and ovf["value"] == 2.0
    # histograms and gauges ride the same guard
    reg.histogram("accl_tenant_dispatch_seconds", tenant="zzz") \
        .observe(1.0)
    reg.gauge("accl_tenant_depth", tenant="zzz").set(1)
    snap = reg.snapshot()
    (h,) = snap["histograms"]["accl_tenant_dispatch_seconds"]
    assert h["labels"]["tenant"] == "other"
    (g,) = snap["gauges"]["accl_tenant_depth"]
    assert g["labels"]["tenant"] == "other"


def test_guard_bounds_hostile_id_stream():
    """10x the cap in distinct ids mints exactly cap+1 series."""
    reg = MetricsRegistry(label_value_cap=8)
    for i in range(80):
        reg.counter("accl_tenant_dispatches_total",
                    tenant=f"t{i:03d}").inc()
    rows = reg.snapshot()["counters"]["accl_tenant_dispatches_total"]
    assert len(rows) == 9  # 8 attributed + `other`
    (other,) = [r for r in rows if r["labels"]["tenant"] == "other"]
    assert other["value"] == 72.0
    # an attributed value keeps its own series afterwards
    reg.counter("accl_tenant_dispatches_total", tenant="t000").inc()
    rows = reg.snapshot()["counters"]["accl_tenant_dispatches_total"]
    (t0,) = [r for r in rows if r["labels"]["tenant"] == "t000"]
    assert t0["value"] == 2.0


def test_guard_leaves_closed_label_sets_alone():
    """Only GUARDED_LABEL_KEYS are capped: op/world/… draw from closed
    sets and keep full attribution past any cap."""
    reg = MetricsRegistry(label_value_cap=1)
    for i in range(5):
        reg.counter("accl_calls_total", op=f"op{i}").inc()
    rows = reg.snapshot()["counters"]["accl_calls_total"]
    assert {r["labels"]["op"] for r in rows} == \
        {f"op{i}" for i in range(5)}


def test_guard_explicit_other_and_env_cap(monkeypatch):
    from accl_tpu.telemetry.metrics import (
        DEFAULT_LABEL_VALUE_CAP,
        _label_value_cap,
    )

    reg = MetricsRegistry(label_value_cap=1)
    # writing to the bucket directly is not an overflow event
    reg.counter("accl_tenant_dispatches_total", tenant="other").inc()
    assert "accl_label_overflow_total" not in \
        reg.snapshot()["counters"]
    # ... nor an admission: the one slot is still free
    reg.counter("accl_tenant_dispatches_total", tenant="a").inc()
    assert {r["labels"]["tenant"] for r in reg.snapshot()["counters"][
        "accl_tenant_dispatches_total"]} == {"other", "a"}
    assert _label_value_cap() == DEFAULT_LABEL_VALUE_CAP
    monkeypatch.setenv("ACCL_METRICS_LABEL_CAP", "3")
    assert _label_value_cap() == 3
    assert MetricsRegistry()._label_value_cap == 3
    monkeypatch.setenv("ACCL_METRICS_LABEL_CAP", "0")
    assert _label_value_cap() == 1  # clamped
    monkeypatch.setenv("ACCL_METRICS_LABEL_CAP", "junk")
    assert _label_value_cap() == DEFAULT_LABEL_VALUE_CAP
    # clear() resets the admitted set with the series
    reg2 = MetricsRegistry(label_value_cap=1)
    reg2.counter("n", tenant="a").inc()
    reg2.clear()
    reg2.counter("n", tenant="b").inc()
    (row,) = reg2.snapshot()["counters"]["n"]
    assert row["labels"] == {"tenant": "b"}


# ---------------------------------------------------------------------------
# the span -> metrics observer rule
# ---------------------------------------------------------------------------


def test_observer_lifts_call_spans_into_series():
    obs = MetricsObserver(MetricsRegistry(), DriftSentinel())
    obs(_call_event(dur_ns=2_000_000, predicted_s=1e-3))
    obs(_call_event(dur_ns=4_000_000, retcode=0x800))
    snap = obs.registry.snapshot()
    calls = snap["counters"]["accl_calls_total"][0]
    assert calls["value"] == 2.0
    assert calls["labels"] == {"op": "allreduce",
                               "algorithm": "EAGER_RING_RS_AG",
                               "protocol": "EAGER", "world": "8"}
    assert snap["counters"]["accl_bytes_total"][0]["value"] == 2 * 4096.0
    h = snap["histograms"]["accl_call_seconds"][0]
    assert h["count"] == 2 and h["p50"] == pytest.approx(2e-3)
    errs = snap["counters"]["accl_errors_total"][0]
    assert errs["labels"] == {"op": "allreduce", "retcode": "2048"}
    # the predicted/measured pair fed the sentinel
    v = obs.sentinel.verdict()["allreduce"]
    assert v["n"] == 1 and v["median_rel_err"] == pytest.approx(0.5)


def test_observer_counts_fused_steps():
    """Fused-batch steps never appear as calls (one dispatch covers
    the batch): the step counter keeps their op mix visible live."""
    obs = MetricsObserver(MetricsRegistry(), DriftSentinel())
    ev = _call_event(op="reduce_scatter", cat="step", dur_ns=0)
    obs(ev)
    obs(ev)
    snap = obs.registry.snapshot()
    (row,) = snap["counters"]["accl_steps_total"]
    assert row["value"] == 2.0 and row["labels"]["op"] == "reduce_scatter"
    assert "accl_calls_total" not in snap["counters"]


def test_observer_skips_dispatch_only_measurements():
    obs = MetricsObserver(MetricsRegistry(), DriftSentinel())
    ev = _call_event(predicted_s=1e-3)
    ev["args"]["dispatch_only"] = True
    obs(ev)
    snap = obs.registry.snapshot()
    # counted as a call, but its host-seam duration is NOT a latency
    # sample and must not feed the histogram or the sentinel
    assert snap["counters"]["accl_calls_total"][0]["value"] == 1.0
    assert "accl_call_seconds" not in snap["histograms"]
    assert obs.sentinel.verdict() == {}


def test_observer_series_follow_a_cleared_registry():
    """The observer keeps its call series' handles; a cleared registry
    gets fresh ones, so no sample lands in a series nobody can read."""
    reg = MetricsRegistry()
    obs = MetricsObserver(reg, DriftSentinel())
    obs(_call_event())
    obs(_call_event())
    reg.clear()
    obs(_call_event(dur_ns=3_000_000))
    snap = reg.snapshot()
    (calls,) = snap["counters"]["accl_calls_total"]
    (hist,) = snap["histograms"]["accl_call_seconds"]
    assert calls["value"] == 1.0
    assert hist["count"] == 1 and hist["sum"] == pytest.approx(3e-3)


def test_observer_feeds_straggler_attribution_from_native_ranks():
    obs = MetricsObserver(MetricsRegistry(), DriftSentinel())
    for _ in range(4):
        for rank in range(4):
            dur = 5_000_000 if rank == 2 else 1_000_000
            obs(_call_event(cat="native", rank=rank, dur_ns=dur))
    (wave,) = obs.sentinel.straggler_report()
    assert wave["op"] == "allreduce" and wave["ranks"] == 4
    assert wave["straggler_rank"] == 2
    assert wave["skew"] == pytest.approx(5.0)


def test_tracer_observer_seam_live_with_ring_disabled():
    """The always-on posture: observers make span() live and receive
    every event at emission, while the disabled ring retains nothing;
    to_trace embeds the registry snapshot + sentinel report."""
    tr = Tracer(enabled=False)
    assert not tr.active
    obs = MetricsObserver(MetricsRegistry(), DriftSentinel())
    tr.add_observer(obs)
    assert tr.active and not tr.enabled
    with tr.span("allreduce", cat="call", track="facade",
                 op="allreduce", world=4) as sp:
        sp.set(algorithm="RING", protocol="EAGER")
    assert tr.snapshot() == []  # ring stayed off
    snap = obs.registry.snapshot()
    assert snap["counters"]["accl_calls_total"][0]["value"] == 1.0
    doc = tr.to_trace({"world": 4})
    assert doc["meta"]["metrics"]["counters"]["accl_calls_total"]
    assert "drift_sentinel" in doc["meta"]
    tr.remove_observer(obs)
    assert not tr.active
    assert tr.span("x", cat="call", track="t") is tr.span(
        "y", cat="call", track="t")  # back to the shared no-op


def test_observer_exception_counted_never_raises():
    tr = Tracer(enabled=True)

    def broken(ev):
        raise RuntimeError("observer bug")

    tr.add_observer(broken)
    tr.emit("x", "call", "t", ts_ns=0, dur_ns=1, args={})
    assert tr.observer_errors == 1
    assert [s["name"] for s in tr.snapshot()] == ["x"]  # ring unharmed


def test_replay_trace_is_the_offline_twin():
    """tools/accl_trace.py --metrics rebuilds the registry from an
    exported trace through the SAME rule the live observer runs."""
    spans = [_call_event(), _call_event(op="bcast")]
    live = MetricsObserver(MetricsRegistry(), DriftSentinel())
    for s in spans:
        live(s)
    replayed = replay_trace({"spans": spans})
    assert replayed.registry.snapshot()["counters"] == \
        live.registry.snapshot()["counters"]


# ---------------------------------------------------------------------------
# drift sentinel
# ---------------------------------------------------------------------------


def test_sentinel_arms_reference_then_flags_regime_change():
    s = DriftSentinel(window=16, min_samples=8, band_factor=3.0,
                      band_floor=0.25)
    # stable regime: predictions ~10% off
    for _ in range(12):
        s.feed("allreduce", predicted_s=1e-3, measured_s=1.1e-3)
    v = v0 = s.verdict()["allreduce"]
    assert v["armed"] and v["in_band"]
    assert v["reference"] == pytest.approx(0.0909, rel=1e-2)
    assert s.flagged() == []
    # regime change: the link got 5x slower, predictions are stale
    for _ in range(16):
        s.feed("allreduce", predicted_s=1e-3, measured_s=5e-3)
    v = s.verdict()["allreduce"]
    assert v["reference"] == v0["reference"]  # frozen at arming
    assert not v["in_band"]
    assert s.flagged() == ["allreduce"]


def test_sentinel_quiet_on_stable_run():
    """Zero false positives: residuals drawn from the reference regime
    (including jitter far past the reference median, as long as the
    MEDIAN stays in band) never flag."""
    s = DriftSentinel(window=32, min_samples=8)
    meas = [1.05e-3, 1.2e-3, 0.9e-3, 1.1e-3]
    for i in range(200):
        s.feed("allreduce", 1e-3, meas[i % len(meas)])
    assert s.flagged() == []
    assert s.verdict()["allreduce"]["in_band"]


def test_sentinel_band_floor_tolerates_tight_reference():
    """A near-perfect reference (median residual ~1%) must not turn
    ordinary noise into drift: the absolute floor keeps the band open."""
    s = DriftSentinel(window=16, min_samples=4, band_factor=3.0,
                      band_floor=0.25)
    for _ in range(8):
        s.feed("bcast", 1e-3, 1.01e-3)
    for _ in range(8):
        s.feed("bcast", 1e-3, 1.2e-3)  # 20% < 1% + floor
    assert s.flagged() == []


def test_sentinel_unarmed_below_min_samples():
    s = DriftSentinel(min_samples=8)
    for _ in range(5):
        s.feed("gather", 1e-3, 9e-3)
    v = s.verdict()["gather"]
    assert v["armed"] is False and "in_band" not in v
    assert s.flagged() == []  # no reference, no claim


def test_sentinel_report_shape_and_reset():
    s = DriftSentinel(window=8, min_samples=2)
    s.feed("allreduce", 1e-3, 2e-3)
    s.feed("allreduce", 1e-3, 2e-3)
    s.feed_rank("allreduce", 1024, 0, 1e-3)
    s.feed_rank("allreduce", 1024, 1, 2e-3)
    rep = s.report()
    assert set(rep) == {"window", "min_samples", "band_factor",
                        "band_floor", "verdict", "flagged", "stragglers"}
    assert rep["stragglers"][0]["straggler_rank"] == 1
    json.dumps(rep)  # JSON-serializable as embedded
    s.reset()
    assert s.verdict() == {} and s.straggler_report() == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_bounded_per_track():
    fr = FlightRecorder(track_capacity=4)
    for i in range(10):
        fr({"name": f"a{i}", "cat": "call", "track": "facade",
            "ts_ns": i, "dur_ns": 1, "args": {}})
        fr({"name": f"b{i}", "cat": "native", "track": "emu/r0",
            "ts_ns": 100 + i, "dur_ns": 1, "args": {}})
    spans = fr.snapshot()
    assert len(spans) == 8  # 4 newest per track
    assert [s["name"] for s in spans if s["track"] == "facade"] == \
        ["a6", "a7", "a8", "a9"]
    assert spans == sorted(spans, key=lambda s: s["ts_ns"])


def test_flight_recorder_trace_doc_is_schema_valid():
    pytest.importorskip("jsonschema")
    fr = FlightRecorder(track_capacity=8)
    fr(_call_event())
    doc = fr.to_trace(reason="unit test")
    assert doc["meta"]["flight_recorder"] is True
    assert doc["meta"]["reason"] == "unit test"
    telemetry.validate_trace(doc)


def test_notify_sticky_retcode_emits_marker_and_freezes(monkeypatch,
                                                        tmp_path):
    """The errors.notify_sticky_retcode seam end to end against the
    process-wide recorder: marker span through the tracer (metrics see
    it), rings frozen, artifact written under ACCL_FLIGHT_DIR."""
    from accl_tpu.errors import notify_sticky_retcode
    from accl_tpu.telemetry import recorder as trec

    assert trec.armed()  # always-on default
    monkeypatch.setenv("ACCL_FLIGHT_DIR", str(tmp_path))
    trec.get_recorder().clear()
    doc = notify_sticky_retcode("allreduce", 0x20, rank=3, count=512)
    assert doc is not None
    (err,) = [s for s in doc["spans"] if s["cat"] == "error"]
    assert err["name"] == "allreduce" and err["track"] == "emu/r3"
    assert err["args"] == {"retcode": 0x20, "rank": 3, "count": 512}
    assert "0x20" in doc["meta"]["reason"]
    assert trec.last_error_trace() is doc
    on_disk = json.loads((tmp_path / "flight_last_error.json").read_text())
    assert on_disk["meta"]["reason"] == doc["meta"]["reason"]


def test_request_completion_with_retcode_freezes_post_mortem():
    """The sticky-error-word write point (BaseRequest.complete) is the
    dump trigger — whether or not the caller ever check()s."""
    from accl_tpu.request import BaseRequest
    from accl_tpu.telemetry import recorder as trec

    trec.get_recorder().clear()
    req = BaseRequest("reduce_scatter")
    req.running()
    req.complete(0x104)
    doc = trec.last_error_trace()
    assert doc is not None
    (err,) = [s for s in doc["spans"] if s["cat"] == "error"]
    assert err["name"] == "reduce_scatter"
    assert err["args"]["retcode"] == 0x104
