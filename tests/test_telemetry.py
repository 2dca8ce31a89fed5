"""Telemetry subsystem tests: the native trace ring (including under
wire faults), the host tracer, the Chrome/Perfetto export + event
schema, and the measured-vs-predicted feedback loop.

The native-ring fault cases are the satellite-4 coverage: a wedged
call's span must carry its retcode AND the deferred-head-mismatch fault
code the RECEIVE_TIMEOUT detail surfaces (runtime.cpp note_defer_locked
-> execute timeout path -> record_span), and ring overflow must drop
the OLDEST spans, count them, and never crash the data plane.
"""

import json

import numpy as np
import pytest

from accl_tpu import ACCLError, CallOptions, ReduceFunction
from accl_tpu.constants import (
    CfgFunc,
    ErrorCode,
    Operation,
    from_numpy_dtype,
    logp_allgather_max_bytes,
    logp_allreduce_max_bytes,
)
from accl_tpu.device.emu_device import EmuWorld
from accl_tpu import telemetry
from accl_tpu.telemetry import native as tnative
from accl_tpu.telemetry.tracer import Tracer

F32 = from_numpy_dtype(np.dtype(np.float32))
RNG = np.random.default_rng(42)


@pytest.fixture
def fault_env(monkeypatch):
    """Set/clear native-runtime env levers around one test (read at
    runtime creation)."""
    def set_env(**kv):
        for k, v in kv.items():
            monkeypatch.setenv(k, str(v))
    yield set_env


@pytest.fixture
def tracer():
    """A fresh, enabled, process-global tracer; restored after."""
    tr = telemetry.get_tracer()
    was = tr.enabled
    tr.clear()
    tr.enable()
    yield tr
    tr.clear()
    if not was:
        tr.disable()


# ---------------------------------------------------------------------------
# native trace ring
# ---------------------------------------------------------------------------


def test_native_ring_records_completed_calls(fault_env):
    """Every completed call lands one span: opcode, bytes, monotonic
    start/end, retcode 0, and counter deltas. Tracing off (the default)
    records nothing."""
    fault_env(ACCL_RT_TRACE=1)
    w = EmuWorld(2, max_eager=4096, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            x = np.ones(512, np.float32)
            out = np.zeros(512, np.float32)
            rank.allreduce(x, out, 512, ReduceFunction.SUM)
            rank.bcast(x, 512, root=0)
        w.run(body)
        spans, dropped = w.ranks[0].trace_read()
    finally:
        w.close()
    assert dropped == 0
    ops = [s["opcode"] for s in spans]
    assert int(Operation.allreduce) in ops and int(Operation.bcast) in ops
    ar = spans[ops.index(int(Operation.allreduce))]
    assert ar["retcode"] == 0 and ar["detail"] == 0
    assert ar["bytes"] == 512 * 4 and ar["count"] == 512
    assert ar["end_ns"] > ar["start_ns"]
    assert ar["d_passes"] >= 1  # at least one execute pass happened


def test_native_ring_disabled_is_empty():
    w = EmuWorld(2, max_eager=4096, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            rank.barrier()
        w.run(body)
        spans, dropped = w.ranks[0].trace_read()
    finally:
        w.close()
    assert spans == [] and dropped == 0


def test_native_ring_overflow_drops_oldest_never_crashes(fault_env):
    """Satellite-4 overflow case: with a 4-slot ring and 10 completed
    copies, the drop counter says 6, exactly 4 spans survive, and they
    are the NEWEST 4 (oldest dropped first)."""
    fault_env(ACCL_RT_TRACE=1, ACCL_RT_TRACE_CAP=4)
    w = EmuWorld(2, max_eager=4096, rx_buf_bytes=4096)
    try:
        r0 = w.ranks[0]
        src = np.arange(16, dtype=np.float32)
        dst = np.zeros(16, np.float32)
        for k in range(10):
            r0.copy(src, dst, k + 1)  # count encodes the call's index
        spans, dropped = r0.trace_read()
    finally:
        w.close()
    assert dropped == 6
    assert len(spans) == 4
    # oldest-first drain of the newest four calls (counts 7, 8, 9, 10)
    assert [s["count"] for s in spans] == [7, 8, 9, 10]


def test_wedged_call_span_carries_retcode_and_fault_counters(fault_env):
    """Satellite 4 x ACCL_RT_FAULT_*: a recv that dies mid-message
    (delayed tail outlives its deadline) must complete with
    RECEIVE_TIMEOUT and its span must carry that retcode plus the
    park-heavy counter signature of the wedge."""
    fault_env(ACCL_RT_TRACE=1, ACCL_RT_FAULT_DELAY_TAIL_MS=700)
    rx_buf = 256
    count = (3 * rx_buf) // 4  # 3 wire segments
    m1 = RNG.standard_normal(count).astype(np.float32)
    w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=rx_buf)
    try:
        def body(rank, i):
            import time

            if i == 1:
                rank.send(m1.copy(), count, dst=0, tag=5)  # tail delayed
                time.sleep(1.0)
                return None
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=300))
            buf = np.zeros(count, np.float32)
            h = rank.start(CallOptions(scenario=Operation.recv, count=count,
                                       root_src_dst=1, tag=5,
                                       data_type=F32), res=buf)
            with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                rank.wait(h)
            return None

        w.run(body)
        spans, _ = w.ranks[0].trace_read()
    finally:
        w.close()
    recvs = [s for s in spans if s["opcode"] == int(Operation.recv)]
    assert len(recvs) == 1
    wedged = recvs[0]
    assert wedged["retcode"] & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
    # the wedge parked the sequencer while waiting on the delayed tail
    assert wedged["d_parks"] >= 1
    assert wedged["end_ns"] - wedged["start_ns"] >= 250e6  # ~the deadline


def test_wedged_span_carries_deferred_mismatch_detail(fault_env):
    """Satellite 4 x satellite 1: a strict collective recv meeting a
    young MISMATCHED head (another message's head on the same link)
    defers (NOT_READY) instead of erroring; when the call then times
    out, its span must carry the RECEIVE_TIMEOUT retcode AND the
    original fault code the mismatch would have raised
    (DMA_SIZE_ERROR here: message-length mismatch)."""
    fault_env(ACCL_RT_TRACE=1)
    c_p2p, c_bcast = 256, 128  # different msg_bytes on the same link
    w = EmuWorld(2, max_eager=4096, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            if i == 1:
                # the p2p head lands first on r0's link; the bcast
                # payload queues behind it at the next seqns
                rank.send(np.ones(c_p2p, np.float32), c_p2p, dst=0, tag=9)
                rank.bcast(np.ones(c_bcast, np.float32), c_bcast, root=1)
                return None
            # timeout (150 ms) well inside the claimable-head grace
            # window (250 ms): every pass defers on the mismatched
            # young head, then the deadline converts the defer into
            # RECEIVE_TIMEOUT (a pass landing past the grace window
            # would fail fast with DMA_SIZE_ERROR instead — the margin
            # keeps a starved CI scheduler from flipping the outcome)
            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=150))
            buf = np.zeros(c_bcast, np.float32)
            h = rank.start(CallOptions(scenario=Operation.bcast,
                                       count=c_bcast, root_src_dst=1,
                                       data_type=F32), op0=buf)
            with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                rank.wait(h)
            return None

        w.run(body)
        spans, _ = w.ranks[0].trace_read()
    finally:
        w.close()
    bcasts = [s for s in spans if s["opcode"] == int(Operation.bcast)]
    assert len(bcasts) == 1
    wedged = bcasts[0]
    assert wedged["retcode"] & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
    assert wedged["detail"] == int(ErrorCode.DMA_SIZE_ERROR)


# ---------------------------------------------------------------------------
# native span lifting (telemetry.native)
# ---------------------------------------------------------------------------


def test_drain_world_attaches_plans_and_predictions(fault_env):
    fault_env(ACCL_RT_TRACE=1)
    from accl_tpu.sequencer.timing import LinkParams

    link = LinkParams(alpha=1e-5, beta=1e9)
    w = EmuWorld(4, max_eager=4096, rx_buf_bytes=4096)
    try:
        def body(rank, i):
            x = np.ones(1024, np.float32)
            out = np.zeros(1024, np.float32)
            rank.allreduce(x, out, 1024, ReduceFunction.SUM)
        w.run(body)
        events, dropped = tnative.drain_world(w, link=link)
    finally:
        w.close()
    assert dropped == 0
    assert {e["track"] for e in events} == {f"emu/r{r}" for r in range(4)}
    for e in events:
        args = e["args"]
        assert args["algorithm"] == "EAGER_RING_RS_AG"
        assert args["coef_messages"] > 0 and args["coef_bytes"] > 0
        assert args["predicted_s"] == pytest.approx(
            link.seconds(args["coef_messages"], args["coef_bytes"]))
        assert args["measured_s"] > 0


def test_aggregate_wire_gbps_reflects_total_volume():
    """The aggregate column charges schedule volume, not payload: an
    8-world eager-ring allreduce moves ~2n(P-1) bytes, so at equal
    (payload, seconds) its aggregate bandwidth is far above payload/s."""
    nbytes, world, secs = 1 << 20, 8, 0.01
    agg = tnative.aggregate_wire_gbps("allreduce", nbytes, world, secs)
    payload = nbytes / secs / 1e9
    assert agg > 5 * payload


# ---------------------------------------------------------------------------
# host tracer
# ---------------------------------------------------------------------------


def test_tracer_disabled_span_is_noop_singleton():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", cat="call", track="x")
    s2 = tr.span("b", cat="phase", track="y")
    assert s1 is s2  # the shared null span: no allocation when off
    with s1 as sp:
        sp.set(anything=1)
    assert tr.snapshot() == []


def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=3, enabled=True)
    for i in range(5):
        tr.emit(f"s{i}", "call", "t", ts_ns=i, dur_ns=1, args={})
    assert tr.drops == 2
    assert [s["name"] for s in tr.snapshot()] == ["s2", "s3", "s4"]


def test_tracer_span_measures_and_attaches_args():
    tr = Tracer(enabled=True)
    with tr.span("op", cat="call", track="facade", count=4) as sp:
        sp.set(algorithm="RING")
    (ev,) = tr.drain()
    assert ev["name"] == "op" and ev["cat"] == "call"
    assert ev["dur_ns"] >= 0
    assert ev["args"] == {"count": 4, "algorithm": "RING"}


def test_tracer_span_records_exception_and_propagates():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with tr.span("bad", cat="phase", track="t"):
            raise ValueError("x")
    (ev,) = tr.drain()
    assert ev["args"]["error"] == "ValueError"


# ---------------------------------------------------------------------------
# export: schema + chrome
# ---------------------------------------------------------------------------


def _mini_trace():
    tr = Tracer(enabled=True)
    tr.emit("allreduce", "native", "emu/r0", ts_ns=10, dur_ns=100,
            args={"op": "allreduce", "coef_messages": 2.0,
                  "coef_bytes": 1000.0, "measured_s": 1e-3,
                  "predicted_s": 2e-3, "retcode": 0})
    tr.emit("lint", "phase", "device", ts_ns=5, dur_ns=0, args={})
    return tr.to_trace({"world": 2})


def test_schema_accepts_valid_and_rejects_drift():
    jsonschema = pytest.importorskip("jsonschema")
    trace = _mini_trace()
    telemetry.validate_trace(trace)
    bad = json.loads(json.dumps(trace))
    bad["spans"][0]["cat"] = "mystery"  # unknown category
    with pytest.raises(jsonschema.ValidationError):
        telemetry.validate_trace(bad)
    bad2 = json.loads(json.dumps(trace))
    del bad2["spans"][0]["ts_ns"]  # missing required field
    with pytest.raises(jsonschema.ValidationError):
        telemetry.validate_trace(bad2)
    bad3 = json.loads(json.dumps(trace))
    bad3["spans"][0]["args"]["predicted_s"] = "fast"  # wrong type
    with pytest.raises(jsonschema.ValidationError):
        telemetry.validate_trace(bad3)


def test_chrome_export_one_named_track_per_rank():
    trace = _mini_trace()
    chrome = telemetry.to_chrome(trace)
    metas = [e for e in chrome["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {m["args"]["name"] for m in metas} == {"emu/r0", "device"}
    assert len(xs) == 2
    # zero-duration phase span stretched to stay clickable
    assert all(e["dur"] > 0 for e in xs)
    # args ride through verbatim for the Perfetto detail pane
    ar = next(e for e in xs if e["name"] == "allreduce")
    assert ar["args"]["coef_messages"] == 2.0


# ---------------------------------------------------------------------------
# feedback loop
# ---------------------------------------------------------------------------


def _synthetic_trace(alpha=1e-4, beta=1e9, n=12, skew=1.0):
    tr = Tracer(enabled=True)
    for k in range(n):
        m = float(2 + k)
        b = float(1 << (12 + k % 8))
        t = (alpha * m + b / beta) * skew
        tr.emit("allreduce", "native", f"emu/r{k % 4}", ts_ns=k,
                dur_ns=int(t * 1e9),
                args={"coef_messages": m, "coef_bytes": b,
                      "measured_s": t})
    return tr.to_trace()


def test_calibrate_from_trace_recovers_link():
    trace = _synthetic_trace(alpha=1e-4, beta=1e9)
    link = telemetry.calibrate_from_trace(trace)
    assert link.alpha == pytest.approx(1e-4, rel=0.05)
    assert link.beta == pytest.approx(1e9, rel=0.05)


def test_calibrate_from_trace_rejects_span_free_trace():
    tr = Tracer(enabled=True)
    tr.emit("lint", "phase", "device", ts_ns=0, dur_ns=5, args={})
    with pytest.raises(ValueError, match="calibratable"):
        telemetry.calibrate_from_trace(tr.to_trace())


def test_residual_improvement_refit_beats_wrong_default():
    from accl_tpu.sequencer.timing import LinkParams

    trace = _synthetic_trace(alpha=1e-4, beta=1e9)
    wrong = LinkParams(alpha=1e-5, beta=4e9)
    out = telemetry.residual_improvement(trace, default=wrong)
    assert out["improved"]
    assert out["median_rel_err_refit"] < out["median_rel_err_default"]


def test_autotune_from_trace_applies_registers(mesh8):
    """The loop closes into the device: autotune_from_trace refits from
    the trace and writes the tuning registers the executors consult."""
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh8)
    trace = _synthetic_trace(alpha=5e-4, beta=0.5e9)
    tuning = telemetry.autotune_from_trace(accl, trace)
    assert accl.cclo.tuning().bcast_flat_tree_max_ranks == \
        tuning.bcast_flat_tree_max_ranks
    assert tuning.reduce_flat_tree_max_count >= 1


# ---------------------------------------------------------------------------
# facade + sequence emission (the host half of the tentpole)
# ---------------------------------------------------------------------------


def test_facade_and_sequence_spans(tracer, mesh8):
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh8)
    n = 8192
    chunk = n // 8
    a = accl.create_buffer(n, data=RNG.standard_normal((8, n))
                           .astype(np.float32))
    b = accl.create_buffer(chunk)
    c = accl.create_buffer(n)
    accl.allreduce(a, c, n, ReduceFunction.SUM)
    with accl.sequence() as seq:
        seq.reduce_scatter(a, b, chunk, ReduceFunction.SUM)
        seq.allgather(b, c, chunk)
    spans = tracer.snapshot()
    by_cat: dict = {}
    for s in spans:
        by_cat.setdefault(s["cat"], []).append(s)

    # eager call span with plan + prediction
    call = next(s for s in by_cat["call"] if s["name"] == "allreduce")
    assert call["args"]["algorithm"] == "EAGER_RING_RS_AG"
    assert call["args"]["predicted_s"] > 0
    assert call["dur_ns"] > 0

    # the eager call's children, then the record -> lint -> compile ->
    # dispatch pipeline, one signature (the dispatch's launch and wait
    # children carry the sequence call's id instead)
    call_kids = [s["name"] for s in by_cat["phase"]
                 if s["args"].get("call_id") == call["args"]["call_id"]]
    assert call_kids == ["stage_in", "plan", "lower", "launch", "wait",
                         "place", "stage_out"]
    seq_phases = [s for s in by_cat["phase"] if "signature" in s["args"]]
    assert {"record", "lint", "compile", "dispatch"} <= {
        s["name"] for s in seq_phases}
    sigs = {s["args"]["signature"] for s in seq_phases}
    assert len(sigs) == 1

    # per-step markers carry step index, op, and the predict estimate
    steps = sorted(by_cat["step"], key=lambda s: s["args"]["step"])
    assert [s["args"]["op"] for s in steps] == ["reduce_scatter",
                                               "allgather"]
    assert all(s["args"]["signature"] in sigs for s in steps)
    assert all(s["args"]["predicted_s"] > 0 for s in steps)

    # the sequence span ties it together and sums the step predictions
    (seq_span,) = by_cat["sequence"]
    assert seq_span["args"]["n_steps"] == 2
    assert seq_span["args"]["signature"] in sigs
    assert seq_span["args"]["predicted_s"] == pytest.approx(
        sum(s["args"]["predicted_s"] for s in steps))
    # the sequence call's children: staging, and the dispatch phase
    # with its launch and wait; every phase span is accounted for
    seq_kids = sorted((s for s in by_cat["phase"] if s["args"].get(
        "call_id") == seq_span["args"]["call_id"]), key=lambda s: s["ts_ns"])
    assert [s["name"] for s in seq_kids] == [
        "stage_in", "dispatch", "launch", "wait", "stage_out"]
    assert len(by_cat["phase"]) == len(
        {id(s) for s in seq_phases + seq_kids}) + len(call_kids)

    # the whole thing round-trips the event schema and the exporter
    trace = tracer.to_trace()
    telemetry.validate_trace(trace)
    chrome = telemetry.to_chrome(trace)
    assert {m["args"]["name"]
            for m in chrome["traceEvents"] if m["ph"] == "M"} == \
        {"facade", "device"}


def test_tracing_off_emits_nothing(mesh8):
    from accl_tpu.accl import ACCL

    tr = telemetry.get_tracer()
    tr.clear()
    assert not tr.enabled  # the default; fault_env never leaks it on
    accl = ACCL(mesh8)
    n = 1024
    a = accl.create_buffer(n)
    c = accl.create_buffer(n)
    accl.allreduce(a, c, n, ReduceFunction.SUM)
    assert tr.snapshot() == []


# ---------------------------------------------------------------------------
# the eager call's phases: child spans of the facade call span
# ---------------------------------------------------------------------------

EAGER_PHASES = ["plan", "lower", "launch", "wait", "place"]


def _children(spans, call):
    """The phase spans carrying `call`'s call_id, in start order."""
    cid = call["args"]["call_id"]
    return sorted((s for s in spans if s["cat"] == "phase"
                   and s["args"].get("call_id") == cid),
                  key=lambda s: s["ts_ns"])


def _assert_nested(call, kids):
    """Each child lies inside its parent, on its track, and no two
    overlap."""
    lo, hi = call["ts_ns"], call["ts_ns"] + call["dur_ns"]
    at = lo
    for k in kids:
        assert k["track"] == call["track"]
        assert k["ts_ns"] >= at, (k["name"], "overlaps its predecessor")
        at = k["ts_ns"] + k["dur_ns"]
        assert at <= hi, (k["name"], "ends after its call")


@pytest.mark.parametrize("resident", [False, True], ids=["host", "device"])
def test_eager_call_phases_nest_in_the_call_span(tracer, mesh4, resident):
    """One eager allreduce on four devices: one call span and its
    plan/lower/launch/wait/place children, disjoint, inside it, sharing
    its call_id; a call on host buffers also stages in and out, one with
    from_device/to_device stages neither."""
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh4)
    n = 4096
    a = accl.create_buffer(n, data=RNG.standard_normal((4, n))
                           .astype(np.float32))
    c = accl.create_buffer(n)
    accl.allreduce(a, c, n, ReduceFunction.SUM)  # compile outside
    tracer.clear()
    accl.allreduce(a, c, n, ReduceFunction.SUM, from_device=resident,
                   to_device=resident)
    spans = tracer.snapshot()
    (call,) = [s for s in spans if s["cat"] == "call"]
    assert call["name"] == "allreduce" and call["track"] == "facade"
    kids = _children(spans, call)
    want = EAGER_PHASES if resident else (
        ["stage_in"] + EAGER_PHASES + ["stage_out"])
    assert [k["name"] for k in kids] == want
    assert len(spans) == 1 + len(kids)
    _assert_nested(call, kids)
    by_name = {k["name"]: k for k in kids}
    assert by_name["plan"]["args"]["algorithm"] == call["args"]["algorithm"]
    assert by_name["lower"]["args"]["hit"] is True
    assert by_name["place"]["args"]["copied"] is False
    if not resident:
        assert by_name["stage_in"]["args"]["bytes"] == a.nbytes
        assert by_name["stage_out"]["args"]["bytes"] == c.nbytes
    telemetry.validate_trace(tracer.to_trace())


def test_call_ids_increase_through_the_process(tracer, mesh4):
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh4)
    n = 256
    a = accl.create_buffer(n)
    c = accl.create_buffer(n)
    for _ in range(3):
        accl.allreduce(a, c, n, ReduceFunction.SUM)
    accl.allgather(a, accl.create_buffer(4 * n), n)
    ids = [s["args"]["call_id"] for s in tracer.snapshot()
           if s["cat"] == "call"]
    assert len(ids) == 4 and ids == sorted(set(ids))


def test_observers_alone_see_one_span_per_call(mesh4):
    """With only the always-on observers installed, an eager call emits
    exactly its call span, no child is built, and the ring stays
    empty."""
    from accl_tpu.accl import ACCL

    tr = telemetry.get_tracer()
    assert not tr.enabled
    tr.clear()
    seen = []
    tr.add_observer(seen.append)
    try:
        accl = ACCL(mesh4)
        n = 512
        a = accl.create_buffer(n)
        c = accl.create_buffer(n)
        for _ in range(3):  # host buffers: staging would be a child
            accl.allreduce(a, c, n, ReduceFunction.SUM)
    finally:
        tr.remove_observer(seen.append)
    assert [(e["name"], e["cat"]) for e in seen] == [("allreduce", "call")] * 3
    assert all(e["args"]["predicted_s"] > 0 for e in seen)
    assert tr.snapshot() == []
    assert tr.current() is None


def _host_events(xplane: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(xplane)
    return [e for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("accl.")]


def test_profiler_session_collects_spans_on_its_clock(mesh4, tmp_path):
    """Under a profiler session, the call span and its children are also
    host-plane annotations (`accl.<name>`, with their args), and the
    ring keeps the same spans though it was never enabled."""
    import glob

    import jax
    from accl_tpu.accl import ACCL

    tr = telemetry.get_tracer()
    assert not tr.enabled
    accl = ACCL(mesh4)
    n = 1024
    a = accl.create_buffer(n)
    c = accl.create_buffer(n)
    accl.allreduce(a, c, n, ReduceFunction.SUM, from_device=True,
                   to_device=True)
    tr.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        accl.allreduce(a, c, n, ReduceFunction.SUM, from_device=True,
                       to_device=True)
    finally:
        jax.profiler.stop_trace()
    ring = tr.drain()
    assert tr.current() is None
    (call,) = [s for s in ring if s["cat"] == "call"]
    assert [k["name"] for k in _children(ring, call)] == EAGER_PHASES

    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {e.name: e for e in _host_events(path)}
    assert {"accl.allreduce"} | {f"accl.{p}" for p in EAGER_PHASES} <= set(
        events)
    outer = events["accl.allreduce"]
    for inner in ("accl.launch", "accl.wait"):
        e = events[inner]
        assert outer.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= outer.start_ns + outer.duration_ns
        assert dict(e.stats)["call_id"] == call["args"]["call_id"]
    assert dict(outer.stats)["algorithm"] == call["args"]["algorithm"]


def test_lowering_cache_counts_and_lower_span_hit(tracer, mesh4):
    """The first call of a shape misses the lowering cache, the second
    hits; the counters move whether or not anything is traced."""
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh4)
    comp = accl.cclo.compiler
    n = 1328  # a shape no other call in this process lowers here
    a = accl.create_buffer(n)
    c = accl.create_buffer(n)
    for _ in range(2):
        accl.allreduce(a, c, n, ReduceFunction.SUM)
    lowers = [s["args"] for s in tracer.snapshot() if s["name"] == "lower"]
    assert [a["hit"] for a in lowers] == [False, True]
    # the miss names the ring its program walks: CPU devices have no
    # coordinates, so the mesh's own order, with no detour counted
    assert lowers[0]["ring_order"] == [0, 1, 2, 3]
    assert lowers[0]["ring_detours"] == 0
    assert "ring_order" not in lowers[1]
    telemetry.validate_trace(tracer.to_trace())
    assert (comp.lower_misses, comp.lower_hits) == (1, 1)
    tracer.disable()
    accl.allreduce(a, c, n, ReduceFunction.SUM)
    assert (comp.lower_misses, comp.lower_hits) == (1, 2)


def test_wider_result_buffer_counts_a_place_copy(tracer, mesh4):
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh4)
    dev = accl.cclo
    n = 256
    a = accl.create_buffer(n, data=np.ones((4, n), np.float32))
    exact = accl.create_buffer(n)
    wide = accl.create_buffer(2 * n)
    accl.allreduce(a, exact, n, ReduceFunction.SUM)
    assert dev.place_copies == 0
    accl.allreduce(a, wide, n, ReduceFunction.SUM)
    assert dev.place_copies == 1
    copied = [s["args"]["copied"] for s in tracer.snapshot()
              if s["name"] == "place"]
    assert copied == [False, True]
    np.testing.assert_array_equal(wide.host[:, :n], 4.0)


def test_duration_register_covers_launch_and_wait(tracer, mesh4):
    """get_duration_ns() is the interval from before the launch to the
    host seeing the result ready: at least the launch and wait spans."""
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh4)
    n = 2048
    a = accl.create_buffer(n)
    c = accl.create_buffer(n)
    accl.allreduce(a, c, n, ReduceFunction.SUM)
    tracer.clear()
    accl.allreduce(a, c, n, ReduceFunction.SUM)
    spans = {s["name"]: s for s in tracer.snapshot()}
    dur = accl.get_duration_ns()
    assert dur >= spans["launch"]["dur_ns"] + spans["wait"]["dur_ns"]
    # the register holds the launch, not the staging around it
    assert dur < spans["allreduce"]["dur_ns"]


def test_predict_call_evaluated_once_per_program(mesh4, monkeypatch):
    """The always-on layer evaluates timing.predict once per compiled
    program, and every call still carries the same predicted_s."""
    from accl_tpu.accl import ACCL
    from accl_tpu.sequencer import timing

    if telemetry.default_link() is None:
        pytest.skip("no committed timing model")
    evaluated = []
    real = timing.predict

    def counting(*a, **kw):
        evaluated.append(a[1])
        return real(*a, **kw)

    monkeypatch.setattr(timing, "predict", counting)
    tr = telemetry.get_tracer()
    seen = []
    tr.add_observer(seen.append)
    try:
        accl = ACCL(mesh4)
        n = 768
        a = accl.create_buffer(n)
        c = accl.create_buffer(n)
        for _ in range(4):
            accl.allreduce(a, c, n, ReduceFunction.SUM)
    finally:
        tr.remove_observer(seen.append)
    assert len(evaluated) == 1
    preds = {e["args"]["predicted_s"] for e in seen}
    plan = accl._last_request.plan
    assert preds == {real(telemetry.default_link(), Operation.allreduce,
                          plan, n, 4, 4,
                          rx_buf_bytes=accl.cclo.eager_rx_buf_size,
                          aggregate=True)}


# ---------------------------------------------------------------------------
# satellite 2: the logp crossovers are single-sourced
# ---------------------------------------------------------------------------


def test_logp_crossovers_single_sourced():
    """timing._logp_* must flip exactly at constants.logp_*_max_bytes —
    the same arithmetic runtime.cpp compiles (hops_saved * HOP_BYTES
    with bit-scan log2) — so a retune of the constants moves model and
    executor together."""
    from accl_tpu.sequencer.timing import _logp_allgather, _logp_allreduce

    for world in (2, 4, 8, 16, 32, 64):
        ar_cross = logp_allreduce_max_bytes(world)
        assert _logp_allreduce(world, ar_cross)
        assert not _logp_allreduce(world, ar_cross + 1)
        ag_cross = logp_allgather_max_bytes(world)
        assert _logp_allgather(world, ag_cross)
        assert not _logp_allgather(world, ag_cross + 1)
    # non-power-of-two worlds never take the logp shape
    from accl_tpu.sequencer.timing import _logp_allreduce as f

    assert not f(6, 1)


def test_logp_crossover_formula_pinned_to_native_source():
    """The C++ rule bodies must use the same hops-saved formulas the
    Python single source encodes (the definition pin in test_timing.py
    covers the HOP_BYTES values; this pins the SHAPE)."""
    import pathlib

    src = (pathlib.Path(__file__).parent.parent / "native" / "src"
           / "runtime.cpp").read_text()
    assert "2 * (world - 1) - 2 * log2_floor(world)" in src
    assert "(world - 1) - log2_floor(world)" in src
    # and the Python source delegates to constants, not local math
    tsrc = (pathlib.Path(__file__).parent.parent / "accl_tpu"
            / "sequencer" / "timing.py").read_text()
    assert "logp_allreduce_max_bytes(world)" in tsrc
    assert "logp_allgather_max_bytes(world)" in tsrc


# ---------------------------------------------------------------------------
# Tier-tagged spans + per-tier refit (PR 8)
# ---------------------------------------------------------------------------


def _two_tier_trace():
    """Synthetic trace with two DISTINCT true links labeled by
    args["tier"], plus a third untagged population on its own link."""
    true = {"inner": (2e-6, 4e9), "outer": (400e-6, 0.1e9),
            None: (1e-4, 1e9)}
    tr = Tracer(enabled=True)
    for tier, (a, b_) in true.items():
        for k in range(8):
            m = float(2 + k)
            b = float(1 << (14 + k % 6))
            t = a * m + b / b_
            args = {"coef_messages": m, "coef_bytes": b,
                    "measured_s": t}
            if tier is not None:
                args["tier"] = tier
            tr.emit("allreduce", "native",
                    f"hier/{tier or 'flat'}/r{k % 2}", ts_ns=k,
                    dur_ns=int(t * 1e9), args=args)
    return tr.to_trace(), true


def test_calibrate_tiers_recovers_each_link_independently():
    """Each tier refits from exactly its own labeled samples: the fast
    and slow links come back distinct (a pooled fit would average
    them into a model of neither)."""
    trace, true = _two_tier_trace()
    tiers = telemetry.calibrate_tiers_from_trace(trace)
    assert tiers.inner.beta == pytest.approx(true["inner"][1], rel=0.05)
    assert tiers.outer.beta == pytest.approx(true["outer"][1], rel=0.05)
    assert tiers.inner.alpha == pytest.approx(true["inner"][0], rel=0.1)
    assert tiers.outer.alpha == pytest.approx(true["outer"][0], rel=0.1)
    assert tiers.inner.beta > 10 * tiers.outer.beta


def test_flat_fit_excludes_tier_tagged_spans():
    """calibrate_from_trace with no tier keeps only UNTAGGED spans — a
    tier-tagged measurement belongs to that tier's link, and pooling
    two different links is the exact failure the labels prevent."""
    from accl_tpu.telemetry.feedback import hop_samples

    trace, true = _two_tier_trace()
    flat = telemetry.calibrate_from_trace(trace)
    assert flat.alpha == pytest.approx(true[None][0], rel=0.05)
    assert flat.beta == pytest.approx(true[None][1], rel=0.05)
    assert len(hop_samples(trace)) == 8
    assert len(hop_samples(trace, tier="inner")) == 8
    # asking for a tier the trace does not carry raises loudly
    with pytest.raises(ValueError, match="tier='bogus'"):
        telemetry.calibrate_from_trace(trace, tier="bogus")


def test_drain_world_tier_tag_and_track_prefix(fault_env):
    """drain_world(tier=, track_prefix=) labels every lifted native
    span with the tier it crossed and keeps the tiers' tracks apart —
    the labeled-sample source for the per-tier refit (SPAN v1
    compatible: `tier` is an ordinary args key)."""
    fault_env(ACCL_RT_TRACE="1")
    w = EmuWorld(2, transport="local")
    try:
        def body(rank, i):
            x = np.ones(64, np.float32)
            out = np.zeros(64, np.float32)
            rank.allreduce(x, out, 64, ReduceFunction.SUM)

        w.run(body)
        events, dropped = tnative.drain_world(w, tier="inner",
                                              track_prefix="hier_pod0")
    finally:
        w.close()
    assert events and dropped == 0
    for e in events:
        assert e["args"]["tier"] == "inner"
        assert e["track"].startswith("hier_pod0/r")
    from accl_tpu.telemetry.tracer import SCHEMA_VERSION

    telemetry.validate_trace({"schema": SCHEMA_VERSION, "meta": {},
                              "spans": events})


def test_default_tier_links_reads_link_tiers(tmp_path):
    """The shipped per-tier calibration round-trips through the timing
    model document; a model without link_tiers yields None (callers
    must leave hierarchical selection off, never invent a slow-tier
    model)."""
    from accl_tpu.telemetry.feedback import default_tier_links

    p = tmp_path / "tm.json"
    p.write_text(json.dumps({
        "link_tiers": {
            "inner": {"alpha_us": 2.0, "beta_gbps": 4.0},
            "outer": {"alpha_us": 400.0, "beta_gbps": 0.1},
        }}))
    tiers = default_tier_links(p)
    assert tiers is not None
    assert tiers.inner.alpha == pytest.approx(2e-6)
    assert tiers.outer.beta == pytest.approx(0.1e9)
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"link": {"alpha_us": 1, "beta_gbps": 1}}))
    assert default_tier_links(bare) is None
    # and the COMMITTED model must carry the tier fit (bench --check's
    # hier cell depends on it; regenerated by bench.py --hier-gate)
    assert default_tier_links() is not None


# ---------------------------------------------------------------------------
# PR 13 satellites: model-cache staleness, residual hardening, and the
# flight-recorder dump-on-error path
# ---------------------------------------------------------------------------


def _bump_mtime(p):
    """Force a strictly larger mtime even on coarse filesystem clocks."""
    import os

    st = p.stat()
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))


def test_default_link_cache_invalidates_on_refit_overwrite(tmp_path,
                                                           monkeypatch):
    """Satellite regression: the per-path cache used to never
    invalidate, so a timing_model.json refit OVERWRITING an
    already-cached model was ignored for the rest of the process. The
    cache now freshness-checks the file's mtime (amortized: at most
    one stat per _STAT_TTL_S — zeroed here so the overwrite is visible
    immediately): an overwrite is re-read."""
    from accl_tpu.telemetry import feedback
    from accl_tpu.telemetry.feedback import (
        default_compute_fit,
        default_link,
        default_tier_links,
    )

    monkeypatch.setattr(feedback, "_STAT_TTL_S", 0.0)

    p = tmp_path / "timing_model.json"
    p.write_text(json.dumps({"link": {"alpha_us": 100.0, "beta_gbps": 1.0}}))
    l1 = default_link(p)
    assert l1 is not None and l1.alpha == pytest.approx(100e-6)
    assert default_tier_links(p) is None  # negative result, cached
    assert default_compute_fit(p) is None

    # a later refit overwrites the file (bench gates do exactly this
    # for link_tiers / compute_fit; a live refitter will for the link)
    p.write_text(json.dumps({
        "link": {"alpha_us": 50.0, "beta_gbps": 2.0},
        "link_tiers": {
            "inner": {"alpha_us": 2.0, "beta_gbps": 4.0},
            "outer": {"alpha_us": 400.0, "beta_gbps": 0.1},
        },
        "compute_fit": {"alpha_us": 10.0, "grad_gbps": 3.0},
    }))
    _bump_mtime(p)
    l2 = default_link(p)
    assert l2 is not None and l2.alpha == pytest.approx(50e-6)
    assert l2.beta == pytest.approx(2e9)
    tiers = default_tier_links(p)  # the stale None must not stick
    assert tiers is not None and tiers.inner.alpha == pytest.approx(2e-6)
    cf = default_compute_fit(p)
    assert cf is not None and cf.rate == pytest.approx(3e9)


def test_default_link_missing_file_then_created(tmp_path, monkeypatch):
    """The negative result is cacheable (mtime None) without making a
    model file that appears LATER invisible."""
    from accl_tpu.telemetry import feedback
    from accl_tpu.telemetry.feedback import default_link

    monkeypatch.setattr(feedback, "_STAT_TTL_S", 0.0)

    p = tmp_path / "timing_model.json"
    assert default_link(p) is None
    assert default_link(p) is None  # served from the cached miss
    p.write_text(json.dumps({"link": {"alpha_us": 7.0, "beta_gbps": 1.0}}))
    link = default_link(p)
    assert link is not None and link.alpha == pytest.approx(7e-6)


def test_residual_machinery_tolerates_empty_and_partial_traces():
    """Satellite hardening: empty and partially-populated traces (no
    spans with predicted_s, zero measured duration, malformed args)
    yield well-typed empty summaries, never exceptions."""
    from accl_tpu.telemetry import residual_rows, residual_summary
    from accl_tpu.telemetry.export import measured_seconds
    from accl_tpu.telemetry.feedback import residual_report

    empty = {"schema": telemetry.SCHEMA_VERSION, "spans": []}
    assert residual_rows(empty) == []
    assert residual_rows({}) == []
    assert residual_summary([]) == {
        "rows": 0, "median_rel_err": None, "per_op_median_rel_err": {}}

    partial = {"spans": [
        {"name": "allreduce"},                       # no args, no dur_ns
        {"cat": "call", "args": {"predicted_s": 0.1}},   # no measurement
        {"name": "x", "track": "t", "ts_ns": 0, "dur_ns": 0,
         "args": {"predicted_s": 0.1}},              # zero measured
        {"name": "y", "track": "t", "ts_ns": 0, "dur_ns": 1000,
         "args": {"predicted_s": "bogus"}},          # malformed prediction
        {"name": "z", "track": "t", "ts_ns": 0, "dur_ns": 1000,
         "args": None},                              # null args
        "not-a-span",                                # wrong type entirely
    ]}
    assert residual_rows(partial) == []
    assert measured_seconds({"args": {"measured_s": "fast"}}) == 0.0
    rep = residual_report(partial)
    assert rep["span_residuals"]["rows"] == 0
    assert rep["span_residuals"]["median_rel_err"] is None
    assert "error" in rep["calibration"]  # <2 calibratable spans, typed

    # a trace with ONE real row still summarizes (the partial entries
    # contribute nothing; they must not poison the good span)
    partial["spans"].append(
        {"name": "allreduce", "track": "emu/r0", "ts_ns": 0,
         "dur_ns": 1_000_000, "args": {"predicted_s": 2e-3}})
    rows = residual_rows(partial)
    assert len(rows) == 1
    s = residual_summary(rows)
    assert s["rows"] == 1 and s["median_rel_err"] == pytest.approx(1.0)


def test_flight_recorder_dump_on_native_fault(fault_env, monkeypatch):
    """Satellite: a collective wedged by ACCL_RT_FAULT_DELAY_TAIL_MS
    (delayed tail -> RECEIVE_TIMEOUT) must leave a self-contained
    post-mortem in the flight recorder — the dumped ring contains the
    failing span (the recv, by op name and count) with its sticky
    retcode — without host tracing (ACCL_TELEMETRY) ever having been
    enabled, and the artifact file lands when ACCL_FLIGHT_DIR is set."""
    import pathlib
    import tempfile

    from accl_tpu.telemetry import recorder as trec

    fault_env(ACCL_RT_TRACE=1, ACCL_RT_FAULT_DELAY_TAIL_MS=700)
    tr = telemetry.get_tracer()
    assert not tr.enabled  # full tracing stays OFF: the recorder alone
    assert trec.armed()    # the always-on default
    with tempfile.TemporaryDirectory() as td:
        monkeypatch.setenv("ACCL_FLIGHT_DIR", td)
        trec.get_recorder().clear()
        rx_buf = 256
        count = (3 * rx_buf) // 4
        m1 = RNG.standard_normal(count).astype(np.float32)
        w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=rx_buf)
        try:
            def body(rank, i):
                import time

                if i == 1:
                    rank.send(m1.copy(), count, dst=0, tag=5)
                    time.sleep(1.0)
                    return None
                rank.call(CallOptions(scenario=Operation.config,
                                      function=int(CfgFunc.set_timeout),
                                      count=300))
                buf = np.zeros(count, np.float32)
                h = rank.start(CallOptions(scenario=Operation.recv,
                                           count=count, root_src_dst=1,
                                           tag=5, data_type=F32), res=buf)
                with pytest.raises(ACCLError, match="RECEIVE_TIMEOUT"):
                    rank.wait(h)
                return None

            w.run(body)
            # the dump-on-error must NOT have consumed the device trace
            # ring: the wedged span is still drainable afterwards
            native_spans, _ = w.ranks[0].trace_read()
        finally:
            w.close()
        doc = trec.last_error_trace()
        assert doc is not None
        assert doc["meta"]["flight_recorder"] is True
        assert "recv" in doc["meta"]["reason"]
        errs = [s for s in doc["spans"] if s["cat"] == "error"]
        assert len(errs) >= 1
        failing = errs[-1]
        assert failing["name"] == "recv"
        assert failing["args"]["count"] == count
        assert failing["args"]["rank"] == 0
        assert failing["args"]["retcode"] & int(
            ErrorCode.RECEIVE_TIMEOUT_ERROR)
        # self-contained: schema-valid, metrics + sentinel in the meta
        pytest.importorskip("jsonschema")
        telemetry.validate_trace(doc)
        assert "metrics" in doc["meta"] and "drift_sentinel" in doc["meta"]
        # the error marker also fed the live metrics registry
        snap = doc["meta"]["metrics"]
        errs_counter = snap["counters"].get("accl_errors_total", [])
        assert any(row["labels"].get("op") == "recv"
                   for row in errs_counter)
        # the opt-in artifact file is the same document
        on_disk = json.loads(pathlib.Path(
            td, "flight_last_error.json").read_text())
        assert on_disk["meta"]["reason"] == doc["meta"]["reason"]
        # and the native ring still carries the wedged span
        recvs = [s for s in native_spans
                 if s["opcode"] == int(Operation.recv)]
        assert len(recvs) == 1
        assert recvs[0]["retcode"] & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)


# ---------------------------------------------------------------------------
# wire-health export (the reliable-wire counters through telemetry)
# ---------------------------------------------------------------------------


def test_wire_health_report_normalizes_and_totals():
    """wire_health_report turns per-rank stats2 dicts into the typed
    trace-meta shape: string rank keys, int-coerced counters, a totals
    row summing every rank; junk values are skipped, empty input yields
    the well-typed empty report."""
    rep = telemetry.wire_health_report({
        1: {"crc_drops": 2, "retx_sent": 3, "junk": "nan"},
        0: {"crc_drops": 1, "retx_sent": 0, "tx_frames": 7.0},
    })
    assert list(rep["per_rank"]) == ["0", "1"]
    assert rep["per_rank"]["1"] == {"crc_drops": 2, "retx_sent": 3}
    assert rep["totals"] == {"crc_drops": 3, "retx_sent": 3,
                             "tx_frames": 7}
    assert telemetry.wire_health_report({}) == {"per_rank": {},
                                                "totals": {}}


def test_wire_health_meta_is_schema_typed():
    """A trace embedding meta.wire_health validates; a malformed one
    (totals missing) fails — the counter rendering cannot drift
    silently."""
    jsonschema = pytest.importorskip("jsonschema")
    trace = {"schema": telemetry.SCHEMA_VERSION, "spans": [],
             "meta": {"wire_health": telemetry.wire_health_report(
                 {0: {"crc_drops": 1}})}}
    telemetry.validate_trace(trace)
    bad = {"schema": telemetry.SCHEMA_VERSION, "spans": [],
           "meta": {"wire_health": {"per_rank": {}}}}
    with pytest.raises(jsonschema.ValidationError):
        telemetry.validate_trace(bad)
    bad2 = {"schema": telemetry.SCHEMA_VERSION, "spans": [],
            "meta": {"wire_health": {"per_rank": {"0": {"x": "y"}},
                                     "totals": {}}}}
    with pytest.raises(jsonschema.ValidationError):
        telemetry.validate_trace(bad2)


def test_wire_health_from_live_world_counters():
    """End to end: a live native world's wire_stats render through the
    report with every stats2 field present and the fault-repair keys
    (WIRE_FAULT_KEYS) a strict subset — the exporter and the resilience
    classifier read the same names."""
    from accl_tpu.device.emu_device import STATS2_FIELDS

    w = EmuWorld(2, transport="local")
    try:
        def body(rank, i):
            out = np.zeros(256, np.float32)
            rank.allreduce(np.ones(256, np.float32), out, 256,
                           ReduceFunction.SUM)

        w.run(body)
        rep = telemetry.wire_health_report(
            {r.rank: r.wire_stats() for r in w.ranks})
    finally:
        w.close()
    for rank_row in rep["per_rank"].values():
        assert tuple(rank_row) == STATS2_FIELDS
    assert set(telemetry.WIRE_FAULT_KEYS) < set(rep["totals"])
    assert rep["totals"]["tx_frames"] > 0
    assert rep["totals"]["crc_drops"] == 0  # clean wire
