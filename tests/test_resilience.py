"""Self-healing collectives (accl_tpu/resilience/, docs/resilience.md).

The contract under test:

  - per-call deadlines are DERIVED from timing.predict under the
    calibrated link plus the drift sentinel's residual band — never a
    constant — and a miss is a structured DeadlineMissed verdict with
    the flight-recorder post-mortem attached (a HOST-side dump
    trigger: a silent hang leaves an artifact even with no sticky
    native retcode);
  - the ResilienceManager's retry/backoff budget separates transient
    stragglers from dead peers, exclusion shrinks the live set, and
    the recovery plan over the survivor world is re-proven through the
    EXISTING semantics + modelcheck stack before install — an
    uncertified plan raises loudly and is never installed;
  - allreduce(mode="live_subset") masks non-survivors to exact zeros
    at the source and the certifier proves the answer sums exactly the
    declared survivors (ghost contributions reject ACCL501);
  - the 30-seed kill fuzz: a random rank dies at a random point of the
    dispatch stream on the native world; survivors detect via derived
    deadlines, exclude, re-certify, reconfigure onto the survivor
    communicator, and every post-recovery answer matches the numpy
    oracle over survivors BITWISE — while a no-fault control run is
    bit-for-bit unaffected by the armed resilience seam.
"""

import os

import numpy as np
import pytest

from accl_tpu import ACCL, ACCLError, ReduceFunction
from accl_tpu.constants import DataType, Operation, TuningParams
from accl_tpu.descriptor import CallOptions
from accl_tpu.device.emu_device import EmuWorld
from accl_tpu.resilience import (
    DeadlineMissed,
    DeadlineMissedError,
    DeadlinePolicy,
    NativeDeadlineGuard,
    RecoveryPlan,
    ResilienceManager,
    RetryBudget,
    UncertifiedRecoveryError,
)
from accl_tpu.sequencer.plan import select_algorithm
from accl_tpu.sequencer.timing import LinkParams
from accl_tpu.telemetry import recorder as flight

LINK = LinkParams(alpha=100e-6, beta=0.5e9)
F32 = DataType.float32
SEL_KW = dict(max_eager_size=1024, eager_rx_buf_size=1024,
              tuning=TuningParams.default())


def _policy(world=4, **kw):
    kw.setdefault("floor_s", 0.05)
    return DeadlinePolicy(LINK, world=world, **kw)


@pytest.fixture(autouse=True)
def _clean_flight_recorder():
    flight.get_recorder().clear()
    yield
    flight.get_recorder().clear()


# ---------------------------------------------------------------------------
# deadline policy
# ---------------------------------------------------------------------------


def test_deadline_exceeds_prediction_and_floor():
    pol = _policy()
    pred = pol.predict_s("allreduce", 16384)
    dl = pol.deadline_s("allreduce", 16384)
    assert dl > pred
    assert dl >= pol.floor_s
    # the band formula is the drift sentinel's, not an ad-hoc one
    from accl_tpu.telemetry.metrics import DriftSentinel

    sent = DriftSentinel(band_factor=pol.band_factor,
                         band_floor=pol.band_floor)
    ref = 0.4
    pol.arm_reference("allreduce", ref)
    assert pol.tolerance("allreduce") == pytest.approx(sent.band_hi(ref))


def test_armed_reference_tightens_unarmed_band():
    pol = _policy()
    loose = pol.deadline_s("allreduce", 16384)
    pol.arm_reference("allreduce", 0.05)
    assert pol.deadline_s("allreduce", 16384) < loose


def test_arm_from_residuals_uses_median():
    pol = _policy()
    ref = pol.arm_from_residuals("bcast", [0.1, 0.3, 0.2])
    assert ref == pytest.approx(0.2)
    assert pol.tolerance("bcast") == pytest.approx(
        max(0.2 * pol.band_factor, 0.2 + pol.band_floor))


def test_deadline_monotonic_in_count():
    pol = _policy()
    small = pol.deadline_s("allreduce", 1024)
    big = pol.deadline_s("allreduce", 1 << 20)
    assert big > small


def test_policy_requires_calibrated_link():
    with pytest.raises(ValueError, match="calibrated"):
        DeadlinePolicy(None, world=4)


def test_check_in_deadline_is_none_and_miss_is_verdict():
    pol = _policy()
    dl = pol.deadline_s("allreduce", 4096)
    assert pol.check("allreduce", 4096, 4, elapsed_s=dl * 0.5) is None
    miss = pol.check("allreduce", 4096, 4, elapsed_s=dl * 10, rank=1,
                     suspect_rank=2, attribution="silent")
    assert isinstance(miss, DeadlineMissed)
    v = miss.verdict()
    assert v["kind"] == "deadline_missed"
    assert v["suspect_rank"] == 2 and v["rank"] == 1
    assert "allreduce" in str(miss) and "suspect r2" in str(miss)


def test_sticky_retcode_is_a_miss_even_inside_deadline():
    # a call that FAILED with RECEIVE_TIMEOUT is a deadline event no
    # matter how fast the failure surfaced
    pol = _policy()
    miss = pol.check("allreduce", 4096, 4, elapsed_s=1e-6,
                     retcode=0x800)
    assert miss is not None and miss.retcode == 0x800
    assert "RECEIVE_TIMEOUT" in str(miss)


# ---------------------------------------------------------------------------
# flight recorder: host-side dump on a deadline miss (satellite)
# ---------------------------------------------------------------------------


def test_deadline_miss_freezes_post_mortem_without_tracing():
    from accl_tpu import telemetry

    tr = telemetry.get_tracer()
    assert not tr.enabled  # the ring is off: the recorder alone fires
    assert flight.armed()
    # seed some context spans so the post-mortem has history to freeze
    tr.emit("allreduce", "call", "facade", ts_ns=1, dur_ns=10,
            args={"op": "allreduce", "count": 64})
    miss = _policy().check("allreduce", 4096, 4, elapsed_s=100.0, rank=3)
    assert miss.post_mortem is not None
    doc = miss.post_mortem
    assert doc["meta"]["flight_recorder"] is True
    assert "deadline missed" in doc["meta"]["reason"]
    # the marker span rode the tracer: cat "error", host-side verdict
    markers = [s for s in doc["spans"] if s.get("cat") == "error"]
    assert markers and markers[-1]["args"]["deadline_missed"] is True
    assert markers[-1]["args"]["measured_s"] == pytest.approx(100.0)
    assert markers[-1]["track"] == "emu/r3"
    # the retained last-error trace IS this dump
    assert flight.last_error_trace()["meta"]["reason"] == doc["meta"]["reason"]
    # schema-valid like every exported trace
    from accl_tpu.telemetry import validate_trace

    validate_trace(doc)


def test_error_marker_spans_never_poison_residual_tables():
    """The miss marker carries the failing call's predicted/elapsed
    pair as DIAGNOSTIC detail — residual_rows must skip cat "error"
    spans, or one wedged wait (rel err ~25x) would skew every residual
    median and any band armed from a post-incident trace."""
    from accl_tpu.telemetry import residual_rows

    trace = {"spans": [
        {"name": "allreduce", "cat": "native", "track": "emu/r0",
         "ts_ns": 0, "dur_ns": 0,
         "args": {"predicted_s": 1e-3, "measured_s": 1.1e-3}},
        {"name": "allreduce", "cat": "error", "track": "emu/r1",
         "ts_ns": 1, "dur_ns": 0,
         "args": {"deadline_missed": True, "retcode": 0x800,
                  "predicted_s": 2e-3, "measured_s": 5.2e-2}},
    ]}
    rows = residual_rows(trace)
    assert len(rows) == 1 and rows[0]["track"] == "emu/r0"


def test_on_deadline_miss_noop_when_disarmed():
    from accl_tpu import telemetry

    telemetry.disable_observability()
    try:
        assert flight.on_deadline_miss("allreduce", count=4) is None
    finally:
        telemetry.enable_observability()


# ---------------------------------------------------------------------------
# manager: budget, attribution, exclusion
# ---------------------------------------------------------------------------


def _mk_miss(suspect=None, rank=0):
    return DeadlineMissed(op="allreduce", count=64, predicted_s=1e-3,
                          deadline_s=5e-3, elapsed_s=1.0, rank=rank,
                          suspect_rank=suspect)


def test_retry_budget_transitions_and_backoff():
    mgr = ResilienceManager(4, budget=RetryBudget(max_retries=2,
                                                  backoff_base_s=0.01,
                                                  backoff_factor=2.0))
    m = _mk_miss(suspect=2)
    assert mgr.record_miss(m) == "retry"
    d1 = mgr.retry_delay_s(2)
    assert mgr.record_miss(m) == "retry"
    d2 = mgr.retry_delay_s(2)
    assert d2 == pytest.approx(d1 * 2.0)  # exponential backoff
    assert mgr.record_miss(m) == "exclude"
    assert len(mgr.misses) == 3


def test_note_recovery_resets_the_budget():
    mgr = ResilienceManager(4, budget=RetryBudget(max_retries=1))
    m = _mk_miss(suspect=1)
    assert mgr.record_miss(m) == "retry"
    mgr.note_recovery(1)  # the retry succeeded: transient straggler
    assert mgr.record_miss(m) == "retry"  # budget is fresh again


def test_attribute_silent_names_the_non_reporter():
    mgr = ResilienceManager(4)
    assert mgr.attribute_silent([0, 1, 3]) == 2
    assert mgr.attribute_silent([0, 1, 2, 3]) is None  # nobody silent
    assert mgr.attribute_silent([0]) is None  # ambiguous: not exactly one


def test_exclude_validations():
    mgr = ResilienceManager(4)
    assert mgr.exclude(2) == (0, 1, 3)
    assert mgr.live_ranks == (0, 1, 3)
    with pytest.raises(ValueError, match="not live"):
        mgr.exclude(2)
    mgr2 = ResilienceManager(2)
    with pytest.raises(ValueError, match="2-rank floor"):
        mgr2.exclude(1)


# ---------------------------------------------------------------------------
# manager: certified replan + hot swap
# ---------------------------------------------------------------------------


def test_replan_ring_on_non_pow2_survivor_world():
    mgr = ResilienceManager(4)
    mgr.exclude(1)
    rp = mgr.replan(Operation.allreduce, count=256)
    assert rp.world == 3 and rp.survivors == (0, 2, 3)
    assert rp.source == "ring"
    assert rp.certificate["diagnostics"] == 0
    assert "semantics(ACCL501-504)" in rp.certificate["checks"]
    assert "modelcheck(ACCL205-207)" in rp.certificate["checks"]


def test_replan_synthesized_on_pow2_survivor_world():
    mgr = ResilienceManager(5)
    mgr.exclude(4)
    rp = mgr.replan(Operation.allreduce, count=1024)
    assert rp.world == 4 and rp.source == "synthesized"
    assert rp.synth_key.startswith("allreduce_w4")
    assert rp.certificate["diagnostics"] == 0


def test_uncertified_replan_raises_and_installs_nothing(monkeypatch):
    from accl_tpu.analysis import semantics
    from accl_tpu.analysis.diagnostics import make

    mgr = ResilienceManager(4)
    mgr.exclude(3)

    def sabotaged(dag, spec, name):
        return [make("ACCL501", "sabotaged certifier")]

    monkeypatch.setattr(semantics, "certify", sabotaged)
    with pytest.raises(UncertifiedRecoveryError, match="NOT installed"):
        mgr.replan(Operation.allreduce, count=64)
    assert mgr.current_plan is None


def test_install_requires_clean_certificate_and_matching_membership():
    mgr = ResilienceManager(4)
    mgr.exclude(0)
    rp = mgr.replan(Operation.allreduce, count=64)
    bad = RecoveryPlan(op="allreduce", survivors=rp.survivors, world=3,
                       count=64, source="ring", plan=None, certificate={})
    with pytest.raises(UncertifiedRecoveryError):
        mgr.install(bad)
    gen = mgr.install(rp)
    assert gen == mgr.generation == 1
    assert mgr.current_plan is rp
    # a stale plan (membership changed since it was built) is refused
    mgr.exclude(1)
    with pytest.raises(ValueError, match="membership"):
        mgr.install(rp)


# ---------------------------------------------------------------------------
# degraded live-subset allreduce: XLA tier
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def accl4(mesh4):
    return ACCL(mesh4)


@pytest.mark.parametrize("live", [(0, 1, 3), (1, 2), (0,)])
def test_live_subset_matches_survivor_oracle_bitwise(accl4, live):
    n = 96
    rng = np.random.default_rng(hash(live) % (1 << 31))
    data = rng.integers(-64, 64, size=(4, n)).astype(np.float32)
    a = accl4.create_buffer(n, np.float32, data)
    b = accl4.create_buffer(n, np.float32)
    accl4.allreduce(a, b, n, ReduceFunction.SUM, mode="live_subset",
                    live_ranks=live)
    want = data[list(live)].sum(0)
    assert np.array_equal(b.host, np.tile(want, (4, 1)))
    accl4.free_buffer(a)
    accl4.free_buffer(b)


@pytest.mark.parametrize("seed", range(30))
def test_live_subset_fuzz_vs_survivor_oracle(accl4, seed):
    """30-seed degraded-mode fuzz: a random survivor set and payload,
    bitwise against the numpy oracle over exactly the declared
    survivors — and the lifted schedule certifies against the
    survivor spec (the verdict cache makes repeated shapes free)."""
    rng = np.random.default_rng(4200 + seed)
    n = int(rng.choice([16, 100]))
    k = int(rng.integers(1, 4))
    live = tuple(sorted(rng.choice(4, size=k, replace=False).tolist()))
    data = rng.integers(-32, 32, size=(4, n)).astype(np.float32)
    a = accl4.create_buffer(n, np.float32, data)
    b = accl4.create_buffer(n, np.float32)
    accl4.allreduce(a, b, n, ReduceFunction.SUM, mode="live_subset",
                    live_ranks=live)
    want = data[list(live)].sum(0)
    assert np.array_equal(b.host, np.tile(want, (4, 1))), \
        f"seed {seed} live {live}"
    from accl_tpu.analysis import semantics

    opts = CallOptions(scenario=Operation.allreduce, count=n,
                       function=int(ReduceFunction.SUM), data_type=F32,
                       live_ranks=live)
    plan = select_algorithm(Operation.allreduce, n, 4, 4,
                            live_ranks=live, **SEL_KW)
    assert not semantics.certify_call(opts, plan, 4)
    accl4.free_buffer(a)
    accl4.free_buffer(b)


def test_live_subset_full_set_is_the_ordinary_allreduce(accl4):
    n = 32
    data = np.arange(4 * n, dtype=np.float32).reshape(4, n)
    a = accl4.create_buffer(n, np.float32, data)
    b = accl4.create_buffer(n, np.float32)
    req = accl4.allreduce(a, b, n, ReduceFunction.SUM,
                          mode="live_subset", live_ranks=(0, 1, 2, 3))
    assert np.array_equal(b.host, np.tile(data.sum(0), (4, 1)))
    # normalized at the facade: the plan carries NO live set, so the
    # compiled program is shared with mode="all"
    assert req.plan.live_ranks == ()
    accl4.free_buffer(a)
    accl4.free_buffer(b)


def test_live_subset_validations(accl4, monkeypatch):
    n = 16
    a = accl4.create_buffer(n, np.float32)
    b = accl4.create_buffer(n, np.float32)
    ar = lambda **kw: accl4.allreduce(a, b, n, ReduceFunction.SUM, **kw)  # noqa: E731
    with pytest.raises(ValueError, match="mode"):
        ar(mode="degraded")
    with pytest.raises(ValueError, match="live_ranks requires"):
        ar(live_ranks=(0, 1))
    with pytest.raises(ValueError, match="non-empty"):
        ar(mode="live_subset", live_ranks=())
    with pytest.raises(ValueError, match="duplicate"):
        ar(mode="live_subset", live_ranks=(1, 1))
    with pytest.raises(ValueError, match="outside"):
        ar(mode="live_subset", live_ranks=(0, 7))
    with pytest.raises(ValueError, match="SUM-only"):
        accl4.allreduce(a, b, n, ReduceFunction.MAX, mode="live_subset",
                        live_ranks=(0, 1))
    with pytest.raises(NotImplementedError, match="exact-wire"):
        ar(mode="live_subset", live_ranks=(0, 1),
           compress_dtype=DataType.float16)
    monkeypatch.setattr(type(accl4.cclo), "supports_live_subset", False)
    with pytest.raises(NotImplementedError, match="XLA-schedule-tier"):
        ar(mode="live_subset", live_ranks=(0, 1))
    accl4.free_buffer(a)
    accl4.free_buffer(b)


def test_live_subset_rides_a_recorded_sequence(accl4):
    """The degraded form records into a fused batch like any other
    call: the DEFAULT lint tier (semantics included) passes it and the
    fused result matches the survivor oracle bitwise."""
    n = 64
    live = (0, 2, 3)
    data = np.arange(4 * n, dtype=np.float32).reshape(4, n)
    a = accl4.create_buffer(n, np.float32, data)
    b = accl4.create_buffer(n, np.float32)
    c = accl4.create_buffer(n, np.float32)
    with accl4.sequence() as seq:
        seq.allreduce(a, b, n, ReduceFunction.SUM, mode="live_subset",
                      live_ranks=live)
        seq.copy(b, c, n)
    want = np.tile(data[list(live)].sum(0), (4, 1))
    assert np.array_equal(b.host, want)
    assert np.array_equal(c.host, want)
    for buf in (a, b, c):
        accl4.free_buffer(buf)


def test_ghost_contribution_rejects_exactly_ACCL501():
    """The corpus fixture's claim, from the live lifted DAGs: a plain
    full-world allreduce judged against a declared survivor set is a
    ghost contribution — ACCL501 and nothing else — while the masked
    schedule certifies clean."""
    from accl_tpu.analysis import semantics

    world, n, live = 4, 8, (0, 1, 3)
    opts_live = CallOptions(scenario=Operation.allreduce, count=n,
                            function=int(ReduceFunction.SUM),
                            data_type=F32, live_ranks=live)
    spec = semantics.collective_spec(opts_live, world)
    plan_live = select_algorithm(Operation.allreduce, n, 4, world,
                                 live_ranks=live, **SEL_KW)
    dag_live = semantics.lift_call(opts_live, plan_live, world)
    assert not semantics.certify(dag_live, spec, "allreduce")
    opts_plain = CallOptions(scenario=Operation.allreduce, count=n,
                             function=int(ReduceFunction.SUM),
                             data_type=F32)
    plan_plain = select_algorithm(Operation.allreduce, n, 4, world,
                                  **SEL_KW)
    dag_plain = semantics.lift_call(opts_plain, plan_plain, world)
    codes = sorted({d.code
                    for d in semantics.certify(dag_plain, spec,
                                               "allreduce")})
    assert codes == ["ACCL501"]


def test_live_sets_are_cache_keyed():
    p1 = select_algorithm(Operation.allreduce, 64, 4, 4,
                          live_ranks=(0, 1), **SEL_KW)
    p2 = select_algorithm(Operation.allreduce, 64, 4, 4,
                          live_ranks=(0, 2), **SEL_KW)
    assert p1 != p2
    o1 = CallOptions(scenario=Operation.allreduce, count=64,
                     data_type=F32, live_ranks=(0, 1))
    o2 = CallOptions(scenario=Operation.allreduce, count=64,
                     data_type=F32, live_ranks=(0, 2))
    assert o1.signature() != o2.signature()


def test_live_subset_validation_in_select_algorithm():
    with pytest.raises(ValueError, match="outside"):
        select_algorithm(Operation.allreduce, 64, 4, 4,
                         live_ranks=(0, 9), **SEL_KW)
    with pytest.raises(ValueError, match="duplicate"):
        select_algorithm(Operation.allreduce, 64, 4, 4,
                         live_ranks=(1, 1), **SEL_KW)
    with pytest.raises(ValueError, match="exact-wire"):
        from accl_tpu.constants import CompressionFlags

        select_algorithm(Operation.allreduce, 64, 4, 4,
                         CompressionFlags.ETH_COMPRESSED,
                         compress_dtype=DataType.float16,
                         live_ranks=(0, 1), **SEL_KW)


# ---------------------------------------------------------------------------
# facade seam: armed deadlines on eager calls
# ---------------------------------------------------------------------------


def test_facade_armed_seam_control_is_bitwise_unaffected(accl4):
    n = 128
    data = np.arange(4 * n, dtype=np.float32).reshape(4, n)
    a = accl4.create_buffer(n, np.float32, data)
    b = accl4.create_buffer(n, np.float32)
    accl4.allreduce(a, b, n, ReduceFunction.SUM)
    plain = np.array(b.host)
    # a generous policy: the control run must see zero misses and the
    # results must be bit-for-bit what the unarmed run produced
    pol = DeadlinePolicy(LinkParams(alpha=1.0, beta=1e9), world=4)
    mgr = ResilienceManager(4, policy=pol)
    accl4.arm_resilience(mgr)
    try:
        accl4.allreduce(a, b, n, ReduceFunction.SUM)
        assert np.array_equal(np.array(b.host), plain)
        assert not mgr.misses
    finally:
        accl4.arm_resilience(None)


def test_facade_armed_seam_records_a_miss_after_warmup(accl4):
    n = 128
    a = accl4.create_buffer(n, np.float32)
    b = accl4.create_buffer(n, np.float32)
    # an absurdly tight policy: any real dispatch outlives it
    pol = DeadlinePolicy(LinkParams(alpha=1e-12, beta=1e15), world=4,
                         floor_s=0.0)
    pol.arm_reference("allreduce", 0.0)
    pol.band_floor = 0.0
    mgr = ResilienceManager(4, policy=pol)
    accl4.arm_resilience(mgr)
    try:
        # the first observation of a shape is the warm-up exemption
        # (XLA compile time is not a wire deadline miss)
        accl4.allreduce(a, b, n, ReduceFunction.SUM)
        assert not mgr.misses
        accl4.allreduce(a, b, n, ReduceFunction.SUM)
    finally:
        accl4.arm_resilience(None)
    assert mgr.misses, "tight deadline did not produce a verdict"
    assert mgr.misses[0].post_mortem is not None
    accl4.free_buffer(a)
    accl4.free_buffer(b)


# ---------------------------------------------------------------------------
# native rank death: env lever, sticky span, guard
# ---------------------------------------------------------------------------


def test_soft_reset_re_exempts_warmed_shapes(mesh4):
    """soft_reset clears the compiled-schedule caches, so the next
    dispatch of an already-warmed shape recompiles — the armed seam
    must re-exempt it instead of flagging compile time as a miss."""
    accl = ACCL(mesh4)
    n = 48
    a = accl.create_buffer(n, np.float32)
    b = accl.create_buffer(n, np.float32)
    from accl_tpu.sequencer.timing import LinkParams as LP

    pol = DeadlinePolicy(LP(alpha=1e-12, beta=1e15), world=4, floor_s=0.0)
    pol.arm_reference("allreduce", 0.0)
    pol.band_floor = 0.0
    mgr = ResilienceManager(4, policy=pol)
    accl.arm_resilience(mgr)
    try:
        accl.allreduce(a, b, n, ReduceFunction.SUM)  # warm-up exempt
        assert not mgr.misses
        accl.soft_reset()  # compiled caches gone
        accl.allreduce(a, b, n, ReduceFunction.SUM)  # recompiles: exempt again
        assert not mgr.misses, \
            "post-reset recompile was flagged as a deadline miss"
        accl.allreduce(a, b, n, ReduceFunction.SUM)  # steady state: checked
        assert mgr.misses
    finally:
        accl.arm_resilience(None)


def test_kill_env_auto_wedges_after_n_calls(monkeypatch):
    monkeypatch.setenv("ACCL_RT_FAULT_KILL_RANK", "1")
    monkeypatch.setenv("ACCL_RT_FAULT_KILL_AFTER", "2")
    n = 64
    w = EmuWorld(2, transport="local")
    try:
        xs = np.arange(2 * n, dtype=np.float32).reshape(2, n)

        def body(rank, i):
            from accl_tpu.constants import CfgFunc

            rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=300))
            outs = []
            for _k in range(2):  # inside the budget: both complete
                out = np.zeros(n, np.float32)
                rank.allreduce(xs[i].copy(), out, n, ReduceFunction.SUM)
                outs.append(out)
            try:  # call 3 is past the budget: rank 1 is dead
                out = np.zeros(n, np.float32)
                rank.allreduce(xs[i].copy(), out, n, ReduceFunction.SUM)
                return outs, "completed"
            except ACCLError as e:
                return outs, e.retcode

        res = w.run(body)
    finally:
        w.close()
    for outs, verdict in res:
        for out in outs:
            assert np.array_equal(out, xs.sum(0))
        assert verdict != "completed" and verdict & 0x800


def test_killed_rank_emits_final_sticky_span(monkeypatch):
    monkeypatch.setenv("ACCL_RT_TRACE", "1")
    n = 32
    w = EmuWorld(2, transport="local")
    try:
        w.ranks[1].kill()

        def body(rank, i):
            from accl_tpu.constants import CfgFunc

            if i == 0:
                rank.call(CallOptions(scenario=Operation.config,
                                      function=int(CfgFunc.set_timeout),
                                      count=200))
            try:
                out = np.zeros(n, np.float32)
                rank.allreduce(np.ones(n, np.float32), out, n,
                               ReduceFunction.SUM)
            except ACCLError:
                pass

        w.run(body)
        spans1, _ = w.ranks[1].trace_read()
        # the kill path recorded the dead rank's final span with the
        # sticky retcode — this is what the flight recorder fires on
        assert spans1, "killed rank left no trace span"
        assert spans1[-1]["retcode"] & 0x800
        spans0, _ = w.ranks[0].trace_read()
        assert spans0 and spans0[-1]["retcode"] & 0x800
    finally:
        w.close()


# ---------------------------------------------------------------------------
# THE 30-seed kill fuzz: detect -> exclude -> re-certify -> reconfigure
# ---------------------------------------------------------------------------


def _fuzz_world_policy():
    pol = DeadlinePolicy(LinkParams(alpha=100e-6, beta=0.5e9), world=4,
                         floor_s=0.05)
    pol.arm_reference("allreduce", 0.3)
    return pol


@pytest.mark.parametrize("seed", range(30))
def test_kill_fuzz_recovery_certified_and_bitwise(seed):
    """Kill a random rank at a random point of the dispatch stream;
    survivors must (1) run a bit-for-bit unaffected control while the
    seam is armed and healthy, (2) detect the death through derived
    deadlines within the retry budget, (3) re-certify a recovery plan
    over the survivor world (never install uncertified), and (4)
    produce post-recovery answers that match the numpy oracle over
    survivors BITWISE on the reconfigured communicator."""
    from accl_tpu.communicator import Communicator, Rank
    from accl_tpu.device.base import CCLOAddr

    rng = np.random.default_rng(7000 + seed)
    world = 4
    n = int(rng.choice([64, 256, 1024]))
    victim = int(rng.integers(world))
    kill_at = int(rng.integers(0, 3))  # healthy dispatches before death
    xs = rng.integers(-32, 32, size=(world, n)).astype(np.float32)
    pol = _fuzz_world_policy()
    budget = RetryBudget(max_retries=1, backoff_base_s=0.01)
    mgr = ResilienceManager(world, policy=pol, budget=budget)
    guard = NativeDeadlineGuard(pol)  # misses attributed by the driver
    full_oracle = xs.sum(0)

    w = EmuWorld(world, transport="local")
    try:
        # -- control phase: armed guard vs plain wait, bit-for-bit ----
        def control(rank, i):
            guard.arm(rank, "allreduce", n)
            guarded, plain = [], []
            for _k in range(kill_at):
                out = np.zeros(n, np.float32)
                h = rank.start(CallOptions(
                    scenario=Operation.allreduce, count=n,
                    function=int(ReduceFunction.SUM), data_type=3),
                    op0=xs[i].copy(), res=out)
                assert guard.wait(rank, h, "allreduce", n) is None
                guarded.append(out)
                out2 = np.zeros(n, np.float32)
                rank.allreduce(xs[i].copy(), out2, n, ReduceFunction.SUM)
                plain.append(out2)
            return guarded, plain

        for guarded, plain in w.run(control):
            for g, p in zip(guarded, plain):
                assert np.array_equal(g, full_oracle)
                assert np.array_equal(g, p)  # armed seam changes nothing

        # -- death + detection within the retry budget ----------------
        # Each retry attempt is ONE w.run phase (threads joined between
        # attempts): survivors stay in lockstep, so every frame a
        # survivor sends lands inside its peers' live wedged calls and
        # is consumed — the links between survivors are clean when the
        # recovery communicator starts (the drain discipline a
        # recovery needs).
        w.ranks[victim].kill()
        action = None
        last_misses: dict[int, DeadlineMissed] = {}
        for attempt in range(budget.max_retries + 1):
            def one_attempt(rank, i):
                if i == victim:
                    return None
                guard.arm(rank, "allreduce", n)
                out = np.zeros(n, np.float32)
                h = rank.start(CallOptions(
                    scenario=Operation.allreduce, count=n,
                    function=int(ReduceFunction.SUM), data_type=3),
                    op0=xs[i].copy(), res=out)
                try:
                    guard.wait(rank, h, "allreduce", n)
                    return None
                except DeadlineMissedError as e:
                    return e.miss

            verdicts = w.run(one_attempt)
            reporters = [i for i, v in enumerate(verdicts)
                         if v is not None]
            assert sorted(reporters) == sorted(
                r for r in range(world) if r != victim), \
                f"seed {seed} attempt {attempt}: not every survivor " \
                f"missed ({reporters})"
            for i in reporters:
                assert verdicts[i].retcode & 0x800
                last_misses[i] = verdicts[i]
            suspect = mgr.attribute_silent(reporters)
            assert suspect == victim
            import dataclasses as _dc

            rep = _dc.replace(last_misses[reporters[0]],
                              suspect_rank=suspect,
                              attribution="silent")
            action = mgr.record_miss(rep)
            if action == "exclude":
                break
        assert action == "exclude", \
            f"seed {seed}: budget never recommended exclusion"
        survivors = mgr.exclude(victim)
        # reconfiguration fence: every survivor is quiescent (threads
        # joined above), so stale frames of the aborted old-world
        # collectives are dropped before the recovery communicator's
        # first call can consume them as data
        for g in survivors:
            w.ranks[g].flush_rx()

        # -- certified replan over the survivor world ------------------
        rp = mgr.replan(Operation.allreduce, count=n)
        assert rp.certificate["diagnostics"] == 0
        assert rp.world == world - 1
        mgr.install(rp)
        assert mgr.generation == 1

        # -- reconfigure: survivor communicator, answers bitwise -------
        addr = int(CCLOAddr.DYNAMIC_BASE)
        comm = Communicator(
            [Rank(device_index=g, session_id=g) for g in survivors],
            0, addr)
        want = xs[list(survivors)].sum(0)

        def recover(rank, i):
            if i == victim:
                return None
            rank.write_communicator(comm)
            guard.arm(rank, "allreduce", n)
            outs = []
            for _k in range(2):
                out = np.zeros(n, np.float32)
                h = rank.start(CallOptions(
                    scenario=Operation.allreduce, count=n,
                    function=int(ReduceFunction.SUM), data_type=3,
                    comm_addr=addr), op0=xs[i].copy(), res=out)
                assert guard.wait(rank, h, "allreduce", n) is None
                outs.append(out)
            return outs

        for i, outs in enumerate(w.run(recover)):
            if i == victim:
                continue
            for out in outs:
                assert np.array_equal(out, want), \
                    f"seed {seed}: post-recovery answer wrong on r{i}"
    finally:
        w.close()


if os.environ.get("ACCL_RT_FAULT_KILL_RANK") or \
        os.environ.get("ACCL_RT_FAULT_KILL_AFTER"):  # pragma: no cover
    raise RuntimeError("kill levers must not leak into the environment")


# ---------------------------------------------------------------------------
# escalation policy: lossy link vs dead rank (IntegrityFault)
# ---------------------------------------------------------------------------


def _miss(suspect=2):
    return DeadlineMissed(op="allreduce", count=1024, predicted_s=0.01,
                          deadline_s=0.05, elapsed_s=0.2,
                          suspect_rank=suspect)


def test_classify_wire_delta_lossy_vs_dark():
    """The classifier keys on REPAIR activity (WIRE_FAULT_KEYS), never
    on the nack/ack chatter: a survivor nacks a dead rank's silence
    too, so 'someone is waiting' counters climb in both cases and must
    not read as lossy."""
    cls = ResilienceManager.classify_wire_delta
    assert cls(None) == "dark"
    assert cls({}) == "dark"
    assert cls({"nack_sent": 40, "nack_rx": 12, "ack_sent": 3}) == "dark"
    assert cls({"crc_drops": 1}) == "lossy"
    assert cls({"retx_sent": 2, "nack_sent": 9}) == "lossy"
    assert cls({"dup_drops": 1}) == "lossy"
    assert cls({"retx_miss": 1}) == "lossy"
    assert cls({"tx_frames": 500, "rx_frames": 480}) == "dark"


def test_assess_miss_lossy_raises_integrity_not_budget():
    """A lossy-classified miss records a structured IntegrityFault
    (post-mortem carried over), returns "integrity", and does NOT
    consume the dead-rank retry budget — the transport's retransmit
    budget owns a lossy link; only a dark wire walks the
    retry->exclude path to reconfiguration."""
    mgr = ResilienceManager(4, budget=RetryBudget(max_retries=1))
    lossy = {"crc_drops": 3, "dup_drops": 1, "retx_sent": 5,
             "retx_miss": 0, "nack_rx": 7, "nack_sent": 9}
    m = _miss()
    assert mgr.assess_miss(m, lossy) == "integrity"
    assert mgr.assess_miss(m, lossy) == "integrity"
    faults = mgr.integrity_faults
    assert len(faults) == 2
    f = faults[0]
    assert (f.op, f.count, f.suspect_rank) == ("allreduce", 1024, 2)
    assert f.crc_drops == 3 and f.retransmits == 5
    assert f.nack_round_trips == 7 and f.dup_drops == 1
    v = f.verdict()
    assert v["kind"] == "integrity_fault"
    assert v["suspect_rank"] == 2 and v["retransmits"] == 5
    assert "no reconfiguration" in str(f)
    # lossy misses are recorded but consumed ZERO retry budget: the
    # next DARK misses still get the full retry->exclude progression
    assert len(mgr.misses) == 2
    assert mgr.assess_miss(_miss(), None) == "retry"
    assert mgr.assess_miss(_miss(), {"nack_sent": 3}) == "exclude"


def test_assess_miss_dark_delegates_to_record_miss():
    mgr = ResilienceManager(4, budget=RetryBudget(max_retries=2))
    dark = {"nack_sent": 12, "ack_rx": 4}
    assert mgr.assess_miss(_miss(), dark) == "retry"
    assert mgr.assess_miss(_miss(), dark) == "retry"
    assert mgr.assess_miss(_miss(), dark) == "exclude"
    assert not mgr.integrity_faults


def test_observe_wire_health_returns_deltas_per_observer():
    """observe_wire_health diffs each OBSERVER rank's snapshot against
    its previous one — the delta window assess_miss classifies."""
    mgr = ResilienceManager(4)
    d0 = mgr.observe_wire_health(0, {"crc_drops": 5, "retx_sent": 2})
    assert d0 == {"crc_drops": 5, "retx_sent": 2}  # first delta = all
    d1 = mgr.observe_wire_health(0, {"crc_drops": 5, "retx_sent": 6})
    assert d1 == {"crc_drops": 0, "retx_sent": 4}
    # per-rank streams are independent
    assert mgr.observe_wire_health(1, {"crc_drops": 1}) == {"crc_drops": 1}
    assert ResilienceManager.classify_wire_delta(d1) == "lossy"
    assert ResilienceManager.classify_wire_delta(
        mgr.observe_wire_health(0, {"crc_drops": 5, "retx_sent": 6})
    ) == "dark"  # nothing moved since


def test_integrity_fault_against_live_chaos_world():
    """End to end on a real native world under seeded corruption: a
    fabricated deadline miss assessed against the world's true wire
    deltas classifies LOSSY (the counters climbed from genuine CRC
    repairs), so the manager raises IntegrityFault instead of
    recommending exclusion."""
    os.environ["ACCL_RT_FAULT_CORRUPT_PCT"] = "30"
    os.environ["ACCL_RT_FAULT_SEED"] = "3"
    try:
        w = EmuWorld(2, max_eager=1 << 20, rx_buf_bytes=256,
                     transport="local")
    finally:
        os.environ.pop("ACCL_RT_FAULT_CORRUPT_PCT", None)
        os.environ.pop("ACCL_RT_FAULT_SEED", None)
    try:
        mgr = ResilienceManager(2)
        for r in w.ranks:
            mgr.observe_wire_health(r.rank, r.wire_stats())

        def body(rank, i):
            out = np.zeros(4096, np.float32)
            rank.allreduce(np.full(4096, i + 1, np.float32), out, 4096,
                           ReduceFunction.SUM)
            return out

        res = w.run(body)
        deltas = [mgr.observe_wire_health(r.rank, r.wire_stats())
                  for r in w.ranks]
    finally:
        w.close()
    for out in res:
        np.testing.assert_array_equal(out, np.full(4096, 3, np.float32))
    total = {k: sum(d.get(k, 0) for d in deltas) for k in deltas[0]}
    assert total["crc_drops"] > 0  # the chaos fired
    assert mgr.assess_miss(_miss(suspect=1), total) == "integrity"
    assert mgr.integrity_faults[0].crc_drops == total["crc_drops"]


def test_integrity_budget_bounds_the_lossy_credit():
    """The lossy credit is bounded per suspect: wire deltas are
    world-global evidence, so a rank that dies while OTHER links are
    lossy would classify lossy forever — past integrity_budget
    consecutive verdicts the miss walks the dead-rank retry/exclude
    path anyway, and note_recovery resets the streak (a lossy link
    that keeps recovering is the transport doing its job)."""
    mgr = ResilienceManager(4, budget=RetryBudget(max_retries=1),
                            integrity_budget=2)
    lossy = {"crc_drops": 1}
    assert mgr.assess_miss(_miss(), lossy) == "integrity"
    assert mgr.assess_miss(_miss(), lossy) == "integrity"
    assert mgr.assess_miss(_miss(), lossy) == "retry"    # credit spent
    assert mgr.assess_miss(_miss(), lossy) == "exclude"  # a real death
    assert len(mgr.integrity_faults) == 2
    mgr2 = ResilienceManager(4, integrity_budget=1)
    assert mgr2.assess_miss(_miss(), lossy) == "integrity"
    mgr2.note_recovery(2)  # the retry succeeded: transport did its job
    assert mgr2.assess_miss(_miss(), lossy) == "integrity"
