"""Timing-model tests: the cclo_sim slot (reference
test/model/simulator/cclo_sim.cpp:25-80 — a second target that predicts
schedule duration). The alpha-beta model must (a) mirror the schedule
structures, (b) recover known link parameters from measurements, and
(c) reproduce the reference tuning defaults as PERFORMANCE crossovers
(accl.cpp:1198-1208), not just control-flow constants."""


import numpy as np
import pytest

from accl_tpu.constants import Operation, TuningParams
from accl_tpu.sequencer.plan import Algorithm, select_algorithm
from accl_tpu.sequencer.timing import (
    LinkParams,
    calibrate,
    coefficients,
    predict,
    tuning_crossovers,
)

RX = 4096
TUNING = TuningParams.default()


def plan_for(op, count, world, max_eager=4096):
    return select_algorithm(op, count, 4, world, max_eager_size=max_eager,
                            eager_rx_buf_size=RX, tuning=TUNING)


def test_coefficients_mirror_schedule_structure():
    # small pow2-world allreduce rides recursive halving-doubling on the
    # native executor (runtime.cpp logp_max_bytes): 2*log2(P) exchange
    # steps moving the same 2n(P-1)/P volume
    p = plan_for(Operation.allreduce, 512, 4)
    assert p.algorithm == Algorithm.EAGER_RING_RS_AG
    m, b = coefficients(Operation.allreduce, p, 512, 4, 4, rx_buf_bytes=RX)
    assert m == 2 * 2 and b == pytest.approx(2 * 3 * 512)
    # above the latency crossover the 2(P-1)-hop ring takes over
    big = 1 << 18  # 1 MB > 8 hops saved x 32 KB
    p = plan_for(Operation.allreduce, big, 4)
    m, b = coefficients(Operation.allreduce, p, big, 4, 4, rx_buf_bytes=RX)
    assert m == 2 * 3 and b == pytest.approx(2 * 3 * big)
    # rendezvous binary-tree bcast: ceil(log2 P) rounds of full payload
    p = plan_for(Operation.bcast, 50_000, 8)
    assert p.algorithm == Algorithm.RNDZV_BIN_TREE
    m, b = coefficients(Operation.bcast, p, 50_000, 4, 8, rx_buf_bytes=RX)
    assert m == 2 * 3 and b == 3 * 200_000
    # large allreduce stays on the segmented ring (the reduce+bcast
    # composition was dropped — emulator-measured 4x slower than bcast)
    p = plan_for(Operation.allreduce, 50_000, 8)
    assert p.algorithm == Algorithm.EAGER_RING_RS_AG
    m, b = coefficients(Operation.allreduce, p, 50_000, 4, 8,
                        rx_buf_bytes=RX)
    assert m > 0 and b > 0
    # composition sums its resolved stages (rendezvous reduce_scatter)
    p = plan_for(Operation.reduce_scatter, 50_000, 8)
    assert p.algorithm == Algorithm.RNDZV_REDUCE_SCATTER and len(p.stages) == 2
    m, b = coefficients(Operation.reduce_scatter, p, 50_000, 4, 8,
                        rx_buf_bytes=RX)
    assert m > 0 and b > 0
    # world 1: free
    p = plan_for(Operation.allreduce, 64, 1)
    assert coefficients(Operation.allreduce, p, 64, 4, 1,
                        rx_buf_bytes=RX) == (0.0, 0.0)


def test_predict_monotone_in_bytes_and_world():
    lp = LinkParams(alpha=1e-5, beta=1e9)
    last = 0.0
    for count in (256, 4096, 65536, 1 << 20):
        p = plan_for(Operation.allreduce, count, 4)
        t = predict(lp, Operation.allreduce, p, count, 4, 4, rx_buf_bytes=RX)
        assert t > last
        last = t
    t4 = predict(lp, Operation.bcast, plan_for(Operation.bcast, 64, 4),
                 64, 4, 4, rx_buf_bytes=RX)
    t8 = predict(lp, Operation.bcast, plan_for(Operation.bcast, 64, 8),
                 64, 4, 8, rx_buf_bytes=RX)
    assert t8 > t4


def test_calibrate_recovers_synthetic_link():
    rng = np.random.default_rng(7)
    true = LinkParams(alpha=25e-6, beta=2.5e9)
    samples = []
    for _ in range(40):
        m = float(rng.integers(1, 40))
        b = float(rng.integers(1, 1 << 22))
        t = true.seconds(m, b) * float(rng.uniform(0.97, 1.03))
        samples.append((m, b, t))
    fit = calibrate(samples)
    assert fit.alpha == pytest.approx(true.alpha, rel=0.15)
    assert fit.beta == pytest.approx(true.beta, rel=0.15)


def test_calibrated_on_live_emulator_predicts_within_order():
    """Fit on a small LIVE emulator sweep, then check held-out predictions
    land within an order of magnitude (the emulator's Python dispatch is
    noisy; the model targets algorithm selection, not microsecond
    accuracy)."""
    import time

    from accl_tpu import ReduceFunction
    from accl_tpu.device.emu_device import EmuWorld

    world = 4
    w = EmuWorld(world, max_eager=4096, rx_buf_bytes=RX)
    try:
        def time_ar(count, iters=8):
            def body(rank, i):
                x = np.ones(count, np.float32)
                out = np.zeros(count, np.float32)
                rank.barrier()
                t0 = time.perf_counter()
                for _ in range(iters):
                    rank.allreduce(x, out, count, ReduceFunction.SUM)
                return (time.perf_counter() - t0) / iters

            return max(w.run(body))

        counts = [256, 4096, 65536, 1 << 19]
        samples = []
        for c in counts[:-1]:
            p = plan_for(Operation.allreduce, c, world)
            m, b = coefficients(Operation.allreduce, p, c, 4, world,
                                rx_buf_bytes=RX)
            samples.append((m, b, time_ar(c)))
        fit = calibrate(samples)
        assert fit.alpha > 0 and fit.beta > 0
        held = counts[-1]
        p = plan_for(Operation.allreduce, held, world)
        pred = predict(fit, Operation.allreduce, p, held, 4, world,
                       rx_buf_bytes=RX)
        meas = time_ar(held)
        assert pred / meas < 10 and meas / pred < 10, (pred, meas)
    finally:
        w.close()


def test_tuning_crossovers_match_reference_defaults():
    """The five tuning registers as performance choices: the bcast
    flat-vs-tree crossover is structural (flat <= 3 ranks exactly, the
    reference default, for ANY link), and the reduce/gather byte
    thresholds are positive, finite, and scale with link latency the way
    a latency-vs-serialization tradeoff must."""
    slow = tuning_crossovers(LinkParams(alpha=100e-6, beta=1e9), world=8)
    fast = tuning_crossovers(LinkParams(alpha=1e-6, beta=1e9), world=8)
    for c in (slow, fast):
        assert c["bcast_flat_tree_max_ranks"] == 3
        # derived large-payload rank crossover lands at the reference
        # default's neighborhood (the reference's 4 encodes ITS link's
        # constants; the pure serialized-vs-rounds tradeoff gives 3)
        assert 2 <= c["reduce_flat_tree_max_ranks"] <= 4
        assert 0 < c["reduce_flat_tree_max_count_bytes"] < float("inf")
    # a lower-latency link tolerates less payload serialization before the
    # tree wins: the byte threshold shrinks with alpha (the reference's
    # 32 KB encodes ITS link's latency/bandwidth point)
    assert (fast["reduce_flat_tree_max_count_bytes"]
            < slow["reduce_flat_tree_max_count_bytes"])
    # the reference's own 32 KB sits between these two link regimes'
    # thresholds — consistent with a 100 Gbps low-latency NIC
    ref = tuning_crossovers(LinkParams(alpha=5e-6, beta=12.5e9), world=8)
    assert 1024 < ref["reduce_flat_tree_max_count_bytes"] < 10 * 1024 * 1024


def test_from_crossovers_register_mapping():
    """Crossover dict -> register values: byte thresholds round to ints
    within the cap; inf (flat never loses) caps instead of overflowing."""
    from accl_tpu import TuningParams

    cross = tuning_crossovers(LinkParams(alpha=5e-6, beta=12.5e9), world=8)
    t = TuningParams.from_crossovers(cross)
    assert t.bcast_flat_tree_max_ranks == 3
    assert t.reduce_flat_tree_max_count == int(
        cross["reduce_flat_tree_max_count_bytes"])
    inf_cross = dict(cross, reduce_flat_tree_max_count_bytes=float("inf"))
    assert TuningParams.from_crossovers(
        inf_cross).reduce_flat_tree_max_count == 1 << 22


def test_facade_autotune_applies_model(mesh8):
    """ACCL.autotune closes the loop model -> registers -> selection:
    the registers land in exchange memory (device.tuning() readback) and
    algorithm selection actually flips at the tuned byte threshold."""
    from accl_tpu import Operation
    from accl_tpu.accl import ACCL
    from accl_tpu.sequencer import Algorithm, select_algorithm

    accl = ACCL(mesh8)
    link = LinkParams(alpha=50e-6, beta=1e9)
    applied = accl.autotune(link=link)
    live = accl.cclo.tuning()
    assert live.reduce_flat_tree_max_count == applied.reduce_flat_tree_max_count
    assert live.bcast_flat_tree_max_ranks == applied.bcast_flat_tree_max_ranks

    # selection flips exactly at the applied threshold (rendezvous
    # regime, where the flat/binomial switch lives)
    thr = applied.reduce_flat_tree_max_count
    world = 8
    below = select_algorithm(Operation.reduce, thr // 4, 4, world,
                             max_eager_size=0, eager_rx_buf_size=1024,
                             tuning=live)
    above = select_algorithm(Operation.reduce, thr, 4, world,
                             max_eager_size=0, eager_rx_buf_size=1024,
                             tuning=live)
    assert below.algorithm == Algorithm.RNDZV_FLAT_TREE
    assert above.algorithm == Algorithm.RNDZV_BIN_TREE


def test_tpu_tier_from_profile(tmp_path):
    """The second calibration tier reads the on-chip profile artifact:
    dispatch alpha from the w1 lanes, HBM beta from stream rows, noise
    rows excluded (they are resolution floors, not measurements)."""
    import pathlib
    import sys

    tools_dir = str(pathlib.Path(__file__).resolve().parents[1] / "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from timing_model import tpu_tier

    csv_path = tmp_path / "profile.csv"
    csv_path.write_text(
        "Test,Bytes,Seconds,GBps,Regime\n"
        "combine_sum_fp32,1024,1.0e-09,1024.0,noise\n"
        "combine_sum_fp32,1073741824,3.6e-03,298.3,stream\n"
        "allreduce_w1_dispatch_datapath_fp32,4096,2.0e-04,0.02,latency\n"
        "allreduce_w1_dispatch_datapath_fp32,262144,2.1e-04,1.2,latency\n"
        "allreduce_w1_dispatch_datapath_fp32,16777216,2.5e-04,67.0,latency\n"
    )
    tier = tpu_tier(csv_path)
    assert tier is not None
    # dispatch alpha ~200us (the constant part of the w1 fit)
    assert 100 <= tier["dispatch_alpha_us"] <= 300
    assert tier["hbm_stream_gbps"] == pytest.approx(298.3)
    assert tier["ici_beta_gbps"] is None
    # projected crossovers exist and are self-consistent with the huge
    # dispatch alpha: flat trees stay preferable to far larger payloads
    # than on the emulator link
    proj = tier["projected_crossovers"]
    assert proj["reduce_flat_tree_max_count_bytes"] > 1 << 20

    # absent profile -> no tier, never a crash
    assert tpu_tier(tmp_path / "missing.csv") is None


def test_facade_autotune_tpu_tier(mesh8, tmp_path):
    """autotune(tier='tpu') derives the registers from the on-chip
    calibration tier (dispatch alpha + HBM-bounded beta); a model without
    a usable tier fails loudly instead of silently tuning from the wrong
    link."""
    import json

    from accl_tpu.accl import ACCL

    model = {
        "link": {"alpha_us": 30.0, "beta_gbps": 0.1},
        "tpu_tier": {"dispatch_alpha_us": 500.0, "hbm_stream_gbps": 300.0},
    }
    p = tmp_path / "timing_model.json"
    p.write_text(json.dumps(model))
    accl = ACCL(mesh8)
    applied = accl.autotune(timing_model_path=p, tier="tpu")
    # 500us of dispatch per round against a 300 GB/s wire: flat trees win
    # to far larger payloads than the emulator tier's 2.8 KB crossover
    assert applied.reduce_flat_tree_max_count > 1 << 20
    assert accl.cclo.tuning().reduce_flat_tree_max_count == \
        applied.reduce_flat_tree_max_count

    p.write_text(json.dumps({"link": model["link"]}))
    with pytest.raises(ValueError):
        accl.autotune(timing_model_path=p, tier="tpu")
    with pytest.raises(ValueError):
        accl.autotune(timing_model_path=p, tier="wat")


# ---------------------------------------------------------------------------
# single-source pinning: the hop-shape constants the timing model uses must
# be the SAME values the native executor compiles in
# ---------------------------------------------------------------------------


def _native_src():
    import pathlib

    return (pathlib.Path(__file__).parent.parent
            / "native" / "src" / "runtime.cpp").read_text()


def _cpp_const(src, name):
    import re

    m = re.search(rf"constexpr\s+uint64_t\s+{name}\s*=\s*([^;]+);", src)
    assert m, f"constexpr {name} not found in native/src/runtime.cpp"
    expr = m.group(1).replace("ull", "").replace("u", "")
    return int(eval(expr, {"__builtins__": {}}))  # noqa: S307 (pinned literal)


def test_logp_constants_pinned_to_native_executor():
    """constants.py is the single source for the logp crossovers and the
    streamed jumbo-segment size; the C++ executor's constexprs must hold
    identical values (a drift here silently skews every prediction the
    timing model makes about the executor)."""
    from accl_tpu.constants import (
        LOGP_ALLGATHER_HOP_BYTES,
        LOGP_ALLREDUCE_HOP_BYTES,
        STREAM_SEG_BYTES,
    )

    src = _native_src()
    assert _cpp_const(src, "LOGP_ALLREDUCE_HOP_BYTES") == \
        LOGP_ALLREDUCE_HOP_BYTES
    assert _cpp_const(src, "LOGP_ALLGATHER_HOP_BYTES") == \
        LOGP_ALLGATHER_HOP_BYTES
    assert _cpp_const(src, "STREAM_SEG_BYTES") == STREAM_SEG_BYTES


def test_logp_constants_actually_used_by_native_rules():
    """The constexprs must be what the selection rules and the jumbo
    sender USE — re-hardcoding a literal in logp_max_bytes would pass the
    definition check while drifting the behavior."""
    src = _native_src()
    assert "hops_saved * LOGP_ALLREDUCE_HOP_BYTES" in src
    assert "hops_saved * LOGP_ALLGATHER_HOP_BYTES" in src
    assert "seg_bytes=*/STREAM_SEG_BYTES" in src


# ---------------------------------------------------------------------------
# wire-byte accounting: ETH_COMPRESSED plans must be charged wire widths
# (+ scale overhead for the quantized lanes), and the autotune crossovers
# must MOVE when a compression lane is active
# ---------------------------------------------------------------------------


def _compressed_plan(op, count, world, wire):
    from accl_tpu.constants import CompressionFlags, DataType

    comp = (CompressionFlags.ETH_COMPRESSED if wire != DataType.none
            else CompressionFlags.NO_COMPRESSION)
    return select_algorithm(op, count, 4, world, comp,
                            max_eager_size=4096, eager_rx_buf_size=RX,
                            tuning=TUNING, compress_dtype=wire)


def test_predict_charges_wire_dtype_widths():
    """The satellite regression: predict() used to charge UNCOMPRESSED
    bytes on ETH_COMPRESSED calls. Cast lanes must halve the byte term,
    the blockwise int8 lanes must shrink it 4/(1+4/256) ~ 3.94x (scale
    side-channel included)."""
    from accl_tpu.constants import DataType
    from accl_tpu.sequencer.timing import wire_elem_bytes

    count, world = 1 << 20, 8  # 4 MiB: byte-dominated ring regime
    p_none = _compressed_plan(Operation.allreduce, count, world,
                              DataType.none)
    p_f16 = _compressed_plan(Operation.allreduce, count, world,
                             DataType.float16)
    p_q = _compressed_plan(Operation.allreduce, count, world,
                           DataType.int8)
    assert p_f16.wire_dtype == DataType.float16
    assert p_q.wire_dtype == DataType.int8
    _, b_none = coefficients(Operation.allreduce, p_none, count, 4, world,
                             rx_buf_bytes=RX)
    _, b_f16 = coefficients(Operation.allreduce, p_f16, count, 4, world,
                            rx_buf_bytes=RX)
    _, b_q = coefficients(Operation.allreduce, p_q, count, 4, world,
                          rx_buf_bytes=RX)
    assert b_none / b_f16 == pytest.approx(2.0)
    assert b_none / b_q == pytest.approx(4 / wire_elem_bytes(4,
                                                             DataType.int8))
    assert b_none / b_q == pytest.approx(3.938, rel=1e-3)
    # and the time prediction follows on a bandwidth-bound link
    lp = LinkParams(alpha=1e-9, beta=1e9)
    t_none = predict(lp, Operation.allreduce, p_none, count, 4, world,
                     rx_buf_bytes=RX)
    t_q = predict(lp, Operation.allreduce, p_q, count, 4, world,
                  rx_buf_bytes=RX)
    assert t_none / t_q == pytest.approx(3.938, rel=1e-2)


def test_tuning_crossovers_shift_with_quantized_wire():
    """Crossover arithmetic runs in WIRE bytes while the registers are
    compared against payload bytes: enabling the quantized lanes must
    stretch the byte thresholds by the compression ratio (the flat-tree
    regime reaches ~3.94x further into payload bytes), leave the
    structural rank crossovers alone, and pin the composition scan to 0
    (compressed calls never route rendezvous)."""
    from accl_tpu.constants import DataType

    link = LinkParams(alpha=25e-6, beta=2.5e9)
    base = tuning_crossovers(link, world=8)
    quant = tuning_crossovers(link, world=8, wire_dtype=DataType.int8)
    ratio = (quant["reduce_flat_tree_max_count_bytes"]
             / base["reduce_flat_tree_max_count_bytes"])
    assert ratio == pytest.approx(4 / (1 + 4 / 256), rel=1e-6)
    assert quant["bcast_flat_tree_max_ranks"] == \
        base["bcast_flat_tree_max_ranks"]
    assert quant["allreduce_composition_max_bytes"] == 0
    assert quant["wire_dtype"] == "int8"
    # cast lanes shift too, by exactly their width ratio
    half = tuning_crossovers(link, world=8, wire_dtype=DataType.bfloat16)
    assert (half["reduce_flat_tree_max_count_bytes"]
            / base["reduce_flat_tree_max_count_bytes"]) == \
        pytest.approx(2.0, rel=1e-6)


def test_facade_autotune_moves_with_quantized_wire(mesh8):
    """ACCL.autotune(wire_dtype=int8) must land DIFFERENT registers than
    the uncompressed tune — the acceptance pin that enabling quantized
    lanes moves the crossovers end to end (model -> registers -> device
    readback)."""
    from accl_tpu import DataType
    from accl_tpu.accl import ACCL

    accl = ACCL(mesh8)
    link = LinkParams(alpha=50e-6, beta=1e9)
    plain = accl.autotune(link=link)
    quant = accl.autotune(link=link, wire_dtype=DataType.int8)
    assert quant.reduce_flat_tree_max_count > plain.reduce_flat_tree_max_count
    assert (quant.reduce_flat_tree_max_count
            / plain.reduce_flat_tree_max_count) == pytest.approx(
        4 / (1 + 4 / 256), rel=1e-2)
    # the quantized tune is live on the device
    assert accl.cclo.tuning().reduce_flat_tree_max_count == \
        quant.reduce_flat_tree_max_count


def test_select_wire_is_a_performance_decision():
    """Compression as a plan dimension: on a latency-dominated call the
    selector keeps the exact fp32 wire (the byte saving cannot clear the
    min_gain bar), on a bandwidth-bound payload it picks the narrowest
    profitable lane (int8 beats the casts)."""
    from accl_tpu.constants import DataType
    from accl_tpu.sequencer.plan import select_wire

    link = LinkParams(alpha=25e-6, beta=2.5e9)
    kw = dict(max_eager_size=4096, eager_rx_buf_size=RX, rx_buf_bytes=RX,
              tuning=TUNING)
    small = select_wire(Operation.allreduce, 16, DataType.float32, 8,
                        link, **kw)
    big = select_wire(Operation.allreduce, 1 << 22, DataType.float32, 8,
                      link, **kw)
    assert small == DataType.none
    assert big == DataType.int8
    # non-fp32 payloads have no compression rows: always uncompressed
    assert select_wire(Operation.allreduce, 1 << 22, DataType.int32, 8,
                       link, **kw) == DataType.none
    # a backend without the quantized ring kernels (quantized_ok=False,
    # from its supports_quantized_wire) gets the runner-up cast lane
    # instead of a pick the facade would reject
    assert select_wire(Operation.allreduce, 1 << 22, DataType.float32, 8,
                       link, quantized_ok=False, **kw) == DataType.float16


def test_predict_sequence_fused_vs_eager_gain():
    """The sequence cost model: wire work is the per-call sum either way;
    fusion saves exactly (k-1) host dispatches."""
    from accl_tpu.sequencer.timing import predict, predict_sequence

    link = LinkParams(alpha=1e-5, beta=1e9)
    world = 4
    calls = []
    for op, count in ((Operation.reduce_scatter, 256),
                      (Operation.allgather, 256),
                      (Operation.bcast, 1024)):
        calls.append((op, plan_for(op, count, world), count, 4))
    t_fused = predict_sequence(link, calls, world, rx_buf_bytes=RX,
                               dispatch_alpha=2e-4)
    t_eager = predict_sequence(link, calls, world, rx_buf_bytes=RX,
                               dispatch_alpha=2e-4, fused=False)
    per_call = sum(predict(link, op, plan, count, 4, world,
                           rx_buf_bytes=RX)
                   for op, plan, count, _ in calls)
    assert t_eager - t_fused == pytest.approx(2 * 2e-4)
    assert t_fused == pytest.approx(per_call + 2e-4)


# ---------------------------------------------------------------------------
# Per-tier links + striped hierarchical cost model (PR 8)
# ---------------------------------------------------------------------------


def _hier_plan(count, stripes=1, inner=2, outer=4, **kw):
    from accl_tpu.sequencer.plan import Plan, Protocol

    return Plan(Protocol.EAGER, Algorithm.HIER_RS_AR_AG, count, 1,
                inner_world=inner, outer_world=outer, stripes=stripes,
                **kw)


def _tiers(ia=2e-6, ib=2e9, oa=300e-6, ob=0.25e9):
    from accl_tpu.sequencer.timing import TierLinks

    return TierLinks(inner=LinkParams(ia, ib), outer=LinkParams(oa, ob))


def test_hier_phase_costs_charge_each_tier_its_own_bytes():
    """One stripe of RS(inner) -> AR(outer) -> AG(inner): phases 1/3
    bill the inner wire, phase 2 the outer — with an int8 outer wire
    only the OUTER phase's bytes shrink (the accounting that lets
    select_tier_wires see int8-on-DCN without pretending ICI
    compressed too)."""
    from accl_tpu.constants import DataType
    from accl_tpu.sequencer.timing import hier_phase_costs

    count, eb = 8192, 4  # 32 KiB over (2, 4)
    phases = hier_phase_costs(_hier_plan(count), count, eb)
    assert [t for t, _m, _b in phases] == ["inner", "outer", "inner"]
    (t1, m1, b1), (t2, m2, b2), (t3, m3, b3) = phases
    chunk = count // 2  # inner chunk == outer shard (exact split here)
    assert b1 == b3 == (2 - 1) * chunk * eb
    assert b2 == 2 * (4 - 1) * (chunk // 4) * eb
    q = hier_phase_costs(_hier_plan(count,
                                    outer_wire_dtype=DataType.int8),
                         count, eb)
    assert q[0][2] == b1 and q[2][2] == b3  # inner untouched
    assert q[1][2] < b2  # outer shrinks to the int8 wire width


def test_predict_tiered_pipeline_formula():
    """T = fill + drain + (S-1) * bottleneck-tier busy time: the S
    stripes overlap across the two link resources, so S=2 costs one
    extra bottleneck period of the HALVED stripe, not a second full
    pass."""
    from accl_tpu.sequencer.timing import hier_phase_costs, predict_tiered

    tl = _tiers()
    count = 1 << 16
    for S in (1, 2, 4):
        plan = _hier_plan(count, stripes=S)
        t = [tl.of(tier).seconds(m, b)
             for tier, m, b in hier_phase_costs(plan, count, 4)]
        want = sum(t) + (S - 1) * max(t[0] + t[2], t[1])
        assert predict_tiered(tl, plan, count, 4) == pytest.approx(want)
    # serialized host: no overlap, S * sum
    plan = _hier_plan(count, stripes=3)
    t = [tl.of(tier).seconds(m, b)
         for tier, m, b in hier_phase_costs(plan, count, 4,
                                            aggregate=True)]
    assert predict_tiered(tl, plan, count, 4, aggregate=True) == \
        pytest.approx(3 * sum(t))


def test_best_stripes_is_the_cost_models_choice():
    """The stripe count is the argmin of the pipelined prediction —
    never a hardcoded constant. On an alpha-dominated outer link more
    stripes mean more slow-tier messages, so S=1 wins; ties break
    toward fewer stripes."""
    from accl_tpu.sequencer.timing import best_stripes, predict_tiered

    tl = _tiers()
    s = best_stripes(tl, 1 << 18, 4, 2, 4)
    best = min(
        (predict_tiered(tl, _hier_plan(1 << 18, stripes=c), 1 << 18, 4), c)
        for c in (1, 2, 4, 8))
    assert predict_tiered(tl, _hier_plan(1 << 18, stripes=s),
                          1 << 18, 4) == pytest.approx(best[0])
    # a stripe count can never exceed the payload
    assert best_stripes(tl, 2, 4, 2, 4) <= 2


def test_hier_crossover_is_contiguous_winning_suffix():
    """The MIN register is the start of the winning suffix: on a
    fast-inner/slow-outer calibration the composition wins from some
    size up (window > 0), every swept size above the returned min
    predicts hier-faster, and an inner link as slow as the outer never
    opens the window."""
    from accl_tpu.sequencer.plan import select_algorithm as sel
    from accl_tpu.sequencer.timing import best_stripes, predict_tiered

    tl = _tiers(ia=2e-6, ib=10e9, oa=300e-6, ob=0.25e9)
    cross = tuning_crossovers(tl.outer, world=8, tier_links=tl,
                              topology=(2, 4))
    lo = cross["hier_allreduce_min_bytes"]
    assert lo > 0
    nb = lo
    while nb <= (1 << 24):
        cnt = nb // 4
        s = best_stripes(tl, cnt, 4, 2, 4)
        t_h = predict_tiered(tl, _hier_plan(cnt, stripes=s), cnt, 4)
        flat = sel(Operation.allreduce, cnt, 4, 8,
                   tuning=TuningParams(bcast_flat_tree_max_ranks=0,
                                       reduce_flat_tree_max_count=0,
                                       reduce_flat_tree_max_ranks=0,
                                       gather_flat_tree_max_count=0),
                   max_eager_size=RX, eager_rx_buf_size=RX)
        t_f = predict(tl.outer, Operation.allreduce, flat, cnt, 4, 8,
                      rx_buf_bytes=RX)
        assert t_h < t_f, f"size {nb} inside the window predicts a loss"
        nb *= 2
    # a world the topology does not factor, or no tier links: off
    assert tuning_crossovers(tl.outer, world=6, tier_links=tl,
                             topology=(2, 4),
                             )["hier_allreduce_min_bytes"] == 0
    assert tuning_crossovers(tl.outer, world=8,
                             )["hier_allreduce_min_bytes"] == 0
    # an inner tier even SLOWER than the outer: the composition's extra
    # inner traffic can only lose, the window stays shut
    inv = _tiers(ia=3000e-6, ib=0.02e9, oa=300e-6, ob=0.25e9)
    assert tuning_crossovers(inv.outer, world=8, tier_links=inv,
                             topology=(2, 4),
                             )["hier_allreduce_min_bytes"] == 0


def test_hier_register_round_trip():
    """configure_tuning_parameters <-> device.tuning() carries the hier
    MIN register like the synth trio, and from_crossovers maps the
    min-bytes crossover onto it."""
    from accl_tpu.device.base import CCLOAddr, CCLODevice
    from accl_tpu.device.tpu_device import TPUDevice

    dev = TPUDevice.__new__(TPUDevice)
    CCLODevice.__init__(dev)
    dev._comm_extents = {}
    dev._comm_cache = {}
    dev.max_rendezvous_size = 32 * 1024
    dev.write(CCLOAddr.HIER_ALLREDUCE_MIN_COUNT, 1 << 18)
    t = TPUDevice.tuning(dev)
    assert t.hier_allreduce_min_count == 1 << 18
    cross = tuning_crossovers(LinkParams(50e-6, 1e9), world=8,
                              tier_links=_tiers(), topology=(2, 4))
    t2 = TuningParams.from_crossovers(cross)
    assert t2.hier_allreduce_min_count == \
        cross["hier_allreduce_min_bytes"]
    assert TuningParams.default().hier_allreduce_min_count == 0


def test_facade_autotune_sets_hier_register_and_tier_wires(mesh8):
    """On a device that declares a two-tier topology, autotune with a
    per-tier calibration (1) opens the HIER_ALLREDUCE_MIN_COUNT window
    from the predicted winning suffix, (2) arbitrates the per-tier
    wires (int8 on the bandwidth-starved outer link, exact inner), and
    (3) the next in-window fp32 selection through the device carries
    BOTH — while a non-fp32 call keeps exact tiers (its arith rows may
    not exist)."""
    from accl_tpu import CallOptions, DataType, Operation
    from accl_tpu.accl import ACCL
    from accl_tpu.device.tpu_device import TPUDevice
    from accl_tpu.sequencer.plan import Algorithm
    from accl_tpu.sequencer.timing import TierLinks

    dev = TPUDevice(mesh8, hier_topology=(2, 4))
    accl = ACCL(device=dev)
    tl = TierLinks(inner=LinkParams(1e-6, 50e9),
                   outer=LinkParams(100e-6, 0.05e9))
    applied = accl.autotune(link=LinkParams(50e-6, 1e9), tier_links=tl)
    assert applied.hier_allreduce_min_count > 0
    assert dev.hier_wires[1] == DataType.int8  # slow outer compresses
    assert dev.hier_wires[0] == DataType.none  # fast inner stays exact

    # 32 MiB payload: beyond every SIZE_GRID window, so the in-window
    # tiered-entry arbitration is inapplicable and the cell pins the
    # COMPOSITION carrying the arbitrated wires (the arbitration
    # itself is pinned in test_plan_selection)
    cnt = max(applied.hier_allreduce_min_count // 4, 1 << 23)
    plan, _, _ = dev._resolve_step(
        CallOptions(scenario=Operation.allreduce, count=cnt, function=0,
                    data_type=DataType.float32), dev._comm_ctx(0))
    assert plan.algorithm == Algorithm.HIER_RS_AR_AG
    assert plan.outer_wire_dtype == DataType.int8
    assert plan.inner_wire_dtype == DataType.none
    p2, _, _ = dev._resolve_step(
        CallOptions(scenario=Operation.allreduce, count=cnt, function=0,
                    data_type=DataType.int32), dev._comm_ctx(0))
    if p2.algorithm == Algorithm.HIER_RS_AR_AG:
        assert p2.outer_wire_dtype == DataType.none


# ---------------------------------------------------------------------------
# alltoall(v): cost shapes pinned to the traced programs + the
# ALLTOALL_COMPRESS_MIN_COUNT crossover
# ---------------------------------------------------------------------------


def _traced_ppermute_bytes(opts, plan, world):
    """Per-rank ppermute operand bytes of the REAL lowered program —
    the executable truth the cost shape must match."""
    from jax.extend import core as jcore

    from accl_tpu.analysis.protocol import (iter_ppermute_eqns,
                                            trace_schedule_jaxpr)

    closed, _, _ = trace_schedule_jaxpr(opts, plan, world)
    return sum(v.aval.size * v.aval.dtype.itemsize
               for eqn in iter_ppermute_eqns(closed)
               for v in eqn.invars
               if not isinstance(v, jcore.Literal))


@pytest.mark.parametrize("wire_name", ["none", "int8"])
@pytest.mark.parametrize("count", [2048, 300])
def test_alltoall_cost_shape_pinned_to_traced_program(wire_name, count):
    """The (P-1)-step pairwise-rotation shape must charge exactly the
    bytes the LOWERED program's ppermutes move — fp32 at payload width,
    the int8 wire at 1 B/elem + the packed per-block scales (the wire
    format pack_wire ships)."""
    from accl_tpu.constants import (CompressionFlags, DataType,
                                    QUANT_BLOCK_ELEMS, QUANT_SCALE_BYTES)
    from accl_tpu.descriptor import CallOptions

    world = 8
    wire = DataType.none if wire_name == "none" else DataType.int8
    comp = (CompressionFlags.ETH_COMPRESSED if wire != DataType.none
            else CompressionFlags.NO_COMPRESSION)
    plan = select_algorithm(Operation.alltoall, count, 4, world, comp,
                            compress_dtype=wire, max_eager_size=4096,
                            eager_rx_buf_size=RX, tuning=TUNING)
    opts = CallOptions(scenario=Operation.alltoall, count=count,
                       data_type=DataType.float32, compress_dtype=wire,
                       compression_flags=comp)
    m, b = coefficients(Operation.alltoall, plan, count, 4, world,
                        rx_buf_bytes=RX)
    traced = _traced_ppermute_bytes(opts, plan, world)
    # one streamed message per rotation step (a rendezvous-size plan
    # pays the address handshake as a second message per step)
    from accl_tpu.sequencer.plan import Protocol

    per = 2 if plan.protocol == Protocol.RENDEZVOUS else 1
    assert m == (world - 1) * per
    if wire == DataType.none:
        assert b == traced == (world - 1) * count * 4
    else:
        # exact traced bytes: codes + 4*ceil(count/256) scale bytes per
        # chunk; the model amortizes the scale per element, so it may
        # sit below the traced ceil by at most one block's scale per hop
        nb = -(-count // QUANT_BLOCK_ELEMS)
        assert traced == (world - 1) * (count + QUANT_SCALE_BYTES * nb)
        assert b <= traced <= b + (world - 1) * QUANT_SCALE_BYTES
        # and the compression is really ~3.94x on aligned payloads
        _, b_fp32 = coefficients(
            Operation.alltoall,
            select_algorithm(Operation.alltoall, count, 4, world,
                             max_eager_size=4096, eager_rx_buf_size=RX,
                             tuning=TUNING),
            count, 4, world, rx_buf_bytes=RX)
        assert b_fp32 / b == pytest.approx(4 / 1.015625, rel=1e-3)


def test_alltoallv_cost_shape_charges_vmax():
    """FLAT_ALLTOALLV hops move max(peer_counts) elements (the padded
    uniform hop shape), in the plan's wire width — pinned against the
    traced program."""
    from accl_tpu.constants import DataType
    from accl_tpu.descriptor import CallOptions

    world, count = 8, 600
    pc = (600, 100, 300, 512, 1, 256, 37, 599)
    plan = select_algorithm(Operation.alltoall, count, 4, world,
                            peer_counts=pc, max_eager_size=4096,
                            eager_rx_buf_size=RX, tuning=TUNING)
    assert plan.algorithm == Algorithm.FLAT_ALLTOALLV
    m, b = coefficients(Operation.alltoall, plan, count, 4, world,
                        rx_buf_bytes=RX)
    assert b == (world - 1) * max(pc) * 4
    opts = CallOptions(scenario=Operation.alltoall, count=count,
                       data_type=DataType.float32, peer_counts=pc)
    assert _traced_ppermute_bytes(opts, plan, world) == b
    # select_wire arbitrates the alltoall family like every other op
    from accl_tpu.sequencer.plan import select_wire

    pick = select_wire(Operation.alltoall, 1 << 20, DataType.float32, 8,
                       LinkParams(5e-6, 2e9), max_eager_size=4096,
                       eager_rx_buf_size=RX, rx_buf_bytes=RX,
                       tuning=TUNING)
    assert pick == DataType.int8  # bandwidth-bound: the quantized wire


def test_alltoall_compress_crossover_contiguous_suffix():
    """The register value is the START of the contiguous winning suffix
    of the predicted int8-vs-fp32 sweep (MIN semantics): predictions at
    and above it must clear the gain bar, the probe just below must
    not."""
    link = LinkParams(alpha=100e-6, beta=2e9)
    cross = tuning_crossovers(link, world=8)
    start = cross["alltoall_compress_min_bytes"]
    assert start > 0

    def gain(nb):
        from accl_tpu.constants import CompressionFlags, DataType

        cnt = max(nb // 4, 1)
        kw = dict(max_eager_size=RX, eager_rx_buf_size=RX,
                  tuning=TuningParams())
        t_f = predict(link, Operation.alltoall,
                      select_algorithm(Operation.alltoall, cnt, 4, 8,
                                       **kw),
                      cnt, 4, 8, rx_buf_bytes=RX)
        t_q = predict(link, Operation.alltoall,
                      select_algorithm(
                          Operation.alltoall, cnt, 4, 8,
                          CompressionFlags.ETH_COMPRESSED,
                          compress_dtype=DataType.int8, **kw),
                      cnt, 4, 8, rx_buf_bytes=RX)
        return (t_f - t_q) / t_f

    nb = start
    while nb <= (1 << 24):
        assert gain(nb) > 0.05, nb
        nb *= 2
    if start > 1 << 10:
        assert gain(start // 2) <= 0.05


def test_alltoall_compress_register_round_trip(mesh8):
    """TuningParams.from_crossovers maps the crossover to the MIN
    register (over-cap clamps to OFF, never widened), and the register
    round-trips through configure_tuning_parameters / CCLOAddr /
    TPUDevice.tuning()."""
    from accl_tpu.accl import ACCL
    from accl_tpu.device.base import CCLOAddr

    base = tuning_crossovers(LinkParams(100e-6, 2e9), world=8)
    tp = TuningParams.from_crossovers(base)
    assert tp.alltoall_compress_min_count == \
        base["alltoall_compress_min_bytes"]
    # over the register cap: a MIN register clamps OFF (0), because
    # min(v, cap) would widen the window into fp32-wins territory
    over = dict(base, alltoall_compress_min_bytes=1 << 30)
    assert TuningParams.from_crossovers(over).alltoall_compress_min_count \
        == 0
    accl = ACCL(mesh8)
    accl.configure_tuning_parameters(tp)
    assert accl.cclo.read(CCLOAddr.ALLTOALL_COMPRESS_MIN_COUNT) == \
        tp.alltoall_compress_min_count
    assert accl.cclo.tuning().alltoall_compress_min_count == \
        tp.alltoall_compress_min_count


# ---------------------------------------------------------------------------
# Compute-communication overlap cost model (ROADMAP item 4)
# ---------------------------------------------------------------------------


def test_striped_coefficients_multiply_messages_not_bytes():
    """A stripe-overlapped EAGER_RING_RS_AG plan's serial cost shape:
    S x the ring's message count (the chains run back to back in the
    serial form), identical total wire bytes."""
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.timing import coefficients, coefficients_aggregate

    n, world = 1 << 18, 8
    base = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, n, 1)
    striped = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG,
                   n // 4, 4, stripes=4)
    m0, b0 = coefficients(Operation.allreduce, base, n, 4, world,
                          rx_buf_bytes=1024)
    m1, b1 = coefficients(Operation.allreduce, striped, n, 4, world,
                          rx_buf_bytes=1024)
    assert m1 == 4 * m0
    assert b1 == pytest.approx(b0)
    am0, ab0 = coefficients_aggregate(Operation.allreduce, base, n, 4,
                                      world, rx_buf_bytes=1024)
    am1, ab1 = coefficients_aggregate(Operation.allreduce, striped, n,
                                      4, world, rx_buf_bytes=1024)
    assert am1 == 4 * am0 and ab1 == pytest.approx(ab0)


def test_predict_overlapped_pipeline_shape():
    """The busy-link vs busy-core pipeline formula, pinned:
    T_serial = compute + S*lam and T_overlap = c + lam + (S-1)*max(c, o)
    with lam the per-stripe chain latency and o = one alpha + the
    stripe's wire bytes."""
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.timing import (LinkParams, coefficients,
                                           predict_overlapped)

    link = LinkParams(500e-6, 0.25e9)
    n, world, S = 1 << 18, 8, 4
    compute_s = 20e-3
    plan = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, n // S, S,
                stripes=S)
    stripe = -(-n // S)
    sp = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, stripe, 1)
    # logp_shape=False: striped plans always run the ring chains
    m, b = coefficients(Operation.allreduce, sp, stripe, 4, world,
                        rx_buf_bytes=1024, logp_shape=False)
    lam = link.seconds(m, b)
    occ = link.seconds(1.0, b)
    c = compute_s / S
    want = c + lam + (S - 1) * max(c, occ)
    got = predict_overlapped(link, plan, n, 4, world,
                             compute_s=compute_s, rx_buf_bytes=1024)
    assert got == pytest.approx(want)
    want_serial = compute_s + S * lam
    got_serial = predict_overlapped(link, plan, n, 4, world,
                                    compute_s=compute_s,
                                    rx_buf_bytes=1024, serial=True)
    assert got_serial == pytest.approx(want_serial)
    # the overlapped form must beat serial in this regime (latency-
    # dominated chains + compute to hide behind)
    assert got < got_serial


def test_best_overlap_stripes_is_the_argmin():
    """best_overlap_stripes returns exactly the candidate minimizing
    predict_overlapped (ties toward fewer stripes), and degenerates to
    1 when a stripe could not hold one world chunk."""
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.timing import (ComputeFit, LinkParams,
                                           best_overlap_stripes,
                                           predict_overlapped)

    link = LinkParams(600e-6, 0.3e9)
    fit = ComputeFit(2e-3, 0.3e9)
    n, world = 1 << 18, 8
    compute_s = fit.seconds(n * 4)
    best = best_overlap_stripes(link, n, 4, world, compute_s=compute_s,
                                rx_buf_bytes=1024)
    costs = {}
    for s in (1, 2, 4, 8):
        plan = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, n, 1,
                    stripes=s)
        costs[s] = predict_overlapped(link, plan, n, 4, world,
                                      compute_s=compute_s,
                                      rx_buf_bytes=1024)
    assert best == min(sorted(costs), key=lambda s: (costs[s], s))
    assert best > 1
    assert best_overlap_stripes(link, 8, 4, world, compute_s=1e-3,
                                rx_buf_bytes=1024) == 1


def test_predict_sequence_overlap_and_serial_forms():
    """predict_sequence with a compute term: the fused form pipelines a
    striped allreduce against the compute (predict_overlapped), the
    eager form pays compute + the striped serial chains + one dispatch
    per call."""
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.timing import (LinkParams, predict_overlapped,
                                           predict_sequence)

    link = LinkParams(600e-6, 0.3e9)
    n, world, S = 1 << 18, 8, 4
    nop = Plan(Protocol.EAGER, Algorithm.NONE, n, 1)
    ar = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, n // S, S,
              stripes=S)
    calls = [(Operation.copy, nop, n, 4),
             (Operation.allreduce, ar, n, 4),
             (Operation.combine, nop, n, 4)]
    compute_s = 15e-3
    alpha_d = 1e-3
    fused = predict_sequence(link, calls, world, rx_buf_bytes=1024,
                             dispatch_alpha=alpha_d, fused=True,
                             compute_s=compute_s)
    want_f = predict_overlapped(link, ar, n, 4, world,
                                compute_s=compute_s,
                                rx_buf_bytes=1024) + alpha_d
    assert fused == pytest.approx(want_f)
    serial = predict_sequence(link, calls, world, rx_buf_bytes=1024,
                              dispatch_alpha=alpha_d, fused=False,
                              compute_s=compute_s)
    want_s = predict_overlapped(link, ar, n, 4, world,
                                compute_s=compute_s, rx_buf_bytes=1024,
                                serial=True) + 3 * alpha_d
    assert serial == pytest.approx(want_s)
    assert serial / fused >= 2.0  # the regime the gate claims


def test_calibrate_compute_recovers_fit():
    """calibrate_compute recovers (alpha, rate) from exact samples —
    the ComputeFit counterpart of the LinkParams fit."""
    from accl_tpu.sequencer.timing import ComputeFit, calibrate_compute

    true = ComputeFit(alpha=3e-3, rate=0.5e9)
    samples = [(b, true.seconds(b))
               for b in (1 << 18, 1 << 20, 1 << 22)]
    fit = calibrate_compute(samples)
    assert fit.alpha == pytest.approx(true.alpha, rel=1e-6)
    assert fit.rate == pytest.approx(true.rate, rel=1e-6)
    assert fit.seconds(1 << 21) == pytest.approx(true.seconds(1 << 21),
                                                 rel=1e-6)


def test_overlap_crossover_contiguous_suffix_and_gating():
    """tuning_crossovers' overlap_min_bytes: absent a compute fit the
    register stays 0; with one it is the start of the contiguous
    winning suffix (every larger swept size must also clear the
    min-gain bar against the serial dispatch->compute twin), scanned
    under the shaped (tier outer) link when one is given."""
    from accl_tpu.sequencer.plan import Algorithm, Plan, Protocol
    from accl_tpu.sequencer.timing import (ComputeFit, LinkParams,
                                           TierLinks,
                                           best_overlap_stripes,
                                           predict_overlapped,
                                           tuning_crossovers)

    link = LinkParams(2e-6, 2e9)
    tiers = TierLinks(inner=LinkParams(2e-6, 2e9),
                      outer=LinkParams(600e-6, 0.3e9))
    fit = ComputeFit(2e-3, 0.3e9)
    no_fit = tuning_crossovers(link, world=8, tier_links=tiers)
    assert no_fit["overlap_min_bytes"] == 0
    cross = tuning_crossovers(link, world=8, tier_links=tiers,
                              compute_fit=fit)
    reg = cross["overlap_min_bytes"]
    assert reg > 0
    # every swept size at/above the register start wins by >5% under
    # the shaped link — contiguity of the suffix, re-derived here
    nb = reg
    while nb <= (1 << 24):
        cnt = nb // 4
        comp = fit.seconds(nb)
        s = best_overlap_stripes(tiers.outer, cnt, 4, 8,
                                 compute_s=comp, rx_buf_bytes=4096)
        plan = Plan(Protocol.EAGER, Algorithm.EAGER_RING_RS_AG, cnt, 1,
                    stripes=s)
        t_on = predict_overlapped(tiers.outer, plan, cnt, 4, 8,
                                  compute_s=comp, rx_buf_bytes=4096)
        t_off = predict_overlapped(tiers.outer, plan, cnt, 4, 8,
                                   compute_s=comp, rx_buf_bytes=4096,
                                   serial=True)
        assert s > 1 and (t_off - t_on) > 0.05 * t_off, nb
        nb *= 2
