"""Schedule synthesis: search determinism, winner correctness (fuzz vs
the numpy oracle through hopdag.execute), library round trips, the
certify gate's reject path, and the select_algorithm crossovers that
make synthesized schedules first-class algorithms.

The selection registers are fitted on the CPU tier and default to
off, so no chip measurement stands behind a synthesized entry yet;
here the PREDICTED side is pinned (the synthesized entry beats the
whole hand-written zoo on its winning cell under the shipped link)
plus the structural properties the library rests on.
"""

import dataclasses
import json
import pathlib
import random

import numpy as np
import pytest

from accl_tpu.constants import (
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    CompressionFlags,
    DataType,
    Operation,
    ReduceFunction,
    TuningParams,
)
from accl_tpu.descriptor import CallOptions
from accl_tpu.analysis import hopdag
from accl_tpu.sequencer import synthesis
from accl_tpu.sequencer.lowering import ScheduleCompiler
from accl_tpu.sequencer.plan import Algorithm, select_algorithm
from accl_tpu.sequencer.timing import (
    coefficients,
    emulator_link,
    predict,
    tuning_crossovers,
)

REPO = pathlib.Path(__file__).resolve().parent.parent

# the shipped calibrated link ACCL.autotune reads (bcast row)
LINK = emulator_link(json.loads(
    (REPO / "accl_log" / "timing_model.json").read_text()))

SELECT_KW = dict(max_eager_size=DEFAULT_MAX_EAGER_SIZE,
                 eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE)


def _oracle(spec, inputs):
    """Exact numpy meaning of the spec's collective over per-rank
    inputs (list of 1-D arrays)."""
    stack = np.stack(inputs)
    if spec.op == "allreduce":
        full = np.sum(stack, axis=0)
        return [full for _ in inputs]
    if spec.op == "allgather":
        cat = np.concatenate(inputs)
        return [cat for _ in inputs]
    if spec.op == "reduce_scatter":
        w = spec.world
        chunk = inputs[0].shape[0] // w
        full = np.sum(stack, axis=0)
        return [full[r * chunk:(r + 1) * chunk] for r in range(w)]
    raise AssertionError(spec.op)


def _inputs(spec, count, rng):
    w = spec.world
    n = count * w if spec.op == "reduce_scatter" else count
    return [rng.integers(-50, 50, n).astype(np.float32)
            for _ in range(w)]


# ---------------------------------------------------------------------------
# Search determinism + certify gate
# ---------------------------------------------------------------------------


def test_search_deterministic_same_winner_dags():
    """Same inputs -> byte-identical winner DAGs (the library can be
    regenerated reproducibly; no hidden RNG in the search)."""
    a = synthesis.search(Operation.allreduce, 8, LINK)
    b = synthesis.search(Operation.allreduce, 8, LINK)
    assert [r.spec for r in a] == [r.spec for r in b]
    assert [hopdag.to_json(r.dag) for r in a] == \
        [hopdag.to_json(r.dag) for r in b]
    assert [r.win_bytes for r in a] == [r.win_bytes for r in b]
    assert a, "search found no allreduce winner at world 8"


def test_search_rejects_uncertifiable_candidate(monkeypatch):
    """A candidate the certifier rejects is DISCARDED loudly, never
    returned — forced by mutating every instantiated DAG to drop a
    combine (the ACCL502 overclaim class)."""
    real = synthesis.instantiate

    def broken(spec, count, func="sum"):
        dag = real(spec, count, func)
        mut = hopdag.mutate(dag, "drop_combine", random.Random(3))
        return mut if mut is not None else dag

    monkeypatch.setattr(synthesis, "instantiate", broken)
    msgs = []
    res = synthesis.search(Operation.allreduce, 4, LINK,
                           log=msgs.append)
    assert res == []
    assert any("DISCARD" in m and "certification" in m for m in msgs)


def test_certify_gate_rejects_mutation_classes():
    """The per-candidate certify gate catches each seeded wrong-result
    class with its stable code (the generator's pruning and the
    certifier agree on what 'correct' means)."""
    entry = synthesis.library()["allreduce_w8_exchange_d1_2_4"]
    dag = entry.load_dag()
    for kind, code in (("drop_combine", "ACCL502"),
                       ("duplicate_combine", "ACCL503")):
        mut = hopdag.mutate(dag, kind, random.Random(11))
        assert mut is not None
        diags = synthesis.certify_dag(mut, entry.spec,
                                      entry.canonical_count)
        assert code in {d.code for d in diags}, kind


def test_invalid_distances_raise():
    bad = synthesis.SynthSpec(key="bad", op="allreduce", world=8,
                              family="exchange", distances=(1, 2, 5))
    with pytest.raises(synthesis.SynthesisError):
        synthesis.instantiate(bad, 16)


# ---------------------------------------------------------------------------
# Library: round trips, verification, windows
# ---------------------------------------------------------------------------


def test_library_nonempty_and_json_round_trip():
    entries = synthesis.library()
    assert entries, "committed synthesized library is empty"
    for key, entry in entries.items():
        dag = entry.load_dag()
        # hop-DAG JSON round trip is exact
        assert hopdag.from_json(hopdag.to_json(dag)) == dag
        # spec round trip is exact
        spec2 = synthesis.SynthSpec.from_json(entry.spec.to_json())
        assert spec2 == entry.spec
        assert spec2.key == key


def test_library_regenerates_and_certifies():
    """The committed DAGs are exactly what the generator produces,
    still certify clean, and their win_bytes windows match fresh
    scoring under the shipped link (the test-side mirror of
    accl_synth.py --verify-library)."""
    msgs = []
    assert synthesis.verify_library(log=msgs.append), "\n".join(msgs)


def test_lower_dag_rejects_cross_rank_reference():
    """A malformed DAG (hand-edited library JSON, future generator bug)
    where one rank's node references another rank's node WITHOUT a hop
    must fail lower_dag loudly — never silently demote to the generic
    masked lowering, whose per-rank env would resolve the reference to
    off-rank garbage at runtime."""
    entry = synthesis.library()[sorted(synthesis.library())[0]]
    dag = entry.load_dag()
    victim = next(n for n in dag.nodes
                  if any(pc.node != hopdag.CONST for pc in n.value))
    other = next(n for n in dag.nodes if n.rank != victim.rank)
    bad_value = tuple(
        dataclasses.replace(pc, node=other.id)
        if pc.node != hopdag.CONST else pc
        for pc in victim.value)
    bad_nodes = tuple(
        dataclasses.replace(n, value=bad_value) if n.id == victim.id
        else n for n in dag.nodes)
    bad = dataclasses.replace(dag, nodes=bad_nodes)
    with pytest.raises(synthesis.SynthesisError,
                       match="cross-rank"):
        synthesis.lower_dag(bad, "ccl")


def test_verify_library_rejects_stale_windows():
    """A scoring-link change that moves the winning windows must fail
    verification, not silently steer select_entry: under a
    pure-bandwidth link the latency-optimal entries stop winning their
    committed cells, and every such entry is reported stale."""
    from accl_tpu.sequencer.timing import LinkParams

    msgs = []
    ok = synthesis.verify_library(
        log=msgs.append, link=LinkParams(alpha=0.0, beta=1e9))
    assert not ok
    assert any("stale selection window" in m for m in msgs), msgs


def test_worlds_without_candidates_yield_empty():
    assert list(synthesis.enumerate_candidates(Operation.allreduce,
                                               6)) == []
    assert synthesis.select_entry(Operation.allreduce, 6, 4096) is None


# ---------------------------------------------------------------------------
# Winner correctness: 30-seed fuzz vs the numpy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("key", sorted(synthesis.library()))
def test_winner_executes_equal_to_oracle_fuzz(key):
    """Every committed winner executes (hopdag.execute, the real
    ops.compression reference for the int8 lanes) equal to the exact
    numpy oracle across 30 seeds: BITWISE on exact integer payloads for
    the fp32 entries; within the documented blockwise-quantization
    bound for the int8-wire entries (one quantization pass per step on
    the partial's path)."""
    entry = synthesis.library()[key]
    spec = entry.spec
    for seed in range(30):
        rng = np.random.default_rng(1000 + seed)
        count = int(rng.integers(1, 5)) * spec.world * 8
        dag = synthesis.instantiate(spec, count)
        inputs = _inputs(spec, count, rng)
        outs = hopdag.execute(dag, [[x] for x in inputs])
        want = _oracle(spec, inputs)
        for r in range(spec.world):
            if spec.wire == "int8":
                # error bound: k quantization passes, each within
                # block_amax/254 per element; |partial| is bounded by
                # the elementwise absolute sum
                k = len(spec.distances)
                bound = k * np.max(np.sum(np.abs(np.stack(inputs)),
                                          axis=0)) / 127.0
                np.testing.assert_allclose(outs[r], want[r],
                                           atol=float(bound), rtol=0)
            else:
                np.testing.assert_array_equal(outs[r], want[r])


def test_max_fold_winner_bitwise():
    entry = synthesis.library()["allreduce_w8_exchange_d1_2_4"]
    rng = np.random.default_rng(7)
    dag = synthesis.instantiate(entry.spec, 32, func="max")
    inputs = _inputs(entry.spec, 32, rng)
    outs = hopdag.execute(dag, [[x] for x in inputs])
    want = np.max(np.stack(inputs), axis=0)
    for o in outs:
        np.testing.assert_array_equal(o, want)


# ---------------------------------------------------------------------------
# Lowered programs: compiled == hopdag.execute == oracle on the mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("key", [
    "allreduce_w8_exchange_d1_2_4",   # symmetric fast-path lowering
    "allreduce_w8_rs_ag_d1_2_4",      # generic masked lowering
    "reduce_scatter_w8_halving_d1_2_4",
    "allgather_w8_doubling_d1_2_4",
])
def test_lowered_program_bitwise_vs_execute(mesh8, key):
    entry = synthesis.library()[key]
    spec = entry.spec
    count = 32
    dag = synthesis.instantiate(spec, count)
    body = synthesis.lower_dag(dag, "ccl")
    fn = ScheduleCompiler(mesh8, use_pallas_ring=False)._finalize(body, 1)
    rng = np.random.default_rng(5)
    inputs = _inputs(spec, count, rng)
    out = np.asarray(fn(np.stack(inputs)))
    ex = hopdag.execute(dag, [[x] for x in inputs])
    for r in range(spec.world):
        np.testing.assert_array_equal(out[r], ex[r])
    want = _oracle(spec, inputs)
    for r in range(spec.world):
        np.testing.assert_array_equal(out[r], want[r])


def test_lowered_via_full_plan_path(mesh8):
    """descriptor + SYNTHESIZED plan -> ScheduleCompiler.lower: the
    first-class-algorithm seam, including a non-world-multiple count
    through the rs_ag padding rule."""
    tuning = TuningParams(synth_allreduce_max_count=1 << 23)
    count = 300_000  # 1.2 MB: inside the w8 rs_ag window, 300000 % 8 != 0
    plan = select_algorithm(Operation.allreduce, count, 4, 8,
                            tuning=tuning, **SELECT_KW)
    assert plan.algorithm == Algorithm.SYNTHESIZED
    assert plan.synth_key == "allreduce_w8_rs_ag_d1_2_4"
    opts = CallOptions(scenario=Operation.allreduce, count=count,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    fn = ScheduleCompiler(mesh8, use_pallas_ring=False).lower(opts, plan)
    rng = np.random.default_rng(9)
    x = rng.integers(-50, 50, (8, count)).astype(np.float32)
    out = np.asarray(fn(x))
    np.testing.assert_array_equal(out, np.tile(np.sum(x, axis=0),
                                               (8, 1)))


def test_unknown_synth_key_raises(mesh8):
    from accl_tpu.sequencer.plan import Plan, Protocol

    plan = Plan(Protocol.EAGER, Algorithm.SYNTHESIZED, 64, 1,
                synth_key="no_such_entry")
    opts = CallOptions(scenario=Operation.allreduce, count=64,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    with pytest.raises(synthesis.SynthesisError):
        ScheduleCompiler(mesh8, use_pallas_ring=False).lower(opts, plan)


# ---------------------------------------------------------------------------
# Selection: crossover registers, windows, and the predicted-win bar
# ---------------------------------------------------------------------------


def test_registers_default_off():
    plan = select_algorithm(Operation.allreduce, 1024, 4, 8,
                            tuning=TuningParams.default(), **SELECT_KW)
    assert plan.algorithm != Algorithm.SYNTHESIZED


def test_select_algorithm_crossover_wins_cell_loses_outside():
    """The synthesized entry is picked exactly inside (register AND
    window): at its winning cell; not above the register; not in the
    window gap between the exchange and rs_ag entries; not for worlds
    without an entry; not for streamed or cast-compressed calls."""
    tuning = TuningParams(synth_allreduce_max_count=16384)
    inside = select_algorithm(Operation.allreduce, 1024, 4, 8,
                              tuning=tuning, **SELECT_KW)
    assert inside.algorithm == Algorithm.SYNTHESIZED
    assert inside.synth_key == "allreduce_w8_exchange_d1_2_4"
    above = select_algorithm(Operation.allreduce, 65536, 4, 8,
                             tuning=tuning, **SELECT_KW)
    assert above.algorithm == Algorithm.EAGER_RING_RS_AG
    # register wide open but the 128 KB cell sits in the gap between
    # the exchange window (<=16 KB) and the rs_ag window (>=1 MB):
    # selection falls through to the hand-written zoo
    wide = TuningParams(synth_allreduce_max_count=1 << 23)
    gap = select_algorithm(Operation.allreduce, 32768, 4, 8,
                           tuning=wide, **SELECT_KW)
    assert gap.algorithm == Algorithm.EAGER_RING_RS_AG
    in_rs_ag = select_algorithm(Operation.allreduce, 1 << 19, 4, 8,
                                tuning=wide, **SELECT_KW)
    assert in_rs_ag.algorithm == Algorithm.SYNTHESIZED
    assert in_rs_ag.synth_key == "allreduce_w8_rs_ag_d1_2_4"
    # no library entry for world 6
    w6 = select_algorithm(Operation.allreduce, 1024, 4, 6,
                          tuning=wide, **SELECT_KW)
    assert w6.algorithm != Algorithm.SYNTHESIZED
    # cast-compressed calls keep the hand-written lanes (only the int8
    # blockwise wire has synthesized entries)
    fp16 = select_algorithm(Operation.allreduce, 1024, 4, 8,
                            CompressionFlags.ETH_COMPRESSED,
                            tuning=wide, compress_dtype=DataType.float16,
                            **SELECT_KW)
    assert fp16.algorithm != Algorithm.SYNTHESIZED


def test_select_algorithm_never_substitutes_int8_entries():
    """Quantized calls must NOT silently get a synthesized schedule,
    even inside the register window: the int8 exchange entries re-encode
    the running partial every hop, so ranks fold differently-quantized
    copies and finish apart by up to the per-block bound — while the
    hand-written quantized ring they would replace is documented
    rank-consistent. The entries stay explicitly addressable."""
    tuning = TuningParams(synth_allreduce_max_count=16384)
    plan = select_algorithm(Operation.allreduce, 1024, 4, 8,
                            CompressionFlags.ETH_COMPRESSED,
                            tuning=tuning, compress_dtype=DataType.int8,
                            **SELECT_KW)
    assert plan.algorithm != Algorithm.SYNTHESIZED
    # the entry itself remains first-class for explicit use
    key = synthesis.select_entry(Operation.allreduce, 8, 4096,
                                 wire="int8")
    assert key is not None and key.endswith("_int8")


def test_int8_exchange_entries_are_rank_divergent():
    """The reason for the rule above, pinned: executing an int8
    exchange entry yields per-rank answers that are each within the
    documented quantization bound of the oracle but NOT equal to each
    other, while the fp32 twin is bitwise rank-consistent."""
    count = 256
    rng = np.random.default_rng(11)
    x = (rng.standard_normal((2, count)) *
         np.array([[1.0], [100.0]])).astype(np.float32)
    e8 = synthesis.entry_for_key("allreduce_w2_exchange_d1_int8")
    outs = hopdag.execute(synthesis.instantiate(e8.spec, count),
                          [[x[r]] for r in range(2)])
    assert not np.array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    e32 = synthesis.entry_for_key("allreduce_w2_exchange_d1")
    o32 = hopdag.execute(synthesis.instantiate(e32.spec, count),
                         [[x[r]] for r in range(2)])
    assert np.array_equal(np.asarray(o32[0]), np.asarray(o32[1]))


def test_crossovers_set_registers_and_selection_follows():
    """ACCL.autotune's path end to end: tuning_crossovers on the
    shipped measured link -> TuningParams.from_crossovers -> the
    synthesized entry is selected at its winning cell."""
    cross = tuning_crossovers(LINK, world=8)
    assert cross["synth_allreduce_max_bytes"] >= 16384
    assert cross["synth_reduce_scatter_max_bytes"] >= 16384
    tuning = TuningParams.from_crossovers(cross)
    assert tuning.synth_allreduce_max_count > 0
    plan = select_algorithm(Operation.allreduce, 1024, 4, 8,
                            tuning=tuning, **SELECT_KW)
    assert plan.algorithm == Algorithm.SYNTHESIZED


def test_predicted_win_beats_whole_hand_written_zoo():
    """The predicted half of the acceptance bar: at the winning cell
    the synthesized schedule beats EVERY hand-written algorithm under
    the shipped link (a measured half needs a chip cell the entry
    serves)."""
    count = 1024  # 4 KB fp32, world 8
    key = synthesis.select_entry(Operation.allreduce, 8, 4096)
    assert key == "allreduce_w8_exchange_d1_2_4"
    spec = synthesis.entry_for_key(key).spec
    t_synth = synthesis.predict_spec(LINK, spec, count, 4)
    t_hand = synthesis.hand_written_best(LINK, Operation.allreduce,
                                         count, 4, 8)
    assert t_synth < t_hand
    # and through the generic predict() path on the selected Plan
    tuning = TuningParams(synth_allreduce_max_count=16384)
    plan = select_algorithm(Operation.allreduce, count, 4, 8,
                            tuning=tuning, **SELECT_KW)
    assert predict(LINK, Operation.allreduce, plan, count, 4, 8,
                   rx_buf_bytes=4096) == pytest.approx(t_synth)


def test_timing_coefficients_for_synth_plans():
    """SYNTHESIZED plans cost through the library entry's step profile:
    exchange at world 8 = 3 messages, 3 payloads of wire bytes."""
    tuning = TuningParams(synth_allreduce_max_count=16384)
    plan = select_algorithm(Operation.allreduce, 1024, 4, 8,
                            tuning=tuning, **SELECT_KW)
    m, b = coefficients(Operation.allreduce, plan, 1024, 4, 8,
                        rx_buf_bytes=4096)
    assert m == 3
    assert b == 3 * 4096


def test_exchange_memory_register_round_trip():
    """configure_tuning_parameters <-> device.tuning() carries the new
    synth registers like the reference's six."""
    from accl_tpu.device.base import CCLOAddr, CCLODevice
    from accl_tpu.device.tpu_device import TPUDevice

    dev = TPUDevice.__new__(TPUDevice)
    CCLODevice.__init__(dev)
    dev._comm_extents = {}
    dev._comm_cache = {}
    dev.max_rendezvous_size = 32 * 1024
    dev.write(CCLOAddr.SYNTH_ALLREDUCE_MAX_COUNT, 4096)
    dev.write(CCLOAddr.SYNTH_REDUCE_SCATTER_MAX_COUNT, 8192)
    t = TPUDevice.tuning(dev)
    assert t.synth_allreduce_max_count == 4096
    assert t.synth_allgather_max_count == 0
    assert t.synth_reduce_scatter_max_count == 8192


# ---------------------------------------------------------------------------
# Pod-scale synthesis: tiered search space, beam pruning, w16-w256
# enumeration (ISSUE 12)
# ---------------------------------------------------------------------------

TIER_LINKS = None


def _shipped_tiers():
    global TIER_LINKS
    if TIER_LINKS is None:
        TIER_LINKS = synthesis.shipped_tier_links()
    return TIER_LINKS


def test_tiered_search_deterministic_and_rediscovers_composition():
    """Same inputs -> byte-identical tiered winner DAGs, and the
    ring x ring member (the hand-written striped composition's exact
    structure) is enumerated but scores as a keep-out TIE, never a
    winner — the search rediscovers the composition and ships only
    what beats it."""
    tl = _shipped_tiers()
    a = synthesis.search(Operation.allreduce, 16, LINK, tiers=(4, 4),
                         tier_links=tl)
    b = synthesis.search(Operation.allreduce, 16, LINK, tiers=(4, 4),
                         tier_links=tl)
    assert [r.spec for r in a] == [r.spec for r in b]
    assert [hopdag.to_json(r.dag) for r in a] == \
        [hopdag.to_json(r.dag) for r in b]
    assert a, "tiered search found no winner at 4x4"
    keys = {s.key for s in
            synthesis.enumerate_tiered_candidates(16, (4, 4))}
    assert "allreduce_w16_t4x4_ring_ring_d1_o1" in keys
    assert all(r.spec.family != "t_ring_ring" for r in a)
    # the rediscovery, numerically: the ring x ring member predicts
    # EXACTLY the striped composition's serial form (a tie, not a win)
    from accl_tpu.sequencer.plan import Plan, Protocol
    from accl_tpu.sequencer.timing import predict_tiered

    rr = next(s for s in
              synthesis.enumerate_tiered_candidates(16, (4, 4))
              if s.family == "t_ring_ring")
    cnt = 4096
    hplan = Plan(Protocol.EAGER, Algorithm.HIER_RS_AR_AG, cnt, 1,
                 inner_world=4, outer_world=4, stripes=1)
    assert synthesis.predict_spec_tiered(tl, rr, cnt, 4) == \
        pytest.approx(predict_tiered(tl, hplan, cnt, 4))


def test_beam_finds_exhaustive_winner_at_w16():
    """Beam pruning must be admissible in practice: at w16 — where the
    exhaustive search is still tractable — the beam-1 search's winner
    is one of the exhaustive winners and wins at least one of the same
    cells (the alpha-beta bound ranks candidates exactly as the full
    scoring does, so the top advantage survives the prune)."""
    tl = _shipped_tiers()
    exhaustive = synthesis.search(Operation.allreduce, 16, LINK,
                                  tiers=(4, 4), tier_links=tl)
    beam = synthesis.search(Operation.allreduce, 16, LINK, beam=1,
                            tiers=(4, 4), tier_links=tl)
    assert len(beam) == 1
    ex_by_key = {r.spec.key: r for r in exhaustive}
    br = beam[0]
    assert br.spec.key in ex_by_key
    assert br.win_bytes == ex_by_key[br.spec.key].win_bytes
    lo, hi = br.win_bytes
    assert any(lo <= nb <= hi for r in exhaustive
               for nb in range(r.win_bytes[0], r.win_bytes[1] + 1)
               if r.win_bytes[0] <= nb <= r.win_bytes[1])
    # flat space too: beam-1 keeps the best predicted advantage
    flat_ex = synthesis.search(Operation.allreduce, 16, LINK)
    flat_beam = synthesis.search(Operation.allreduce, 16, LINK, beam=1)
    assert len(flat_beam) == 1
    assert flat_beam[0].spec.key in {r.spec.key for r in flat_ex}


def test_enumeration_scales_to_w256():
    """The branch-and-bound DFS finds the dominance representative at
    pod scale without the combinations blowup: w64-w256 enumerate in
    well under a second and yield the recursive-doubling tuple."""
    import time

    t0 = time.time()
    for world in (64, 128, 256):
        cands = list(synthesis.enumerate_candidates(
            Operation.allreduce, world))
        assert cands, f"no candidates at w{world}"
        k = world.bit_length() - 1
        assert cands[0].distances == tuple(1 << i for i in range(k))
        tiered = list(synthesis.enumerate_tiered_candidates(
            world, (16, world // 16)))
        assert tiered, f"no tiered candidates at w{world}"
    assert time.time() - t0 < 5.0, "enumeration no longer scales"
    # non-power-of-two axes stay searchable through the ring kinds
    odd = list(synthesis.enumerate_tiered_candidates(24, (3, 8)))
    assert odd and all(s.family.startswith("t_ring") for s in odd)


def test_tiered_costs_charge_each_tier_separately():
    """The tier annotation is load-bearing: hop_layout's per-hop tiers
    match the per-tier cost split, inner hops never bill the outer
    link, and predict_spec_tiered = sum of each tier's alpha-beta
    charge (the hier_phase_costs accounting, generalized)."""
    from accl_tpu.sequencer.timing import LinkParams, TierLinks

    spec = synthesis.entry_for_key(
        "allreduce_w8_t2x4_lg_rs_ag_d1_o1_2").spec
    layout = synthesis.hop_layout(spec)
    elems = synthesis._tiered_step_elems(spec, 1024)
    assert [t for t, _ in layout] == [t for t, _ in elems]
    phases = synthesis.tiered_phase_costs(spec, 1024, 4)
    by_tier = {t: (m, b) for t, m, b in phases}
    # inner: 1 RS hop + 1 AG hop of the 1/L chunk; outer: 4 rs_ag hops
    assert by_tier["inner"][0] == 2
    assert by_tier["outer"][0] == 4
    assert by_tier["inner"][1] == 2 * (1024 // 2) * 4
    # an infinitely fast inner link leaves exactly the outer charge
    fast_inner = TierLinks(inner=LinkParams(0.0, 1e18),
                           outer=LinkParams(1e-4, 1e9))
    t = synthesis.predict_spec_tiered(fast_inner, spec, 1024, 4)
    m_o, b_o = by_tier["outer"]
    assert t == pytest.approx(1e-4 * m_o + b_o / 1e9)


def test_library_carries_certified_w16_and_tiered_entries():
    """The committed library covers pod-scale worlds: w16 entries for
    every op plus tiered entries for the (2,4) and (4,4) factorings
    (the acceptance bar's w16+ clause; certification itself is
    test_library_regenerates_and_certifies)."""
    entries = synthesis.library()
    w16 = {k for k, e in entries.items()
           if e.spec.world >= 16 and not e.spec.tiers}
    assert any(k.startswith("allreduce_w16") for k in w16)
    assert any(k.startswith("allgather_w16") for k in w16)
    assert any(k.startswith("reduce_scatter_w16") for k in w16)
    tiered = {tuple(e.spec.tiers) for e in entries.values()
              if e.spec.tiers}
    assert (2, 4) in tiered and (4, 4) in tiered


def test_tiered_entry_lowered_bitwise_vs_execute(mesh8):
    """The compiled tiered program (hops as RankMap-perm ppermutes via
    the generic lowering) is bitwise the hop-DAG's numeric execution
    and the numpy oracle on the 8-dev mesh — including the padding rule
    for counts that do not chunk by inner*outer."""
    entry = synthesis.library()["allreduce_w8_t2x4_lg_rs_ag_d1_o1_2"]
    spec = entry.spec
    count = 96
    dag = synthesis.instantiate(spec, count)
    body = synthesis.lower_dag(dag, "ccl")
    fn = ScheduleCompiler(mesh8, use_pallas_ring=False)._finalize(body, 1)
    rng = np.random.default_rng(31)
    inputs = _inputs(spec, count, rng)
    out = np.asarray(fn(np.stack(inputs)))
    ex = hopdag.execute(dag, [[x] for x in inputs])
    want = _oracle(spec, inputs)
    for r in range(spec.world):
        np.testing.assert_array_equal(out[r], ex[r])
        np.testing.assert_array_equal(out[r], want[r])
    # full plan path with a non-chunking count (pad + trim)
    tuning = TuningParams(hier_allreduce_min_count=1)
    plan = select_algorithm(
        Operation.allreduce, 300, 4, 8, tuning=tuning, topology=(2, 4),
        tier_links=_shipped_tiers(), **SELECT_KW)
    assert plan.algorithm == Algorithm.SYNTHESIZED
    assert synthesis.entry_for_key(plan.synth_key).spec.tiers == (2, 4)
    opts = CallOptions(scenario=Operation.allreduce, count=300,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    fn2 = ScheduleCompiler(mesh8, use_pallas_ring=False).lower(opts, plan)
    x = rng.integers(-50, 50, (8, 300)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(fn2(x)), np.tile(np.sum(x, axis=0), (8, 1)))


def test_tier_layout_mismatch_is_fatal():
    """A DAG whose hops do not match the spec's tier annotation must
    fail the lowering cross-check loudly — a mis-annotated hop would
    silently bill DCN traffic to ICI (and compile the wrong perm)."""
    entry = synthesis.library()["allreduce_w8_t2x4_lg_exchange_d1_o1_2"]
    spec = entry.spec
    dag = synthesis.instantiate(spec, entry.canonical_count)
    synthesis._check_tier_layout(dag, spec)  # the real pair is clean
    lying = dataclasses.replace(spec, family="t_lg_ring",
                                outer_distances=(1,))
    with pytest.raises(synthesis.SynthesisError, match="tier|channels"):
        synthesis._check_tier_layout(dag, lying)


def test_select_entry_tiers_filter_and_crossover_exclusion():
    """Flat selection (tiers=()) never returns a tiered entry, tiered
    selection only matches its exact factoring, and the flat synth
    registers' crossover scan ignores tiered entries (their windows are
    per-tier predictions, meaningless on the uniform link)."""
    key = synthesis.select_entry(Operation.allreduce, 8, 4096)
    assert key is not None
    assert not synthesis.entry_for_key(key).spec.tiers
    tkey = synthesis.select_entry(Operation.allreduce, 8, 4096,
                                  tiers=(2, 4))
    assert tkey is not None
    assert synthesis.entry_for_key(tkey).spec.tiers == (2, 4)
    assert synthesis.select_entry(Operation.allreduce, 8, 4096,
                                  tiers=(4, 2)) is None
    # w16 flat registers derive only from flat w16 entries; with the
    # tiered entries committed the scan must still match a
    # tiered-library-free scoring of the same flat entries
    cross = tuning_crossovers(LINK, world=16)
    assert cross["synth_allreduce_max_bytes"] > 0
    flat16 = [e for e in synthesis.library().values()
              if e.spec.op == "allreduce" and e.spec.world == 16
              and not e.spec.wire and not e.spec.tiers]
    assert flat16, "flat w16 allreduce entries missing"


def test_export_prunes_stale_in_scope_entries(tmp_path, monkeypatch):
    """--export removes in-scope library files that no longer win any
    cell (otherwise verify_library's stale-window FAIL could never be
    resolved by re-exporting) while out-of-scope entries survive."""
    import sys

    sys.path.insert(0, str(REPO / "tools"))
    try:
        import accl_synth
    finally:
        sys.path.pop(0)

    src = synthesis.library_dir()
    stale = tmp_path / "allreduce_w2_exchange_stale.json"
    stale.write_text((src / "allreduce_w2_exchange_d1.json").read_text())
    kept = tmp_path / "allreduce_w4_exchange_d1_2.json"
    kept.write_text((src / "allreduce_w4_exchange_d1_2.json").read_text())
    monkeypatch.setattr(synthesis, "library_dir", lambda: tmp_path)
    args = type("A", (), dict(
        worlds=[2], ops=["allreduce"], tiers=None, beam=None,
        timing_model=str(REPO / "accl_log" / "timing_model.json"),
        alpha_us=None, beta_gbps=None))()
    try:
        assert accl_synth.run_search(args, export=True)
        assert not stale.exists(), "in-scope stale entry not pruned"
        assert kept.exists(), "out-of-scope entry must be kept"
        assert (tmp_path / "allreduce_w2_exchange_d1.json").exists()
    finally:
        synthesis.clear_library_cache()
