"""Semantic certifier: contribution-set abstract interpretation.

Pins the analysis/semantics.py + analysis/hopdag.py contract:

  * every shipping schedule family LIFTS into the hop-DAG IR and
    CERTIFIES against its declared collective (including quantized-wire
    and segmented variants);
  * the lifted DAG is numerically faithful: executing it reproduces the
    collective bitwise (exact payloads) or within the documented
    quantization bound;
  * seeded single-hop mutations (drop/duplicate/reorder a combine, swap
    same-hop payloads) are rejected with the RIGHT ACCL5xx code AND
    execute to wrong numbers — zero certified-clean/numeric-mismatch
    disagreements;
  * the semantic corpus fixtures pass the linter/model checker ALONE
    (the class neither predecessor catches) and fail exactly in the
    certifier; ACCL504 complements, never duplicates, the hazard
    pass's batch-level ACCL101;
  * the pass rides the DEFAULT lint tier (SequenceLinter wiring, cache,
    in-band budget).
"""

import json
import pathlib
import random
import zlib

import numpy as np
import pytest

from accl_tpu.constants import (
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_MAX_RENDEZVOUS_SIZE,
    CompressionFlags,
    DataType,
    Operation,
    ReduceFunction,
    TuningParams,
)
from accl_tpu.descriptor import CallOptions
from accl_tpu.analysis import CODES, SequenceLinter, hopdag, semantics
from accl_tpu.analysis.diagnostics import enforce
from accl_tpu.analysis.hopdag import (
    HopDag,
    Node,
    Piece,
    concat_values,
    const_value,
    slice_value,
    splice_value,
)
from accl_tpu.errors import LintError
from accl_tpu.sequencer.plan import select_algorithm

CORPUS = pathlib.Path(__file__).parent.parent / "tools" / "lint_corpus"

_TREES = TuningParams(
    gather_flat_tree_max_fanin=2,
    gather_flat_tree_max_count=64,
    bcast_flat_tree_max_ranks=2,
    reduce_flat_tree_max_ranks=2,
    reduce_flat_tree_max_count=64,
    allreduce_composition_max_count=1 << 30,
)


def _opts_plan(scen, count, world, *, root=0, func=ReduceFunction.SUM,
               wire=DataType.none, tuning=None, peer_counts=()):
    comp = (CompressionFlags.ETH_COMPRESSED if wire != DataType.none
            else CompressionFlags.NO_COMPRESSION)
    rsd = root if scen not in (Operation.send, Operation.recv) else root
    opts = CallOptions(scenario=scen, count=count, root_src_dst=rsd,
                       function=int(func), data_type=DataType.float32,
                       compress_dtype=wire, compression_flags=comp,
                       peer_counts=tuple(peer_counts))
    plan = select_algorithm(
        scen, count, 4, world, comp,
        max_eager_size=DEFAULT_MAX_EAGER_SIZE,
        eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
        tuning=tuning or TuningParams.default(DEFAULT_MAX_RENDEZVOUS_SIZE),
        compress_dtype=wire, peer_counts=tuple(peer_counts))
    return opts, plan


def _lift(scen, count, world, **kw):
    opts, plan = _opts_plan(scen, count, world, **kw)
    dag = semantics.lift_call(opts, plan, world)
    return opts, plan, dag


def _certify(opts, dag, world):
    return semantics.certify(dag, semantics.collective_spec(opts, world),
                             opts.scenario.name)


# ---------------------------------------------------------------------------
# Hop-DAG IR
# ---------------------------------------------------------------------------


class TestHopDag:
    def test_piece_algebra(self):
        v = concat_values((Piece(4, 0),), const_value(2, 1.5), (Piece(3, 1, 5),))
        assert hopdag.value_length(v) == 9
        s = slice_value(v, 3, 4)
        assert hopdag.value_length(s) == 4
        assert s[0] == Piece(1, 0, 3)
        assert s[1].fill == 1.5 and s[1].node == hopdag.CONST
        assert s[2] == Piece(1, 1, 5)
        sp = splice_value(v, (Piece(2, 2),), 4)
        assert hopdag.value_length(sp) == 9
        assert sp[1] == Piece(2, 2)

    def test_slice_past_end_is_stale_fill(self):
        v = (Piece(4, 0),)
        s = slice_value(v, 2, 6)
        assert hopdag.value_length(s) == 6
        assert s[-1].node == hopdag.CONST

    def test_json_roundtrip(self):
        _, _, dag = _lift(Operation.allreduce, 8, 2)
        dag2 = hopdag.from_json(json.loads(json.dumps(hopdag.to_json(dag))))
        assert dag2.nodes == dag.nodes
        assert dag2.outputs == dag.outputs
        assert (dag2.world, dag2.n_in, dag2.in_elems, dag2.out_elems) == (
            dag.world, dag.n_in, dag.in_elems, dag.out_elems)

    def test_validate_order_clean_on_lifted(self):
        for scen in (Operation.allreduce, Operation.alltoall):
            _, _, dag = _lift(scen, 8, 4)
            assert hopdag.validate_order(dag) == []

    def test_validate_order_flags_forward_ref(self):
        nodes = (
            Node(0, "arg", 0, 4, arg=0),
            Node(1, "send", 0, 4, value=(Piece(4, 2),), hop=0, peer=1),
            Node(2, "arg", 1, 4, arg=0),
            Node(3, "recv", 1, 4, hop=0, peer=0),
        )
        dag = HopDag(2, 1, 4, 4, nodes,
                     ((Piece(4, 0),), (Piece(4, 3),)))
        diags = hopdag.validate_order(dag)
        assert [d.code for d in diags] == ["ACCL504"]

    def test_rank_programs_match_protocol(self):
        from accl_tpu.analysis.protocol import simulate

        _, _, dag = _lift(Operation.allgather, 4, 4)
        programs = hopdag.rank_programs(dag)
        assert simulate(programs, blocking_sends=False) == []

    def test_execute_stale_reads_zeros(self):
        nodes = (
            Node(0, "arg", 0, 4, arg=0),
            Node(1, "send", 0, 4, value=(Piece(4, 3),), hop=0, peer=1),
            Node(2, "recv", 1, 4, hop=0, peer=0),
            Node(3, "cast", 0, 4, value=(Piece(4, 0),)),
        )
        dag = HopDag(2, 1, 4, 4, nodes,
                     ((Piece(4, 0),), (Piece(4, 2),)))
        outs = hopdag.execute(dag, [[np.arange(4, dtype=np.float32)],
                                    [np.arange(4, dtype=np.float32)]])
        # the send read node 3 before it ran: rank 1 receives stale zeros
        assert np.array_equal(outs[1], np.zeros(4, np.float32))


# ---------------------------------------------------------------------------
# Specs + certification over shipping schedules
# ---------------------------------------------------------------------------

_FAMILY_GRID = [
    # (scenario, count, world, kwargs)
    (Operation.bcast, 12, 4, {}),
    (Operation.bcast, 12, 5, {"root": 3}),
    (Operation.bcast, 8, 4, {"tuning": _TREES}),
    (Operation.scatter, 6, 4, {"root": 2}),
    (Operation.gather, 6, 4, {"root": 1}),
    (Operation.gather, 6, 5, {"tuning": _TREES}),
    (Operation.reduce, 16, 4, {"root": 2}),
    (Operation.reduce, 16, 4, {"root": 1, "func": ReduceFunction.MAX}),
    (Operation.reduce, 16, 6, {"tuning": _TREES}),
    (Operation.allgather, 8, 4, {}),
    (Operation.allreduce, 16, 4, {}),
    (Operation.allreduce, 16, 3, {"func": ReduceFunction.MAX}),
    (Operation.allreduce, 600, 4, {}),  # multi-segment eager ring
    (Operation.allreduce, 16, 4, {"tuning": _TREES}),  # composed
    (Operation.reduce_scatter, 8, 4, {}),
    (Operation.alltoall, 6, 4, {}),
    # the quantized exchange: packed codes+scales, one message per hop
    # (per-hop encode at 6; the block-aligned encode-once form at 256)
    (Operation.alltoall, 6, 4, {"wire": DataType.int8}),
    (Operation.alltoall, 256, 4, {"wire": DataType.int8}),
    # the capacity-bounded exchange: routed prefixes + PROVEN zero
    # tails (the MoE overflow drop as descriptors), exact and quantized
    (Operation.alltoall, 10, 4, {"peer_counts": (10, 3, 7, 1)}),
    (Operation.alltoall, 300, 4, {"peer_counts": (128, 300, 9, 64),
                                  "wire": DataType.int8}),
    (Operation.send, 16, 4, {"root": 1 | (3 << 16)}),
    (Operation.allreduce, 300, 4, {"wire": DataType.int8}),
    (Operation.reduce_scatter, 16, 4, {"wire": DataType.int8}),
    (Operation.allgather, 16, 4, {"wire": DataType.int8}),
    # cast lanes: compress/decompress surface as cast nodes (identity
    # provenance, numeric fidelity kept for the executor)
    (Operation.allreduce, 32, 4, {"wire": DataType.float16}),
    (Operation.allgather, 8, 4, {"wire": DataType.bfloat16}),
]


class TestCertifyShippingSchedules:
    @pytest.mark.parametrize("scen,count,world,kw", _FAMILY_GRID,
                             ids=lambda v: getattr(v, "name", str(v)))
    def test_family_certifies_clean(self, scen, count, world, kw):
        opts, _, dag = _lift(scen, count, world, **kw)
        assert _certify(opts, dag, world) == []

    def test_barrier_has_no_payload_contract(self):
        opts, _ = _opts_plan(Operation.barrier, 0, 4)
        assert semantics.collective_spec(opts, 4) is None

    def test_certify_call_caches_by_signature(self):
        semantics.clear_cache()
        opts, plan = _opts_plan(Operation.allgather, 8, 4)
        assert semantics.certify_call(opts, plan, 4) == []
        before = len(semantics._CERT_CACHE)
        assert semantics.certify_call(opts, plan, 4) == []
        assert len(semantics._CERT_CACHE) == before == 1

    def test_spec_shapes(self):
        opts, _ = _opts_plan(Operation.reduce_scatter, 4, 2)
        spec = semantics.collective_spec(opts, 2)
        assert spec is not None
        (length, op, terms), = spec[1]
        assert length == 4 and op == "sum"
        assert terms == {("a", 0, 0, 4): 1, ("a", 1, 0, 4): 1}
        opts_r, _ = _opts_plan(Operation.reduce, 4, 3, root=1)
        spec_r = semantics.collective_spec(opts_r, 3)
        assert spec_r[0] is None and spec_r[2] is None
        assert spec_r[1] is not None


# ---------------------------------------------------------------------------
# Corpus decomposition: the class neither predecessor catches
# ---------------------------------------------------------------------------


class TestSemanticCorpus:
    BAD = {
        "bad_semantic_double_count.json": "ACCL503",
        "bad_semantic_partial_gather.json": "ACCL502",
        "bad_semantic_stale_relay.json": "ACCL504",
        "bad_semantic_misrouted_chunk.json": "ACCL501",
    }

    @pytest.mark.parametrize("name", sorted(BAD))
    def test_linter_and_modelcheck_alone_pass_it(self, name):
        """The proof the pass catches a NEW class: the protocol
        matching game AND the exhaustive-interleaving checker both
        accept these DAGs' hops; only contribution sets object."""
        from accl_tpu.analysis.protocol import simulate

        fx = json.loads((CORPUS / name).read_text())
        dag = hopdag.from_json(fx["dag"])
        programs = hopdag.rank_programs(dag)
        assert simulate(programs, blocking_sends=False) == []
        assert SequenceLinter(dag.world).check_interleavings(programs) == []

    @pytest.mark.parametrize("name", sorted(BAD))
    def test_certifier_rejects_with_exact_code(self, name):
        fx = json.loads((CORPUS / name).read_text())
        dag = hopdag.from_json(fx["dag"])
        opts_d = dict(fx["collective"])
        scen = Operation[opts_d["op"]]
        func = ReduceFunction[opts_d.get("function", "SUM")]
        opts, _ = _opts_plan(scen, int(opts_d["count"]), dag.world,
                             root=int(opts_d.get("root", 0)), func=func)
        codes = {d.code for d in _certify(opts, dag, dag.world)}
        assert codes == {self.BAD[name]}

    def test_good_fixture_certifies(self):
        fx = json.loads((CORPUS / "good_semantic_allreduce.json").read_text())
        dag = hopdag.from_json(fx["dag"])
        opts, _ = _opts_plan(Operation.allreduce, 4, dag.world)
        assert _certify(opts, dag, dag.world) == []

    def test_stale_read_complements_hazard_pass(self):
        """Cross-check, not duplication: the BATCH-level stale tail
        stays ACCL101 (hazard pass), the IR-level order violation is
        ACCL504 (certifier) — no fixture triggers both."""
        raw = json.loads((CORPUS / "bad_raw_stale_tail.json").read_text())
        from tools.accl_lint import lint_fixture

        codes = {d.code for d in lint_fixture(raw)}
        assert "ACCL101" in codes
        assert not any(c.startswith("ACCL5") for c in codes)
        relay = json.loads(
            (CORPUS / "bad_semantic_stale_relay.json").read_text())
        dag = hopdag.from_json(relay["dag"])
        sem = {d.code for d in hopdag.validate_order(dag)}
        assert sem == {"ACCL504"}


# ---------------------------------------------------------------------------
# Default-tier wiring
# ---------------------------------------------------------------------------


class TestLinterWiring:
    def _steps_plans(self, world=4):
        steps = [CallOptions(scenario=Operation.allreduce, count=16,
                             root_src_dst=0,
                             function=int(ReduceFunction.SUM),
                             data_type=DataType.float32,
                             addr_0=0x10, addr_2=0x20)]
        plans = [_opts_plan(Operation.allreduce, 16, world)[1]]
        return steps, plans

    def test_default_tier_runs_semantics(self, monkeypatch):
        calls = []
        orig = semantics.check_batch_semantics

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(semantics, "check_batch_semantics", spy)
        steps, plans = self._steps_plans()
        assert SequenceLinter(4).lint(steps, plans) == []
        assert calls  # the pass ran WITHOUT deep=True

    def test_warning_predecessors_do_not_skip_semantics(self, monkeypatch):
        """A WAR/WAW-warned batch still dispatches under lint="error",
        so it must still get its answer certified; only error-severity
        predecessors (whose batch never ships) skip the pass."""
        calls = []
        orig = semantics.check_batch_semantics

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(semantics, "check_batch_semantics", spy)

        def opt(scen, count, a0, a2):
            return CallOptions(scenario=scen, count=count, function=0,
                               data_type=DataType.float32,
                               addr_0=a0, addr_2=a2)

        def plan(o):
            return select_algorithm(
                o.scenario, o.count, 4, 4, o.compression_flags,
                max_eager_size=DEFAULT_MAX_EAGER_SIZE,
                eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
                tuning=TuningParams.default(DEFAULT_MAX_RENDEZVOUS_SIZE))

        war = [opt(Operation.copy, 16, 1, 2), opt(Operation.copy, 16, 3, 1)]
        diags = SequenceLinter(4).lint(war, [plan(o) for o in war])
        assert [d.severity for d in diags] == ["warning"]
        assert calls, "warning-only batch skipped semantic certification"

        calls.clear()
        raw = [opt(Operation.reduce_scatter, 8, 1, 2),
               opt(Operation.bcast, 32, 2, 2)]
        diags = SequenceLinter(4).lint(raw, [plan(o) for o in raw])
        assert any(d.severity == "error" for d in diags)
        assert not calls, "error-poisoned batch still ran semantics"

    def test_semantic_diag_enforced_as_error(self, monkeypatch):
        from accl_tpu.analysis.diagnostics import make

        monkeypatch.setattr(
            semantics, "check_batch_semantics",
            lambda *a, **kw: [make("ACCL501", "planted", step=0)])
        steps, plans = self._steps_plans()
        diags = SequenceLinter(4).lint(steps, plans)
        assert [d.code for d in diags] == ["ACCL501"]
        assert diags[0].severity == "error"
        with pytest.raises(LintError):
            enforce(diags, "error")

    def test_semantic_codes_registered(self):
        for code in ("ACCL501", "ACCL502", "ACCL503", "ACCL504"):
            assert CODES[code][1] == "error"

    def test_inband_budget_defers_huge_segmented(self):
        opts, plan = _opts_plan(Operation.allreduce, 1_000_000, 8)
        assert not semantics._within_inband_budget(opts, plan, 8)
        small_o, small_p = _opts_plan(Operation.allreduce, 1024, 8)
        assert semantics._within_inband_budget(small_o, small_p, 8)

    def test_unsupported_is_skip_not_claim(self, monkeypatch):
        def boom(*a, **kw):
            raise semantics.UnsupportedSchedule("planted")

        monkeypatch.setattr(semantics, "certify_call", boom)
        steps, plans = self._steps_plans()
        assert semantics.check_batch_semantics(steps, plans, 4) == []
        with pytest.raises(semantics.UnsupportedSchedule):
            semantics.check_batch_semantics(steps, plans, 4, strict=True)


# ---------------------------------------------------------------------------
# Certifier-vs-execution fuzz: 30 seeds per collective family
# ---------------------------------------------------------------------------

_SEEDS = 30

# family -> (scenario, wire, count pool, world pool)
_FUZZ_FAMILIES = {
    "bcast": (Operation.bcast, DataType.none, (4, 12, 33), (2, 3, 4)),
    "scatter": (Operation.scatter, DataType.none, (3, 8, 16), (2, 3, 4)),
    "gather": (Operation.gather, DataType.none, (3, 8, 16), (2, 3, 4)),
    "reduce": (Operation.reduce, DataType.none, (4, 16, 40), (2, 3, 4)),
    "allgather": (Operation.allgather, DataType.none, (4, 8, 24), (2, 3, 4)),
    "reduce_scatter": (Operation.reduce_scatter, DataType.none,
                       (4, 8, 16), (2, 3, 4)),
    "allreduce": (Operation.allreduce, DataType.none, (8, 16, 48), (2, 3, 4)),
    "alltoall": (Operation.alltoall, DataType.none, (3, 6, 12), (2, 3, 4)),
    "sendrecv": (Operation.send, DataType.none, (4, 16, 64), (2, 3, 4)),
    # segmented eager ring (multiple segment slots through the same
    # body the pallas ring's segmentation uses on the lax path)
    "allreduce_segmented": (Operation.allreduce, DataType.none,
                            (600, 700, 2600), (2, 4)),
    "allreduce_quant": (Operation.allreduce, DataType.int8,
                        (16, 300, 520), (2, 4)),
    "reduce_scatter_quant": (Operation.reduce_scatter, DataType.int8,
                             (8, 64, 130), (2, 4)),
    "allgather_quant": (Operation.allgather, DataType.int8,
                        (8, 64, 130), (2, 4)),
}

_MUTATION_CODE = {
    "drop_combine": "ACCL502",
    "duplicate_combine": "ACCL503",
    "reorder_combine": "ACCL504",
    "swap_send_values": "ACCL501",
}


def _oracle(scen, operands, world, count, root, func):
    """Numpy reference of the DECLARED collective (what certified-clean
    must compute)."""
    red = (lambda a: np.sum(a, axis=0)) if func == ReduceFunction.SUM \
        else (lambda a: np.max(a, axis=0))
    xs = [o[0] for o in operands]
    if scen == Operation.bcast:
        return [xs[root]] * world
    if scen == Operation.scatter:
        return [xs[root][r * count:(r + 1) * count] for r in range(world)]
    if scen == Operation.gather:
        full = np.concatenate(xs)
        return [full if r == root else None for r in range(world)]
    if scen == Operation.allgather:
        return [np.concatenate(xs)] * world
    if scen == Operation.reduce:
        return [red(np.stack(xs)) if r == root else None
                for r in range(world)]
    if scen == Operation.allreduce:
        return [red(np.stack(xs))] * world
    if scen == Operation.reduce_scatter:
        full = red(np.stack(xs))
        return [full[r * count:(r + 1) * count] for r in range(world)]
    if scen == Operation.alltoall:
        return [np.concatenate([xs[c][r * count:(r + 1) * count]
                                for c in range(world)])
                for r in range(world)]
    if scen == Operation.send:
        src, dst = root & 0xFFFF, (root >> 16) & 0xFFFF
        return [xs[src] if r == dst else xs[r] for r in range(world)]
    raise AssertionError(scen)


def _payloads(rng, world, n_in, elems, quantized):
    """Integer-valued float32 payloads. Non-quantized: every element is
    UNIQUE across ranks/slots (sums stay exact in float32 and any
    misroute/swap is numerically visible). Quantized: small positive
    ints, so the documented per-block error bound stays tight."""
    if quantized:
        return [[np.asarray(rng.integers(1, 9, elems), np.float32)
                 for _ in range(n_in)] for _ in range(world)]
    return [[(np.arange(elems, dtype=np.float32) + 1.0
              + float((r * n_in + s) * elems))
             for s in range(n_in)] for r in range(world)]


def _applicable_mutations(dag, quantized):
    kinds = []
    has_combine = any(n.kind == "combine" for n in dag.nodes)
    has_sum = any(n.kind == "combine" and n.func == "sum"
                  for n in dag.nodes)
    if has_combine:
        kinds.append("drop_combine")
        if any(any(dag.nodes[p.node].kind == "recv" for p in n.refs())
               for n in dag.nodes if n.kind == "combine"):
            kinds.append("reorder_combine")
    if has_sum:
        kinds.append("duplicate_combine")
    if not quantized:
        # swapping a scales side-channel send is invisible to the
        # contribution domain (codes carry provenance); keep the swap
        # mutation on plain-wire DAGs where every send carries payload
        kinds.append("swap_send_values")
    return kinds


@pytest.mark.parametrize("family", sorted(_FUZZ_FAMILIES))
def test_certifier_vs_execution_fuzz(family):
    scen, wire, counts, worlds = _FUZZ_FAMILIES[family]
    quantized = wire == DataType.int8
    mismatches = []
    for seed in range(_SEEDS):
        rng = np.random.default_rng([zlib.crc32(family.encode()), seed])
        pyrng = random.Random(seed * 7919 + len(family))
        world = int(rng.choice(worlds))
        count = int(rng.choice(counts))
        rooted = scen in (Operation.bcast, Operation.scatter,
                          Operation.gather, Operation.reduce)
        root = int(rng.integers(world)) if rooted else 0
        func = ReduceFunction.SUM
        if scen in (Operation.reduce, Operation.allreduce) \
                and seed % 5 == 4:
            func = ReduceFunction.MAX
        if scen == Operation.send:
            src = int(rng.integers(world))
            dst = int(rng.integers(world))
            root = src | (dst << 16)
        opts, plan = _opts_plan(scen, count, world, root=root, func=func,
                                wire=wire)
        dag = semantics.lift_call(opts, plan, world)
        spec = semantics.collective_spec(opts, world)
        diags = semantics.certify(dag, spec, scen.name)
        assert diags == [], (family, seed, [str(d) for d in diags])

        operands = _payloads(rng, world, dag.n_in, dag.in_elems,
                             quantized)
        # quantized bound: one quantization pass per hop on the
        # partial's path, each |err| <= block_amax / 254
        max_abs = max(float(np.max(np.abs(b)))
                      for per_rank in operands for b in per_rank)
        bound = (world + 1) * world * max_abs / 254.0 + 1e-5

        def broken_vs_oracle(candidate, refs):
            for r in range(world):
                if refs[r] is None:
                    continue
                got = candidate[r][: len(refs[r])]
                if quantized:
                    if not np.allclose(got, refs[r], atol=bound):
                        return True
                elif not np.array_equal(got, refs[r]):
                    return True
            return False

        outs = hopdag.execute(dag, operands)
        refs = _oracle(scen, operands, world, count, root, func)
        if broken_vs_oracle(outs, refs):
            mismatches.append((family, seed, "clean-dag"))

        # mutation leg: certifier verdict and numeric truth must AGREE.
        # A mutation can land on a dead fold (one feeding only
        # don't-care outputs) — then the certifier's silence is correct
        # and the numbers must still match; a FLAGGED mutation carries
        # its class code, and (for the spec-driven classes under SUM)
        # provably wrong numbers.
        kinds = _applicable_mutations(dag, quantized)
        if not kinds:
            continue
        kind = kinds[seed % len(kinds)]
        mut = hopdag.mutate(dag, kind, pyrng)
        if mut is None:
            continue
        mcodes = {d.code for d in semantics.certify(mut, spec, scen.name)}
        mouts = hopdag.execute(mut, operands)
        numeric_broken = broken_vs_oracle(mouts, refs)
        if not mcodes:
            assert not numeric_broken, (
                family, seed, kind,
                "certified clean but numerically wrong")
            continue
        assert _MUTATION_CODE[kind] in mcodes, (family, seed, kind, mcodes)
        assert all(c.startswith("ACCL5") for c in mcodes)
        if (func == ReduceFunction.SUM
                and kind in ("drop_combine", "duplicate_combine",
                             "swap_send_values")):
            # these classes are flagged from the SPEC comparison, so a
            # flagged instance must reach a constrained output — and
            # with exact unique payloads that is numerically visible
            assert numeric_broken, (family, seed, kind,
                                    "flagged but numerically invisible")
    assert not mismatches, mismatches


# ---------------------------------------------------------------------------
# alltoallv: the drop region is PROVEN, not assumed
# ---------------------------------------------------------------------------


class TestAlltoallvSemantics:
    def test_dropped_tail_must_be_empty(self):
        """A schedule that leaks data into the capacity-dropped tail
        (here: the full dense exchange run against an alltoallv spec)
        must fail certification — the drop is part of the declared
        meaning, so 'extra' data is a wrong result, not a bonus."""
        world, count = 4, 10
        pc = (10, 3, 7, 1)
        opts_v, _ = _opts_plan(Operation.alltoall, count, world,
                               peer_counts=pc)
        # lift the DENSE exchange but certify against the v-spec
        _, _, dense_dag = _lift(Operation.alltoall, count, world)
        diags = semantics.certify(
            dense_dag, semantics.collective_spec(opts_v, world),
            "alltoall")
        codes = {d.code for d in diags}
        assert codes == {"ACCL501"}, diags

    def test_lifted_quantized_alltoallv_executes_faithfully(self):
        """The lifted DAG of the quantized capacity-bounded exchange is
        numerically faithful: hopdag.execute (the numpy reference
        datapath) reproduces the oracle within the per-block bound,
        with dropped tails exactly zero."""
        world, count = 4, 300
        pc = (128, 300, 9, 64)
        opts, _, dag = _lift(Operation.alltoall, count, world,
                             peer_counts=pc, wire=DataType.int8)
        rng = np.random.default_rng(19)
        xs = [rng.standard_normal(world * count).astype(np.float32)
              for _ in range(world)]
        outs = hopdag.execute(dag, [[x] for x in xs])
        bound = max(np.abs(x).max() for x in xs) / 254 * 1.01
        for r in range(world):
            for src in range(world):
                got = outs[r][src * count:(src + 1) * count]
                want = np.zeros(count, np.float32)
                want[:pc[r]] = xs[src][r * count:r * count + pc[r]]
                if src == r:
                    np.testing.assert_array_equal(got, want)
                else:
                    assert np.abs(got - want).max() <= bound
                    np.testing.assert_array_equal(
                        got[pc[r]:], np.zeros(count - pc[r], np.float32))
