#!/usr/bin/env python
"""accl_lint: replay recorded descriptor batches through the static
analyzer (accl_tpu/analysis/, docs/lint.md).

Three modes, combinable:

  --corpus [DIR]   replay every *.json fixture under DIR (default
                   tools/lint_corpus/): known-bad batches must be
                   rejected with their expected diagnostic codes,
                   known-good batches must come back clean
  --schedules      abstractly interpret every shipping schedule family
                   in sequencer/schedules.py (both protocol regimes,
                   several worlds/roots) and require zero diagnostics
  --deep           force the deep tier everywhere: fixtures run the
                   exhaustive-interleaving model checker (ACCL205-207)
                   even without "deep": true, and --schedules
                   model-checks every config's hop programs over all
                   match orders (budgeted; truncation fails the gate)
  --semantic       semantic certification (ACCL501-504): --schedules
                   additionally proves every config's contribution sets
                   equal its declared collective (strict: a schedule
                   the certifier cannot lift FAILS the gate), and the
                   corpus replay enforces "expect_semantic" exactly
  --sample N       deterministically subsample the --schedules sweep
                   to ~N configs (the CI slice for the deep tier)
  --interference   cross-program pair sweep (ACCL601-604): pairwise-
                   certify concurrent footprints over every shipped
                   schedule family (disjoint arenas must certify clean
                   via summaries ALONE — zero escalations), adversarial
                   overlap/slot/steal/unliftable rows must reject with
                   their exact codes, and the recorded MoE / decode /
                   train-step program pairs must certify clean or
                   reject with a stable ACCL6xx (never ACCL604)
  FILE...          lint individual fixture files

Exit status is 0 only when every expectation holds — the CI lint job
runs `accl_lint.py --corpus --schedules` (default tier),
`accl_lint.py --interference --corpus`, and
`accl_lint.py --deep --corpus --schedules --sample N` as gates.

Fixture schema (JSON):
  kind "sequence":       "steps" (descriptor dicts: op/count/dtype/
                         addr_0/addr_1/addr_2/root/function/tag/comm)
                         or "words" (the batched 15-word call stream),
                         plus optional "world", "deep",
                         "use_pallas_ring", "overlap", "buffer_widths"
  kind "rank_programs":  "programs": per-rank event lists
                         ({kind: send|recv|coll, peer, tag, count,
                         comm, op} — peer "any" is the any-source
                         wildcard), optional "blocking_sends", "deep"
                         (run the interleaving checker over the
                         programs), "budget_states"
  kind "slots":          "num_slots", "instances" [[step, seg, slot]],
                         "deps" [[from, to]]
  kind "hopdag":         "dag" (analysis.hopdag.to_json form) plus
                         "collective" ({op, count, root, function}) —
                         the protocol passes run over the DAG's hops
                         (these must satisfy "expect", [] for the
                         bad-semantic fixtures: the point is that the
                         linter/model checker ALONE pass them) and the
                         semantic certifier checks the DAG against the
                         declared collective ("expect_semantic")
  kind "concurrent":     "tenants": list of sub-fixtures (each of kind
                         "sequence" or "rank_programs", same schema as
                         above plus optional "title"/"world"/
                         "use_pallas_ring"/"overlap"/"persistent");
                         each tenant is lifted to its ProgramFootprint
                         and the set is pairwise-certified
                         (analysis/interference.py, ACCL601-604).
                         "expect" is enforced EXACTLY (set equality —
                         a cross-program fixture must reject with its
                         precise codes, no more, no less); optional
                         "expect_escalations" pins the product-
                         modelcheck escalation count (0 proves the
                         summary-only fast path)
  all kinds:             "expect": diagnostic codes that MUST surface
                         ([] = the batch must lint clean), "title";
                         "expect_semantic": ACCL5xx codes the semantic
                         certifier must emit, EXACTLY (set equality)
"""

import argparse
import json
import os
import pathlib
import sys

# the deep pass traces schedule bodies under jax's abstract evaluation;
# keep that off any real accelerator (and quiet) regardless of where
# the CLI runs — must happen before anything imports jax. The
# --interference model-pair sweep records real programs over an 8-way
# virtual mesh, so ask for the devices up front (a user-set XLA_FLAGS
# wins; the sweep adapts to whatever device count materializes).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from accl_tpu.constants import (  # noqa: E402
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_MAX_RENDEZVOUS_SIZE,
    DataType,
    Operation,
    ReduceFunction,
    TAG_ANY,
    TuningParams,
    dtype_nbytes,
)
from accl_tpu.descriptor import CallOptions, SequenceDescriptor  # noqa: E402
from accl_tpu.analysis import (  # noqa: E402
    SequenceLinter,
    check_slots,
    simulate,
)
from accl_tpu.analysis.modelcheck import Budget  # noqa: E402
from accl_tpu.analysis.protocol import (  # noqa: E402
    ANY_SRC,
    Event,
    check_hops,
    rank_programs_from_hops,
    trace_schedule_hops,
)
from accl_tpu.analysis.slots import SlotInstance, SlotTimeline  # noqa: E402
from accl_tpu.analysis import hopdag as hopdag_mod  # noqa: E402
from accl_tpu.analysis import semantics as semantics_mod  # noqa: E402
from accl_tpu.sequencer.plan import select_algorithm  # noqa: E402

DEFAULT_CORPUS = pathlib.Path(__file__).resolve().parent / "lint_corpus"


def _step_from_dict(d: dict) -> CallOptions:
    from accl_tpu.constants import CompressionFlags

    op = Operation[d["op"]]
    fn = d.get("function", 0)
    if isinstance(fn, str):
        fn = int(ReduceFunction[fn])
    dt = d.get("dtype", "float32")
    data_type = DataType[dt] if isinstance(dt, str) else DataType(dt)
    # "compress": wire dtype of an ETH_COMPRESSED call (e.g. "int8" for
    # the blockwise-quantized lanes) — mirrors the facade's
    # compress_dtype resolution in _prepare
    cp = d.get("compress")
    compress_dtype = (DataType[cp] if isinstance(cp, str)
                      else DataType(cp)) if cp is not None else DataType.none
    comp_flags = (CompressionFlags.ETH_COMPRESSED
                  if compress_dtype not in (DataType.none, data_type)
                  else CompressionFlags.NO_COMPRESSION)
    return CallOptions(
        scenario=op,
        count=int(d.get("count", 0)),
        comm_addr=int(d.get("comm", 0)),
        root_src_dst=int(d.get("root", d.get("root_src_dst", 0))),
        function=int(fn),
        tag=int(d.get("tag", TAG_ANY)),
        addr_0=int(d.get("addr_0", 0)),
        addr_1=int(d.get("addr_1", 0)),
        addr_2=int(d.get("addr_2", 0)),
        data_type=data_type,
        compress_dtype=compress_dtype,
        compression_flags=comp_flags,
        # "live_ranks": the declared surviving-contributor set of a
        # degraded live-subset allreduce (the certifier's spec demands
        # exactly these ranks' contributions — docs/resilience.md)
        live_ranks=tuple(int(r) for r in d.get("live_ranks", ())),
    )


def _default_plan(opts: CallOptions, world: int):
    return select_algorithm(
        opts.scenario, opts.count, dtype_nbytes(opts.data_type), world,
        opts.compression_flags, opts.stream_flags,
        max_eager_size=DEFAULT_MAX_EAGER_SIZE,
        eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
        tuning=TuningParams.default(DEFAULT_MAX_RENDEZVOUS_SIZE),
        compress_dtype=opts.compress_dtype,
        live_ranks=opts.live_ranks,
    )


def _fixture_budget(fx: dict) -> Budget:
    if "budget_states" in fx:
        return Budget(max_states=int(fx["budget_states"]))
    return Budget()


def _programs_from_fixture(fx: dict) -> list:
    def peer_of(e: dict) -> int:
        p = e.get("peer", -1)
        return ANY_SRC if p in ("any", "ANY") else int(p)

    return [
        [Event(e["kind"], peer_of(e),
               int(e.get("tag", TAG_ANY)), int(e.get("count", 0)),
               int(e.get("comm", 0)), e.get("op", ""))
         for e in prog]
        for prog in fx["programs"]
    ]


def _tenant_footprint(t: dict, i: int, default_world: int):
    """Lift one "concurrent" sub-fixture to its ProgramFootprint —
    through the SAME extractors the device attaches at compile time, so
    the corpus replays exactly what certify_concurrent sees."""
    from accl_tpu.analysis.interference import (
        footprint_from_rank_programs, footprint_from_steps)

    kind = t.get("kind", "sequence")
    world = int(t.get("world", default_world))
    label = t.get("title", f"tenant{i}")
    if kind == "sequence":
        steps = [_step_from_dict(d) for d in t["steps"]]
        plans = tuple(_default_plan(o, world) for o in steps)
        return footprint_from_steps(
            steps, world,
            persistent=frozenset(int(a) for a in t.get("persistent", ())),
            use_pallas_ring=bool(t.get("use_pallas_ring", False)),
            pallas_ring_overlap=bool(t.get("overlap", True)),
            plans=plans, label=label)
    if kind == "rank_programs":
        return footprint_from_rank_programs(
            _programs_from_fixture(t), world, label=label)
    raise ValueError(f"unknown tenant kind {kind!r}")


def lint_fixture(fx: dict, deep: bool = False) -> list:
    """Run one fixture through the analyzer; returns Diagnostics.
    `deep=True` (the CLI's --deep) forces the exhaustive-interleaving
    tier even for fixtures that don't opt in with `"deep": true`."""
    kind = fx.get("kind", "sequence")
    world = int(fx.get("world", 4))
    if kind == "sequence":
        if "words" in fx:
            steps = list(
                SequenceDescriptor.from_words(list(fx["words"])).steps)
        else:
            steps = [_step_from_dict(d) for d in fx["steps"]]
        widths = None
        if "buffer_widths" in fx:
            widths = {int(k, 0) if isinstance(k, str) else int(k): int(v)
                      for k, v in fx["buffer_widths"].items()}
        linter = SequenceLinter(
            world,
            use_pallas_ring=bool(fx.get("use_pallas_ring", False)),
            pallas_ring_overlap=bool(fx.get("overlap", True)),
            deep=deep or bool(fx.get("deep", False)),
            budget=_fixture_budget(fx),
        )
        plans = [_default_plan(o, world) for o in steps]
        return linter.lint(steps, plans, buffer_widths=widths)
    if kind == "rank_programs":
        programs = _programs_from_fixture(fx)
        diags = simulate(programs,
                         blocking_sends=bool(fx.get("blocking_sends",
                                                    True)))
        if (deep or fx.get("deep", False)) and not diags:
            # deep tier: certify the chains over EVERY legal match
            # order, not just the canonical schedule simulate ran
            diags = SequenceLinter(
                world,
                budget=_fixture_budget(fx)).check_interleavings(programs)
        return diags
    if kind == "slots":
        timeline = SlotTimeline(
            int(fx["num_slots"]),
            [SlotInstance(*map(int, i)) for i in fx["instances"]],
            {(int(a), int(b)) for a, b in fx.get("deps", [])},
        )
        return check_slots(timeline)
    if kind == "hopdag":
        # raw hop-DAG fixtures: the protocol/model-check passes see the
        # DAG's hops as per-rank programs (for the bad-semantic corpus
        # these must come back CLEAN — the class only the semantic
        # certifier catches), then the certifier checks the DAG against
        # its declared collective
        dag = hopdag_mod.from_json(fx["dag"])
        programs = hopdag_mod.rank_programs(dag)
        diags = simulate(programs, blocking_sends=False)
        if (deep or fx.get("deep", False)) and not diags:
            diags = SequenceLinter(
                dag.world,
                budget=_fixture_budget(fx)).check_interleavings(programs)
        coll = fx.get("collective")
        if coll is not None:
            opts = _step_from_dict(coll)
            spec = semantics_mod.collective_spec(opts, dag.world)
            diags = list(diags) + semantics_mod.certify(
                dag, spec, opts.scenario.name)
        return diags
    if kind == "concurrent":
        from accl_tpu.analysis.interference import InterferenceCertifier

        # every tenant must certify ALONE first — cross-program
        # fixtures demonstrate defects only the pairwise tier sees, so
        # a tenant failing its own single-program passes is a broken
        # fixture, not an interference finding
        solo = []
        for i, t in enumerate(fx["tenants"]):
            solo += lint_fixture({"world": fx.get("world", 4), **t},
                                 deep=deep)
        if solo:
            return solo
        certifier = InterferenceCertifier(budget=_fixture_budget(fx))
        fps = [_tenant_footprint(t, i, int(fx.get("world", 4)))
               for i, t in enumerate(fx["tenants"])]
        diags = certifier.certify(fps)
        want_esc = fx.get("expect_escalations")
        if want_esc is not None and certifier.escalations != int(want_esc):
            raise AssertionError(
                f"expected {want_esc} product-modelcheck escalations, "
                f"certifier took {certifier.escalations} (the summary-"
                "only fast path is part of this fixture's contract)")
        return diags
    raise ValueError(f"unknown fixture kind {kind!r}")


def run_fixture_file(path: pathlib.Path,
                     deep: bool = False) -> tuple[bool, str]:
    fx = json.loads(path.read_text())
    diags = lint_fixture(fx, deep=deep)
    got = [d.code for d in diags]
    expect = fx.get("expect", [])
    expect_sem = fx.get("expect_semantic")
    if expect_sem is not None:
        # semantic expectations are EXACT (set equality on the ACCL5xx
        # codes): a bad-semantic fixture must be rejected with its
        # specific code, and the non-semantic passes must satisfy
        # "expect" — [] meaning the linter/model checker alone pass it
        got5 = sorted({c for c in got if c.startswith("ACCL5")})
        rest = [c for c in got if not c.startswith("ACCL5")]
        sem_ok = got5 == sorted(set(expect_sem))
        if expect:
            rest_ok = not [c for c in expect if c not in rest]
        else:
            rest_ok = not rest
        ok = sem_ok and rest_ok
        verdict = (f"semantic {got5 or ['clean']}"
                   + (f" + {sorted(set(rest))}" if rest else "")
                   if ok else
                   f"EXPECTED semantic {sorted(set(expect_sem))} got "
                   f"{got5} (other codes: {sorted(set(rest))})")
    elif fx.get("kind") == "concurrent":
        # cross-program fixtures are EXACT: the pairwise certifier must
        # emit precisely the expected code set — a fixture built to
        # reject ACCL602 surfacing a stray ACCL601 means the footprint
        # regions drifted, and that must fail the replay
        ok = sorted({c for c in got}) == sorted(set(expect))
        verdict = ((f"rejected with exactly {sorted(set(got))}"
                    if expect else "clean") if ok else
                   f"EXPECTED exactly {sorted(set(expect))} got "
                   f"{sorted(set(got))}")
    elif expect:
        missing = [c for c in expect if c not in got]
        ok = not missing
        verdict = (f"rejected with {sorted(set(got))}" if ok else
                   f"MISSED {missing} (got {sorted(set(got))})")
    else:
        ok = not diags
        verdict = "clean" if ok else f"UNEXPECTED {sorted(set(got))}"
    detail = "".join(f"\n      {d}" for d in diags) if not ok else ""
    return ok, f"{path.name:40s} {verdict}{detail}"


def run_corpus(corpus_dir: pathlib.Path, deep: bool = False) -> bool:
    files = sorted(corpus_dir.glob("*.json"))
    if not files:
        print(f"no fixtures under {corpus_dir}", file=sys.stderr)
        return False
    ok_all = True
    n_bad = n_good = 0
    for path in files:
        try:
            ok, line = run_fixture_file(path, deep=deep)
        except Exception as e:  # a crashing fixture is a failing fixture
            ok, line = False, f"{path.name:40s} ERROR {type(e).__name__}: {e}"
        ok_all &= ok
        fx_d = json.loads(path.read_text())
        is_bad = bool(fx_d.get("expect")) or bool(
            fx_d.get("expect_semantic"))
        n_bad += is_bad
        n_good += not is_bad
        print(("  ok  " if ok else " FAIL ") + line)
    print(f"corpus: {len(files)} fixtures "
          f"({n_bad} known-bad, {n_good} known-good)")
    return ok_all


def run_schedules(deep: bool = False, sample: int = 0,
                  semantic: bool = False) -> bool:
    """Interpret every shipping schedule family per rank and require it
    clean — the conformance half of the acceptance gate. `deep=True`
    additionally model-checks each config's hop programs over every
    legal match order (ACCL205-207; a truncated exploration FAILS the
    gate — the sweep must complete within budget, never silently
    partial). `semantic=True` additionally certifies every config's
    contribution sets against its declared collective (ACCL501-504,
    strict and unbudgeted: a config the certifier cannot lift fails the
    gate — inability must never read as certified). `sample=N` keeps a
    deterministic ~N-config slice (CI's deep tier)."""
    import time as _time

    t0 = _time.monotonic()
    ok = True
    rooted = (Operation.bcast, Operation.scatter, Operation.gather,
              Operation.reduce)
    tunings = {
        "default": TuningParams.default(DEFAULT_MAX_RENDEZVOUS_SIZE),
        # force the binary-tree / capped-fan-in branches
        "trees": TuningParams(
            gather_flat_tree_max_fanin=2,
            gather_flat_tree_max_count=64,
            bcast_flat_tree_max_ranks=2,
            reduce_flat_tree_max_ranks=2,
            reduce_flat_tree_max_count=64,
            allreduce_composition_max_count=1 << 30,
        ),
    }
    scens = (Operation.bcast, Operation.scatter, Operation.gather,
             Operation.reduce, Operation.allgather, Operation.allreduce,
             Operation.reduce_scatter, Operation.alltoall,
             Operation.barrier, Operation.send)
    configs = []
    for world in (2, 4, 8):
        for scen in scens:
            roots = range(world) if scen in rooted else (0,)
            for root in roots:
                for count in (16, 100_000):
                    for tname, tuning in tunings.items():
                        if scen == Operation.barrier and count != 16:
                            continue
                        configs.append((world, scen, root, count,
                                        tname, tuning, DataType.none))
        # the quantized-wire cells: the families with int8 ring variants
        # (codes relayed, accumulation only at combine points) plus the
        # pairwise exchange (packed codes+scales, one message per hop —
        # both the block-aligned encode-once form at 8192 and the
        # per-hop form at 16) — both the protocol interpretation and
        # the semantic certifier must hold through the encoded datapath
        for scen in (Operation.allreduce, Operation.reduce_scatter,
                     Operation.allgather, Operation.alltoall):
            for count in (16, 8192):
                configs.append((world, scen, 0, count, "default",
                                tunings["default"], DataType.int8))
        # alltoallv cells: the capacity-bounded exchange
        # (schedules.alltoallv_schedule) with uniform-trim and
        # heterogeneous per-peer capacity vectors, exact and quantized
        # wire — the certifier must prove the routed prefix AND the
        # dropped (zero) tail of every slot (the MoE overflow-drop
        # semantics as descriptors)
        for count, pattern in ((300, "uniform"), (1024, "hetero")):
            if pattern == "uniform":
                pc = (max(count // 2, 1),) * world
            else:
                pc = tuple(max(count // (i + 1), 1) for i in range(world))
            for wire in (DataType.none, DataType.int8):
                configs.append((world, Operation.alltoall, 0, count,
                                "default", tunings["default"], wire,
                                ("a2av", pc)))
        # synthesized-schedule cells (sequencer/synthesis.py): payloads
        # inside the committed library entries' winning windows,
        # selected via maxed synth crossover registers — the lowered
        # hop-DAG programs must interpret, model-check and certify
        # exactly like the hand-written zoo (strict under --semantic).
        # Cells whose (op, world, size) no entry serves fall through to
        # the hand-written plan and stay valid sweep rows.
        synth_tuning = TuningParams(
            synth_allreduce_max_count=1 << 22,
            synth_allgather_max_count=1 << 22,
            synth_reduce_scatter_max_count=1 << 22,
        )
        for scen, count, wire in (
                (Operation.allreduce, 1024, DataType.none),
                (Operation.allreduce, 1024, DataType.int8),
                (Operation.reduce_scatter, 1024, DataType.none),
                (Operation.allgather, 65536, DataType.none)):
            configs.append((world, scen, 0, count, "synth",
                            synth_tuning, wire))
        # stripe-overlapped allreduce cells (sequencer/plan.py's
        # OVERLAP_MIN_COUNT window + timing.best_overlap_stripes):
        # the register-selected striped segmentation must interpret,
        # model-check and certify exactly like the unstriped ring.
        # Config tuples grow a trailing ("olap", stripes) extra; the
        # depth is pinned per cell the way the hier sweep pins its
        # stripe depths.
        olap_tuning = TuningParams(overlap_min_count=1)
        for count, stripes in ((64, 2), (4096, 4)):
            configs.append((world, Operation.allreduce, 0, count,
                            "olap", olap_tuning, DataType.none,
                            ("olap", stripes)))
        # degraded live-subset allreduce cells (accl_tpu/resilience/,
        # docs/resilience.md): the source-masked ring selected through
        # live_ranks — the certifier must prove the answer sums EXACTLY
        # the declared survivor set (all-but-one and a half-world set;
        # deduplicated — at world 2 the two coincide)
        for count in (16, 8192):
            for lr in sorted({
                    tuple(r for r in range(world) if r != world - 1),
                    tuple(range(max(world // 2, 1)))}):
                configs.append((world, Operation.allreduce, 0, count,
                                "live", tunings["default"], DataType.none,
                                ("live", lr)))
    # hierarchical two-tier cells (sequencer/hierarchical.py): the
    # striped composition selected through the register window for
    # every (inner, outer) factoring, several stripe depths, and the
    # per-tier wire combinations — each must interpret, model-check and
    # certify exactly like the flat zoo. Config tuples grow a trailing
    # (topology, tier_wires, stripes) extra; None for the flat sweep.
    # MIN register: any positive payload >= 1 byte selects the
    # composition, so every sweep size below exercises it
    hier_tuning = TuningParams(hier_allreduce_min_count=1)
    for world, factorings in ((4, ((2, 2),)), (8, ((2, 4), (4, 2)))):
        for L, P in factorings:
            for count, stripes in ((64, 1), (8192, 2)):
                for tw in ((DataType.none, DataType.none),
                           (DataType.none, DataType.int8),
                           (DataType.float16, DataType.none)):
                    configs.append((world, Operation.allreduce, 0, count,
                                    "hier", hier_tuning, DataType.none,
                                    ("hier", (L, P), tw, stripes)))
    # tiered synthesized cells (sequencer/synthesis.py factored
    # families): the committed tiered hop-DAG selected through the
    # REAL in-window arbitration (the hier register + a declared
    # topology + the predicted-time tie-break against the striped
    # composition) — its lowered body must interpret, model-check and
    # certify exactly like the composition it displaces. Config tuples
    # grow a trailing ("synth_tier", topology) extra; the plain hier
    # rows above pin the composition itself via tiered_synth_ok=False.
    for world, topo, count in ((8, (2, 4), 8192), (8, (2, 4), 65536)):
        configs.append((world, Operation.allreduce, 0, count,
                        "synth_tier", hier_tuning, DataType.none,
                        ("synth_tier", topo)))
    if sample and sample < len(configs):
        # deterministic slice: every ceil(total/sample)-th config, so
        # the CI subset is stable across runs and spans all families
        stride = -(-len(configs) // sample)
        configs = configs[::stride]
    n = 0
    budget = Budget()
    for cfg in configs:
        world, scen, root, count, tname, tuning, wire = cfg[:7]
        extra = cfg[7] if len(cfg) > 7 else None
        hier = extra[1:] if extra is not None and extra[0] == "hier" \
            else None
        a2av = extra[1] if extra is not None and extra[0] == "a2av" \
            else None
        olap = extra[1] if extra is not None and extra[0] == "olap" \
            else None
        synth_tier = (extra[1] if extra is not None
                      and extra[0] == "synth_tier" else None)
        live = extra[1] if extra is not None and extra[0] == "live" \
            else None
        from accl_tpu.constants import CompressionFlags

        rsd = root if scen != Operation.send \
            else 0 | ((world - 1) << 16)
        comp_flags = (CompressionFlags.ETH_COMPRESSED
                      if wire != DataType.none
                      else CompressionFlags.NO_COMPRESSION)
        opts = CallOptions(
            scenario=scen, count=count, root_src_dst=rsd,
            function=int(ReduceFunction.SUM),
            data_type=DataType.float32,
            compress_dtype=wire, compression_flags=comp_flags,
            peer_counts=a2av or (), live_ranks=live or ())
        hier_kw: dict = {}
        if hier is not None or synth_tier is not None:
            from accl_tpu.sequencer.timing import LinkParams, TierLinks
        if hier is not None:
            topo, tier_wires, stripes = hier

            # a representative fast-inner/slow-outer calibration: only
            # the stripe count depends on it, and the sweep pins the
            # depth explicitly below. tiered_synth_ok=False pins the
            # COMPOSITION through the twin-measurement escape — the
            # in-window arbitration would otherwise resolve these
            # cells to the committed tiered entries, which have their
            # own synth_tier rows below
            hier_kw = dict(topology=topo, tier_wires=tier_wires,
                           tiered_synth_ok=False,
                           tier_links=TierLinks(
                               inner=LinkParams(2e-6, 2e9),
                               outer=LinkParams(30e-6, 0.25e9)))
        if synth_tier is not None:
            # a WAN-class outer link (a shaped slow tier):
            # per-message latency on the slow tier dominates, which is
            # exactly where the log-step tiered entries displace the
            # striped composition in the arbitration
            hier_kw = dict(topology=synth_tier,
                           tier_links=TierLinks(
                               inner=LinkParams(2e-6, 2e9),
                               outer=LinkParams(300e-6, 0.25e9)))
        olap_kw: dict = {}
        if olap is not None:
            from accl_tpu.sequencer.timing import (ComputeFit,
                                                   LinkParams)

            # a representative shaped-link + compute calibration: the
            # register must engage through the REAL selection path;
            # the sweep pins the stripe depth explicitly below
            olap_kw = dict(overlap_link=LinkParams(600e-6, 0.3e9),
                           overlap_compute=ComputeFit(2e-3, 0.3e9))
        plan = select_algorithm(
            scen, count, 4, world, comp_flags,
            max_eager_size=DEFAULT_MAX_EAGER_SIZE,
            eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
            tuning=tuning, compress_dtype=wire,
            peer_counts=a2av or (), live_ranks=live or (),
            **hier_kw, **olap_kw)
        if live is not None:
            assert plan.algorithm.name == "EAGER_RING_RS_AG" \
                and plan.live_ranks == live, \
                f"live-subset config did not select the masked ring: {plan}"
        if olap is not None:
            import dataclasses as _dc

            assert plan.algorithm.name == "EAGER_RING_RS_AG" \
                and plan.stripes > 1, \
                f"overlap config did not stripe the ring: {plan}"
            seg = -(-count // olap)
            seg += (-seg) % world
            plan = _dc.replace(plan, stripes=olap, seg_count=seg,
                               num_segments=max(-(-count // seg), 1))
        if a2av is not None:
            assert plan.algorithm.name == "FLAT_ALLTOALLV", \
                f"alltoallv config did not select the v-schedule: {plan}"
        if hier is not None:
            import dataclasses as _dc

            assert plan.algorithm.name == "HIER_RS_AR_AG", \
                f"hier config did not select the composition: {plan}"
            plan = _dc.replace(plan, stripes=hier[2])
        if synth_tier is not None:
            assert plan.algorithm.name == "SYNTHESIZED" \
                and plan.synth_key, \
                f"synth_tier config did not arbitrate to a tiered " \
                f"entry: {plan}"
        # trace each schedule body ONCE (the dominant cost): the hops
        # feed the per-config interpretation AND, under --deep, the
        # exhaustive-interleaving checker
        hops = trace_schedule_hops(opts, plan, world)
        diags = check_hops(hops, world)
        if not diags:
            programs = rank_programs_from_hops(hops, world)
            diags = simulate(programs, blocking_sends=False)
            if deep and not diags:
                diags = SequenceLinter(
                    world, budget=budget).check_interleavings(programs)
                # ANY truncation fails the deep gate: a partial sweep
                # must never read as a clean one
                if any(d.code == "ACCL207" for d in diags):
                    ok = False
            if semantic and not diags:
                # strict: UnsupportedSchedule is a gate failure, never
                # a silent pass
                try:
                    diags = semantics_mod.check_batch_semantics(
                        [opts], [plan], world, strict=True)
                except semantics_mod.UnsupportedSchedule as e:
                    ok = False
                    print(f" FAIL {scen.name} world={world} "
                          f"count={count}: certifier cannot lift: {e}")
        n += 1
        if diags:
            ok = False
            print(f" FAIL {scen.name} world={world} "
                  f"root={root} count={count} "
                  f"tuning={tname} wire={wire.name} "
                  f"{plan.algorithm.name}: "
                  f"{[str(d) for d in diags]}")
    dt = _time.monotonic() - t0
    print(f"schedules: {n} (scenario, world, root, size, tuning, wire) "
          f"configurations interpreted"
          + (" + model-checked" if deep else "")
          + (" + semantically certified" if semantic else "") + " "
          + ("clean" if ok else "WITH DEFECTS")
          + f" in {dt:.1f}s")
    return ok


def run_interference() -> bool:
    """The cross-program pair sweep (the --interference gate):

    1. footprints over every shipped schedule family with DISJOINT
       buffer arenas pairwise-certify clean via summaries alone — the
       escalation counter must stay 0 (the O(N^2)-cheap fast path the
       multi-tenant admission control relies on);
    2. adversarial rows reject with their EXACT codes: a shared-region
       pair ACCL601, a pallas-ring slot pair ACCL603, a wildcard-steal
       rank-program pair ACCL602, an unliftable footprint ACCL604;
    3. the recorded MoE / decode / train-step programs (REAL recorders
       over a virtual mesh, no XLA compile) pairwise-certify clean or
       reject with a stable ACCL6xx — never ACCL604: every shipped
       program family must be liftable."""
    import time as _time

    from accl_tpu.analysis.interference import (
        InterferenceCertifier, footprint_from_rank_programs,
        footprint_from_steps)
    from accl_tpu.analysis.protocol import recv, send

    t0 = _time.monotonic()
    ok = True

    # -- 1. disjoint-arena family sweep: summaries alone, zero
    #       escalations ------------------------------------------------
    families = [
        ("allreduce", [dict(op="allreduce", count=4096)]),
        ("quantized", [dict(op="allreduce", count=8192,
                            compress="int8")]),
        ("rs_ag", [dict(op="reduce_scatter", count=1024),
                   dict(op="allgather", count=1024)]),
        ("alltoall", [dict(op="alltoall", count=512)]),
        ("alltoallv", [dict(op="alltoall", count=300)]),
        ("bcast_gather", [dict(op="bcast", count=256),
                          dict(op="gather", count=256)]),
        ("hier", [dict(op="allreduce", count=8192)]),
        ("decode_like", [dict(op="copy", count=64),
                         dict(op="allreduce", count=64),
                         dict(op="combine", count=64)]),
        ("train_like", [dict(op="copy", count=2048),
                        dict(op="allreduce", count=2048),
                        dict(op="combine", count=2048)]),
    ]

    def arena_steps(rows: list, base: int, world: int):
        steps = []
        nxt = [base]

        def alloc() -> int:
            nxt[0] += 0x100000
            return nxt[0]

        for row in rows:
            d = dict(row)
            d["addr_0"] = alloc()
            if d["op"] == "combine":
                d["addr_1"] = alloc()
            d["addr_2"] = alloc()
            if d["op"] == "alltoall" and d["count"] == 300:
                # alltoallv footprint rides the same descriptor shape;
                # peer_counts don't change the prefix access model
                pass
            steps.append(_step_from_dict(d))
        return steps

    n_pairs = 0
    for world in (2, 4, 8):
        certifier = InterferenceCertifier()
        fps = []
        for i, (name, rows) in enumerate(families):
            steps = arena_steps(rows, 0x10000000 * (i + 1), world)
            plans = tuple(_default_plan(o, world) for o in steps)
            fps.append(footprint_from_steps(
                steps, world, plans=plans, label=f"{name}@{world}"))
        bad = [fp for fp in fps if fp.unliftable is not None]
        if bad:
            ok = False
            for fp in bad:
                print(f" FAIL {fp.label}: unliftable footprint "
                      f"({fp.unliftable})")
        diags = certifier.certify(fps)
        n_pairs += certifier.pairs_checked
        if diags:
            ok = False
            for d in diags:
                print(f" FAIL disjoint sweep world={world}: {d}")
        if certifier.escalations:
            ok = False
            print(f" FAIL disjoint sweep world={world}: "
                  f"{certifier.escalations} escalations (summary-only "
                  "fast path violated)")

    # -- 2. adversarial rows: exact codes ------------------------------
    def expect_exact(title: str, fps, codes: set) -> None:
        nonlocal ok
        got = {d.code for d in InterferenceCertifier().certify(fps)}
        if got != codes:
            ok = False
            print(f" FAIL {title}: expected exactly {sorted(codes)}, "
                  f"got {sorted(got)}")

    world = 4
    a = arena_steps([dict(op="allreduce", count=256)], 0x10000000, world)
    b = arena_steps([dict(op="allreduce", count=256)], 0x20000000, world)
    shared = arena_steps([dict(op="allreduce", count=256)], 0x10000000,
                         world)
    mk = lambda s, label, **kw: footprint_from_steps(  # noqa: E731
        s, world, plans=tuple(_default_plan(o, world) for o in s),
        label=label, **kw)
    expect_exact("overlap pair", [mk(a, "A"), mk(shared, "B")],
                 {"ACCL601"})
    expect_exact("slot pair",
                 [mk(a, "A", use_pallas_ring=True),
                  mk(b, "B", use_pallas_ring=True)], {"ACCL603"})
    steal_a = footprint_from_rank_programs(
        [[recv(1, TAG_ANY, 4)], [send(0, 3, 4)]], 2, label="A")
    steal_b = footprint_from_rank_programs(
        [[recv(1, 9, 4)], [send(0, 9, 4)]], 2, label="B")
    expect_exact("steal pair", [steal_a, steal_b], {"ACCL602"})
    broken = footprint_from_steps([object()], world, label="broken")
    expect_exact("unliftable pair", [mk(a, "A"), broken], {"ACCL604"})

    # -- 3. recorded model-program pairs (real recorders, no compile) --
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from accl_tpu import ACCL
    from accl_tpu.models import moe as moe_mod
    from accl_tpu.models import transformer as trf

    # world 4: the tp decode step needs world | n_heads, and 4 is the
    # widest the tiny sweep config supports (the footprint layer itself
    # is world-agnostic — worlds 2-8 are covered by the sweep above)
    n_dev = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("ccl",))
    accl = ACCL(mesh)

    def rec_footprint(rec, label: str):
        return footprint_from_steps(
            rec.calls, accl.world, persistent=rec._persistent,
            label=label)

    model_fps = []
    for tag in ("moe", "moe2"):
        disp, mid, out = (accl.create_buffer(1024, np.float32)
                          for _ in range(3))
        seq = accl.sequence()
        seq.alltoall(disp, mid, 128,
                     res_stream=moe_mod.MOE_EXPERT_STREAM)
        seq.alltoall(mid, out, 128)
        model_fps.append(rec_footprint(seq, tag))
    cfg = trf.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64)
    params = trf.init_params(cfg, jax.random.key(0))
    rec, _ = trf.record_decode_step(accl, cfg, params, batch=2,
                                    max_len=8)
    model_fps.append(rec_footprint(rec, "decode"))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab,
                          (accl.world, 1, 8)).astype(np.int32)
    rec, _ = trf.record_train_step(accl, cfg, tokens,
                                   np.roll(tokens, -1, axis=2))
    model_fps.append(rec_footprint(rec, "train"))

    certifier = InterferenceCertifier()
    for i in range(len(model_fps)):
        for j in range(i + 1, len(model_fps)):
            fa, fb = model_fps[i], model_fps[j]
            diags = certifier.check_pair(fa, fb)
            n_pairs += 1
            codes = sorted({d.code for d in diags})
            if "ACCL604" in codes:
                ok = False
                print(f" FAIL {fa.label} x {fb.label}: ACCL604 — a "
                      "shipped program family must be liftable")
            print(f"  {fa.label:8s} x {fb.label:8s} "
                  + ("clean" if not codes else str(codes)))

    # -- 4. the multi-tenant corpus rows: every kind=="concurrent"
    #       fixture replays through run_fixture_file, so the sweep and
    #       the corpus can never disagree about a tenant mix -----------
    n_corpus = 0
    for path in sorted(DEFAULT_CORPUS.glob("*.json")):
        try:
            if json.loads(path.read_text()).get("kind") != "concurrent":
                continue
            fok, line = run_fixture_file(path)
        except Exception as e:  # a crashing fixture is a failing one
            fok, line = False, (f"{path.name:40s} ERROR "
                                f"{type(e).__name__}: {e}")
        n_corpus += 1
        ok &= fok
        print(("  ok  " if fok else " FAIL ") + line)

    dt = _time.monotonic() - t0
    print(f"interference: {n_pairs} pairs certified across the family "
          f"sweep, adversarial rows and recorded model programs, "
          f"{n_corpus} concurrent corpus fixtures replayed "
          + ("clean" if ok else "WITH DEFECTS") + f" in {dt:.1f}s")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--corpus", nargs="?", const=str(DEFAULT_CORPUS),
                    default=None, metavar="DIR",
                    help="replay the fixture corpus (default "
                         "tools/lint_corpus/)")
    ap.add_argument("--schedules", action="store_true",
                    help="interpret every shipping schedule and require "
                         "it clean")
    ap.add_argument("--deep", action="store_true",
                    help="force the exhaustive-interleaving tier on "
                         "fixtures and --schedules (ACCL205-207)")
    ap.add_argument("--semantic", action="store_true",
                    help="semantically certify every --schedules config "
                         "against its declared collective "
                         "(ACCL501-504, strict)")
    ap.add_argument("--sample", type=int, default=0, metavar="N",
                    help="deterministically subsample --schedules to "
                         "~N configurations")
    ap.add_argument("--interference", action="store_true",
                    help="cross-program pair sweep: pairwise-certify "
                         "concurrent footprints over the shipped "
                         "schedule families, adversarial rows and the "
                         "recorded model programs (ACCL601-604)")
    ap.add_argument("files", nargs="*", help="individual fixture files")
    args = ap.parse_args(argv)
    if not (args.corpus or args.schedules or args.interference
            or args.files):
        ap.error("nothing to do: pass --corpus, --schedules, "
                 "--interference, or files")
    ok = True
    if args.corpus:
        ok &= run_corpus(pathlib.Path(args.corpus), deep=args.deep)
    if args.schedules:
        ok &= run_schedules(deep=args.deep, sample=args.sample,
                            semantic=args.semantic)
    if args.interference:
        ok &= run_interference()
    for f in args.files:
        fok, line = run_fixture_file(pathlib.Path(f), deep=args.deep)
        ok &= fok
        print(("  ok  " if fok else " FAIL ") + line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
