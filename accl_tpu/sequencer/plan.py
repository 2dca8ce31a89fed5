"""Algorithm selection: which schedule runs a given call.

Ports the firmware's per-collective selection logic
(reference: ccl_offload_control.c — bcast .c:796-988, scatter .c:992-1123,
gather .c:1128-1294, allgather .c:1297-1503, reduce .c:1507-1744,
reduce_scatter .c:1748-1852, allreduce .c:1855-2075, alltoall .c:2123-2218,
barrier .c:2078-2120) as a pure function so the Python lowering, the native
C++ runtime, and the tests all agree on exactly one set of rules.
"""

from __future__ import annotations

import dataclasses
import enum
import functools

from ..constants import (
    CompressionFlags,
    DataType,
    Operation,
    StreamFlags,
    TuningParams,
)


class Protocol(enum.IntEnum):
    EAGER = 0  # segmented through preallocated RX ring slots
    RENDEZVOUS = 1  # bulk zero-copy transfer after an address handshake


class Algorithm(enum.IntEnum):
    """Schedule families (SURVEY.md §2.7 table)."""

    NONE = 0  # local-only ops: copy/combine, world==1 corner cases
    EAGER_SENDRECV = 1  # segmented pipeline through rx slots (.c:611-648)
    RNDZV_SENDRECV = 2  # zero-copy one-sided write (.c:587-610)
    EAGER_FLAT = 3  # root fan-out, segmented (eager bcast/scatter)
    EAGER_RING = 4  # daisy-chain (eager gather/allgather/reduce/rs)
    EAGER_RING_RS_AG = 5  # ring reduce-scatter + ring allgather (eager allreduce)
    RNDZV_FLAT_TREE = 6  # out-of-order flat tree (small world/message)
    RNDZV_BIN_TREE = 7  # distance-doubling binary tree (bcast/reduce)
    RNDZV_RING = 8  # rendezvous ring (allgather)
    RNDZV_REDUCE_BCAST = 9  # allreduce = reduce + bcast (.c:1878-1887)
    RNDZV_REDUCE_SCATTER = 10  # reduce_scatter = reduce + scatter (.c:1768-1781)
    FLAT_ALLTOALL = 11  # pairwise exchange (.c:2140-2211)
    BARRIER_GATHER_SCATTER = 12  # zero-count notification tree (.c:2078-2120)
    # A search-produced hop-DAG from the committed synthesized library
    # (sequencer/synthesis.py): Plan.synth_key names the entry; the
    # compiler lowers the certified DAG instead of a Python body.
    SYNTHESIZED = 13
    # Striped two-tier allreduce (sequencer/hierarchical.py, HiCCL's
    # multiply/factor composition): RS(inner) -> AR(outer shard) ->
    # AG(inner) over Plan.stripes software-pipelined stripes, with
    # per-tier wire dtypes. Reachable only through the
    # HIER_ALLREDUCE_MIN_COUNT register window on a device that
    # declares a two-tier topology.
    HIER_RS_AR_AG = 14
    # Capacity-bounded pairwise exchange (schedules.alltoallv_schedule):
    # the dense alltoall's rotation with per-peer valid counts
    # (Plan.peer_counts) — every hop moves max(peer_counts) elements and
    # the overflow tail is dropped to zeros at the source, the MoE
    # dispatch's dropped-token semantics expressed in the schedule.
    FLAT_ALLTOALLV = 15


@dataclasses.dataclass(frozen=True)
class Plan:
    """The resolved execution plan for one call.

    seg_count is in elements: the eager segment size (rx-buffer capacity in
    elements, world-aligned where the algorithm strides by world size,
    .c:1898-1901). tree_fanin/tree_distance parameterize the flat/binary
    trees. All fields are static so a Plan is part of the XLA cache key.
    """

    protocol: Protocol
    algorithm: Algorithm
    seg_count: int  # elements per eager segment (== count when unsegmented)
    num_segments: int
    tree_fanin: int = 0  # flat-tree fan-in cap (gather tuning)
    use_bin_tree: bool = False
    # Composed algorithms (rendezvous allreduce/reduce_scatter) re-run the
    # per-stage selection with the same tuning registers, the way the
    # firmware re-enters reduce()/broadcast()/scatter() (.c:1878-1887,
    # .c:1768-1781). The stage plans are resolved here so lowering and the
    # native runtime never re-derive selection rules.
    stages: tuple["Plan", ...] = ()
    # The dtype payloads travel in on cross-rank hops (DataType.none =
    # uncompressed). Compression is a PLAN dimension, not just a
    # descriptor flag: the timing model charges wire bytes from this
    # field (cast lanes at the cast width, int8 at 1 B + amortized
    # per-block scale), so predict()/autotune() crossovers move when a
    # wire is active and select_wire() can arbitrate it by predicted
    # time (HiCCL's compression-as-measured-decision posture).
    wire_dtype: DataType = DataType.none
    # SYNTHESIZED plans: the library entry key (sequencer/synthesis.py)
    # the compiler lowers. Part of the frozen Plan, so it rides the XLA
    # cache key like every other selection decision.
    synth_key: str = ""
    # HIER_RS_AR_AG plans: the two-tier shape and the per-tier wire
    # decision. inner/outer_world pin the topology the schedule was
    # selected for; stripes is the cost-model-chosen pipeline depth —
    # shared with stripe-overlapped EAGER_RING_RS_AG plans (the
    # OVERLAP_MIN_COUNT register), where it counts the independent
    # stripe chains a fused program overlaps against adjacent compute
    # (timing.best_overlap_stripes' argmin; 1 = the serial form);
    # inner/outer_wire_dtype are the per-tier compression lanes
    # (select_tier_wires arbitrates each link separately — int8 on DCN
    # while fp32 stays on ICI). All frozen, so every one of these
    # decisions rides the Plan/XLA cache key.
    inner_world: int = 0
    outer_world: int = 0
    stripes: int = 1
    inner_wire_dtype: DataType = DataType.none
    outer_wire_dtype: DataType = DataType.none
    # FLAT_ALLTOALLV plans: the static per-peer valid counts the
    # schedule truncates each slot to (the descriptor's peer_counts).
    # Frozen, so two alltoallv calls with different capacity vectors
    # can never share a compiled program or a timing estimate.
    peer_counts: tuple[int, ...] = ()
    # Degraded live-subset allreduce (the descriptor's live_ranks): the
    # declared surviving-contributor set. Non-empty only on
    # EAGER_RING_RS_AG plans selected for `allreduce(mode=
    # "live_subset")` — the schedule masks every non-member's operand
    # to exact zeros at the source before the ordinary ring runs, so
    # the answer provably sums exactly the survivors. Frozen and
    # cache-keyed like peer_counts: two survivor sets can never share
    # a compiled program.
    live_ranks: tuple[int, ...] = ()


def is_rendezvous(
    bytes_count: int,
    compression: CompressionFlags,
    stream: StreamFlags,
    max_eager_size: int,
) -> bool:
    """The protocol switch every collective applies first
    (e.g. .c:808, .c:1524, .c:1879): large, uncompressed, non-streamed
    messages go rendezvous; everything else is eager."""
    return (
        bytes_count > max_eager_size
        and compression == CompressionFlags.NO_COMPRESSION
        and stream == StreamFlags.NO_STREAM
    )


def eager_seg_count(
    count: int,
    dtype_nbytes: int,
    eager_rx_buf_size: int,
    stream: StreamFlags,
    world_align: int = 1,
) -> int:
    """Eager segment size in elements (.c:925-936, .c:1891-1901): the rx
    buffer capacity, optionally rounded down to a multiple of world size for
    algorithms that stride chunks by rank; streamed operands are never
    segmented because streams can't be re-read."""
    if stream & StreamFlags.OP0_STREAM:
        return count
    seg = max(eager_rx_buf_size // dtype_nbytes, 1)
    if world_align > 1:
        seg -= seg % world_align
        seg = max(seg, world_align)
    return min(seg, count) if count > 0 else seg


def _segments(count: int, seg: int) -> int:
    return max((count + seg - 1) // seg, 1)


def select_algorithm(
    scenario: Operation,
    count: int,
    dtype_nbytes: int,
    world_size: int,
    compression: CompressionFlags = CompressionFlags.NO_COMPRESSION,
    stream: StreamFlags = StreamFlags.NO_STREAM,
    *,
    max_eager_size: int,
    eager_rx_buf_size: int,
    tuning: TuningParams,
    compress_dtype: DataType = DataType.none,
    topology: tuple[int, int] | None = None,
    tier_wires: tuple[DataType, DataType] = (DataType.none, DataType.none),
    tier_links=None,
    peer_counts: tuple[int, ...] = (),
    overlap_link=None,
    overlap_compute=None,
    tiered_synth_ok: bool = True,
    live_ranks: tuple[int, ...] = (),
) -> Plan:
    """Resolve scenario + message + communicator into a Plan.

    Selection rules are the firmware's, collective by collective; each
    branch cites the reference decision point. `compress_dtype` names
    the wire dtype of an ETH_COMPRESSED call (the descriptor's
    compress_dtype): it rides the Plan so the timing model charges wire
    widths, not payload widths.

    `topology=(inner_world, outer_world)` declares the caller's
    two-tier shape (a DCN device's (ici, dcn) extents, or a virtual
    factoring of a flat mesh). With it, allreduce payloads inside the
    HIER_ALLREDUCE_MIN_COUNT register window run the striped two-tier
    composition (Algorithm.HIER_RS_AR_AG); the register defaults 0
    (off) and is set by ACCL.autotune from the calibrated per-tier
    crossover, so absent a tune the behavior is bit-for-bit the flat
    selection. `tier_wires=(inner, outer)` are the per-tier wire dtypes
    (select_tier_wires arbitrates them); `tier_links` is a
    timing.TierLinks used to pick the stripe count (default: the
    shipped per-tier calibration, telemetry.feedback.default_tier_links
    — no calibration means 1 stripe, never a made-up pipeline depth).

    `overlap_link` (timing.LinkParams) and `overlap_compute`
    (timing.ComputeFit) parameterize the OVERLAP_MIN_COUNT register's
    stripe choice for exact eager allreduces (the consumer-spliced
    gradient-sync seam): inside the window the call runs as
    Plan.stripes independent stripe chains whose depth is
    timing.best_overlap_stripes' argmin under the calibrated shaped
    link and the measured compute term. Defaults load the shipped
    calibration (the tier-outer link and compute_fit) from
    telemetry.feedback; with no calibration the plan stays the serial
    form — never a made-up pipeline depth. Register 0 (the default)
    keeps selection bit-for-bit unchanged.
    """
    bytes_count = count * dtype_nbytes
    rndzv = is_rendezvous(bytes_count, compression, stream, max_eager_size)
    proto = Protocol.RENDEZVOUS if rndzv else Protocol.EAGER
    wire = (compress_dtype
            if compression & CompressionFlags.ETH_COMPRESSED
            and compress_dtype != DataType.none
            else DataType.none)

    def eager_plan(algorithm: Algorithm, world_align: int = 1) -> Plan:
        seg = eager_seg_count(
            count, dtype_nbytes, eager_rx_buf_size, stream, world_align
        )
        return Plan(Protocol.EAGER, algorithm, seg, _segments(count, seg),
                    wire_dtype=wire)

    def rndzv_plan(algorithm: Algorithm, **kw) -> Plan:
        return Plan(Protocol.RENDEZVOUS, algorithm, count, 1,
                    wire_dtype=wire, **kw)

    # Local-only operations and single-rank corner cases (.c:1520-1522,
    # .c:1765-1767, .c:1875-1877: world==1 reductions degrade to copy).
    if scenario in (Operation.copy, Operation.combine, Operation.config, Operation.nop):
        return Plan(proto, Algorithm.NONE, count, 1)
    if world_size == 1 and scenario != Operation.barrier:
        return Plan(proto, Algorithm.NONE, count, 1)

    # Degraded live-subset allreduce (accl_tpu/resilience/): a declared
    # surviving-contributor set pins the plan to the source-masked eager
    # ring — the one schedule family the certifier proves against the
    # survivor spec — BEFORE any performance window (hier / synthesized
    # / overlap / the rendezvous composition): degraded mode is the
    # certified-correctness path, and those windows were all calibrated
    # for the full-contributor collective. A full survivor set IS the
    # ordinary allreduce and falls through (the facade normalizes it to
    # () so the compiled program is shared, like the all-full alltoallv
    # vector).
    if scenario == Operation.allreduce and live_ranks:
        from ..descriptor import normalize_live_ranks

        lr = normalize_live_ranks(live_ranks, world_size)
        if lr != tuple(range(world_size)):
            if compression != CompressionFlags.NO_COMPRESSION:
                raise ValueError(
                    "live-subset allreduce is exact-wire only: the "
                    "certified degraded mode does not compose with "
                    "compression lanes")
            base = eager_plan(Algorithm.EAGER_RING_RS_AG,
                              world_align=world_size)
            return dataclasses.replace(base, live_ranks=lr)

    # Striped two-tier allreduce (sequencer/hierarchical.py): reachable
    # ONLY inside the HIER_ALLREDUCE_MIN_COUNT register window on a
    # caller that declared a two-tier topology — the same
    # measured-selection posture as the synth registers (register 0
    # keeps selection bit-for-bit unchanged). Checked BEFORE the flat
    # synthesized library: the flat synth windows were calibrated on a
    # uniform link, and on a declared two-tier world their flat
    # hop-DAGs would drag full payloads across the slow tier — a
    # caller who declared the topology and tuned the hier register has
    # asserted the per-tier calibration governs here. INSIDE the
    # window, though, the hand-written composition no longer pre-empts
    # unconditionally: a committed TIERED library entry for this exact
    # factoring (synthesis.select_entry(tiers=...), scored per-tier
    # against the striped composition itself) arbitrates BY PREDICTED
    # TIME under the same per-tier calibration — the composition is
    # one point in the factored search space, and the schedule that
    # predicts faster wins the cell. No tiered entry (or no per-tier
    # calibration) keeps the old behavior bit-for-bit;
    # tiered_synth_ok=False pins the composition (the lint
    # twin-measurement escape, like select_wire's quantized_ok). Only
    # exact uncompressed unstreamed calls are eligible; per-tier
    # compression rides tier_wires/the plan's tier dtypes instead of
    # the descriptor's global compression flag.
    if scenario == Operation.allreduce and topology is not None:
        inner_w, outer_w = topology
        if (tuning.hier_allreduce_min_count > 0
                and inner_w > 1 and outer_w > 1
                and inner_w * outer_w == world_size
                and bytes_count >= tuning.hier_allreduce_min_count
                and stream == StreamFlags.NO_STREAM
                and compression == CompressionFlags.NO_COMPRESSION):
            from .timing import best_stripes, predict_tiered

            iw, ow = tier_wires
            links = tier_links
            if links is None:
                from ..telemetry.feedback import default_tier_links

                links = default_tier_links()
            stripes = 1
            if links is not None:
                stripes = best_stripes(
                    links, count, dtype_nbytes, inner_w, outer_w,
                    inner_wire=iw, outer_wire=ow)
            hier_plan = Plan(Protocol.EAGER, Algorithm.HIER_RS_AR_AG,
                             count, 1, inner_world=inner_w,
                             outer_world=outer_w, stripes=stripes,
                             inner_wire_dtype=iw, outer_wire_dtype=ow)
            if tiered_synth_ok and links is not None:
                from . import synthesis
                from .timing import predict_synth_tiered

                key = synthesis.select_entry(
                    scenario, world_size, bytes_count,
                    tiers=(inner_w, outer_w))
                if key is not None:
                    synth_plan = Plan(Protocol.EAGER,
                                      Algorithm.SYNTHESIZED, count, 1,
                                      synth_key=key,
                                      inner_world=inner_w,
                                      outer_world=outer_w)
                    t_synth = predict_synth_tiered(
                        links, synth_plan, count, dtype_nbytes)
                    t_hier = predict_tiered(links, hier_plan, count,
                                            dtype_nbytes)
                    if t_synth < t_hier:
                        return synth_plan
            return hier_plan

    # Latency-window synthesized schedules (synthesis.SIZE_GRID_LAT):
    # exact uncompressed unstreamed allreduce payloads inside the
    # SYNTH_LATENCY_MAX_COUNT window run the committed latency-grid
    # hop-DAG — the minimum-step members scored on the 1-64 KiB decode
    # grid where the alpha term dominates. Checked BEFORE the std
    # synth window: the lat register is derived contiguous-from-bottom
    # on the fine grid, so inside it the lat entry is the calibrated
    # winner even where the coarser std window also claims the cell.
    # Register 0 (the default) skips this branch entirely — selection
    # is bit-for-bit the established behavior.
    if (scenario == Operation.allreduce
            and tuning.synth_latency_max_count
            and 0 < bytes_count <= tuning.synth_latency_max_count
            and stream == StreamFlags.NO_STREAM
            and compression == CompressionFlags.NO_COMPRESSION):
        from . import synthesis

        key = synthesis.select_entry(scenario, world_size, bytes_count,
                                     grid="lat")
        if key is not None:
            return Plan(Protocol.EAGER, Algorithm.SYNTHESIZED,
                        count, 1, wire_dtype=wire, synth_key=key)

    # Synthesized schedules (sequencer/synthesis.py): payloads inside a
    # synth crossover register run the search-produced hop-DAG for this
    # (op, world) when the committed library carries a certified entry
    # whose predicted winning window covers the payload. Registers
    # default 0 (off) and are set by ACCL.autotune from the calibrated
    # timing model — selection from measured crossovers, the same
    # posture as every other register. Only exact uncompressed
    # unstreamed calls are eligible: the library's int8-wire entries
    # (exchange family re-encodes the running partial every hop) are
    # NOT rank-consistent — different ranks fold differently-quantized
    # copies and finish apart by up to the per-block bound — so they
    # must never silently replace the hand-written quantized ring,
    # whose rank-consistent round-trip is a documented contract
    # (docs/api.md). int8 entries stay first-class for explicit use
    # (synthesis.select_entry(wire="int8"), tools/accl_synth).
    synth_reg = {
        Operation.allreduce: tuning.synth_allreduce_max_count,
        Operation.allgather: tuning.synth_allgather_max_count,
        Operation.reduce_scatter: tuning.synth_reduce_scatter_max_count,
    }.get(scenario, 0)
    if (synth_reg and 0 < bytes_count <= synth_reg
            and stream == StreamFlags.NO_STREAM
            and compression == CompressionFlags.NO_COMPRESSION):
        from . import synthesis

        key = synthesis.select_entry(scenario, world_size, bytes_count)
        if key is not None:
            return Plan(Protocol.EAGER, Algorithm.SYNTHESIZED,
                        count, 1, wire_dtype=wire, synth_key=key)

    if scenario in (Operation.send, Operation.recv):
        # send .c:573-649 / recv .c:653-710: rendezvous one-sided write vs
        # eager segmented pipeline.
        if rndzv:
            return rndzv_plan(Algorithm.RNDZV_SENDRECV)
        return eager_plan(Algorithm.EAGER_SENDRECV)

    if scenario == Operation.bcast:
        if rndzv:
            # .c:814-867: binary tree once the world outgrows the flat-tree
            # tuning register; else out-of-order flat fan-out (.c:868-919).
            if world_size > tuning.bcast_flat_tree_max_ranks:
                return rndzv_plan(Algorithm.RNDZV_BIN_TREE, use_bin_tree=True)
            return rndzv_plan(Algorithm.RNDZV_FLAT_TREE, tree_fanin=world_size - 1)
        return eager_plan(Algorithm.EAGER_FLAT)  # .c:921-988 root fan-out

    if scenario == Operation.scatter:
        if rndzv:
            return rndzv_plan(Algorithm.RNDZV_FLAT_TREE, tree_fanin=world_size - 1)
        return eager_plan(Algorithm.EAGER_FLAT)  # .c:992-1123 round-robin

    if scenario == Operation.gather:
        if rndzv:
            # .c:1142-1204: flat tree, fan-in capped above the tuning count
            # threshold (gather fan-in 2 above 32 KB, accl.cpp:1200-1201).
            if bytes_count > tuning.gather_flat_tree_max_count:
                fanin = max(tuning.gather_flat_tree_max_fanin, 1)
            else:
                fanin = world_size - 1
            return rndzv_plan(Algorithm.RNDZV_FLAT_TREE, tree_fanin=fanin)
        return eager_plan(Algorithm.EAGER_RING)  # .c:1206-1293 daisy chain

    if scenario == Operation.allgather:
        if rndzv:
            return rndzv_plan(Algorithm.RNDZV_RING)  # .c:1314-1401
        return eager_plan(Algorithm.EAGER_RING)  # .c:1402-1499

    if scenario == Operation.reduce:
        if rndzv:
            # .c:1531: flat tree when world or message is small, else
            # distance-doubling binary tree (.c:1603-1727).
            if (
                world_size <= tuning.reduce_flat_tree_max_ranks
                or bytes_count <= tuning.reduce_flat_tree_max_count
            ):
                return rndzv_plan(Algorithm.RNDZV_FLAT_TREE, tree_fanin=world_size - 1)
            return rndzv_plan(Algorithm.RNDZV_BIN_TREE, use_bin_tree=True)
        return eager_plan(Algorithm.EAGER_RING)  # .c:1730-1743 ring relay

    if scenario == Operation.reduce_scatter:
        if rndzv:
            # .c:1768-1781: reduce(count*world, root=0) then scatter(count).
            sub = functools.partial(
                select_algorithm,
                dtype_nbytes=dtype_nbytes,
                world_size=world_size,
                compression=compression,
                stream=stream,
                max_eager_size=max_eager_size,
                eager_rx_buf_size=eager_rx_buf_size,
                tuning=tuning,
                compress_dtype=compress_dtype,
            )
            return rndzv_plan(
                Algorithm.RNDZV_REDUCE_SCATTER,
                stages=(
                    sub(Operation.reduce, count * world_size),
                    sub(Operation.scatter, count),
                ),
            )
        return eager_plan(Algorithm.EAGER_RING, world_align=world_size)  # .c:1782-1850

    if scenario == Operation.allreduce:
        # Segmented ring reduce-scatter + ring allgather with world-aligned
        # segments as the DEFAULT at every size (.c:1888-2071): the ring
        # moves the bandwidth-optimal 2*bytes*(P-1)/P per link with chunks
        # pipelined down both phases, while the reference's rendezvous
        # reduce+bcast composition (.c:1878-1887) serializes full payloads
        # through tree combine nodes — measured 4x slower than bcast alone
        # at 1 MB / 8 ranks on the native emulator (accl_log/emu_bench.csv).
        # The composition stays reachable through a tuning register (the
        # reference's runtime-tunable-selection posture, accl.cpp:1198-1208)
        # so the timing model can arbitrate per (size, world) on links
        # where trees win; register 0 keeps the measured ring default.
        if rndzv and bytes_count <= tuning.allreduce_composition_max_count:
            sub = functools.partial(
                select_algorithm,
                dtype_nbytes=dtype_nbytes,
                world_size=world_size,
                compression=compression,
                stream=stream,
                max_eager_size=max_eager_size,
                eager_rx_buf_size=eager_rx_buf_size,
                tuning=tuning,
                compress_dtype=compress_dtype,
            )
            return rndzv_plan(
                Algorithm.RNDZV_REDUCE_BCAST,
                stages=(
                    sub(Operation.reduce, count),
                    sub(Operation.bcast, count),
                ),
            )
        plan = eager_plan(Algorithm.EAGER_RING_RS_AG,
                          world_align=world_size)
        # Stripe-overlapped gradient allreduce (the OVERLAP_MIN_COUNT
        # register): an exact eager allreduce inside the window runs as
        # Plan.stripes independent stripe chains, so a fused program
        # can overlap stripe i's wire with stripe i+1's compute (the
        # consumer-spliced gradient-sync seam). XLA-schedule-tier in
        # effect: only autotuned XLA/DCN devices ever move the
        # register off 0, and the native runtime's selection never
        # reads it — the same scoping as the hier register. The stripe
        # count is timing.best_overlap_stripes' argmin under the
        # calibrated shaped link and the measured compute term — no
        # calibration means the serial plan, never a made-up depth.
        if (tuning.overlap_min_count > 0
                and compression == CompressionFlags.NO_COMPRESSION
                and bytes_count >= tuning.overlap_min_count):
            link, fit = overlap_link, overlap_compute
            if link is None or fit is None:
                from ..telemetry import feedback as _fb

                if fit is None:
                    fit = _fb.default_compute_fit()
                if link is None:
                    tl = _fb.default_tier_links()
                    link = tl.outer if tl is not None \
                        else _fb.default_link()
            if link is not None and fit is not None:
                from .timing import best_overlap_stripes

                stripes = best_overlap_stripes(
                    link, count, dtype_nbytes, world_size,
                    compute_s=fit.seconds(bytes_count),
                    rx_buf_bytes=eager_rx_buf_size)
                if stripes > 1:
                    seg = -(-count // stripes)
                    seg += (-seg) % world_size
                    # world-aligning the stripe segment can merge the
                    # tail stripes (count=100, world=8, S=8 -> seg=16
                    # -> 7 chains): the frozen stripe count must be
                    # the chain count the lowering actually runs, or
                    # the cost model and the serialized twin's barrier
                    # accounting drift off the real program
                    n_seg = _segments(count, seg)
                    if n_seg > 1:
                        return dataclasses.replace(
                            plan, seg_count=seg, num_segments=n_seg,
                            stripes=n_seg)
        return plan

    if scenario == Operation.alltoall:
        # alltoallv: a per-peer capacity vector turns the dense rotation
        # into the capacity-bounded exchange. An all-full vector IS the
        # dense alltoall and normalizes to it (one compiled program, no
        # vmax machinery), so `alltoallv(counts=(count,)*world)` is
        # bit-for-bit `alltoall`.
        if peer_counts and any(c != count for c in peer_counts):
            if len(peer_counts) != world_size:
                raise ValueError(
                    f"alltoallv needs {world_size} peer counts, got "
                    f"{len(peer_counts)}")
            if any(c <= 0 or c > count for c in peer_counts):
                raise ValueError(
                    f"alltoallv peer counts {peer_counts} outside "
                    f"(0, {count}]")
            pc = tuple(int(c) for c in peer_counts)
            if rndzv:
                return rndzv_plan(Algorithm.FLAT_ALLTOALLV, peer_counts=pc)
            return dataclasses.replace(
                eager_plan(Algorithm.FLAT_ALLTOALLV), peer_counts=pc)
        return rndzv_plan(Algorithm.FLAT_ALLTOALL) if rndzv else eager_plan(
            Algorithm.FLAT_ALLTOALL
        )  # .c:2140-2211

    if scenario == Operation.barrier:
        # .c:2078-2120: count==0 notification gather-to-0 then scatter.
        return Plan(Protocol.RENDEZVOUS, Algorithm.BARRIER_GATHER_SCATTER, 0, 1)

    raise ValueError(f"no algorithm for scenario {scenario!r}")


def select_wire(
    scenario: Operation,
    count: int,
    data_type: DataType,
    world_size: int,
    link,
    *,
    max_eager_size: int,
    eager_rx_buf_size: int,
    rx_buf_bytes: int,
    tuning: TuningParams,
    arith_table: dict | None = None,
    min_gain: float = 0.05,
    aggregate: bool = False,
    quantized_ok: bool = True,
) -> DataType:
    """Pick the wire dtype for a call by PREDICTED TIME — compression as
    a plan dimension, not a flag (HiCCL's point that compression and
    algorithm choice must be measured performance decisions).

    Candidates are the arithmetic-configuration rows whose uncompressed
    dtype matches the payload (fp32 -> {fp16, bf16, int8-blockwise} on
    the default table) plus the uncompressed baseline. Each candidate is
    re-planned (compressed calls route eager) and costed through the
    calibrated timing model with WIRE-byte accounting; a compressed wire
    is chosen only when it beats the baseline by more than `min_gain`
    relative — on latency-dominated small payloads, where wire bytes
    barely move the prediction, the call keeps its exact fp32 wire
    rather than paying quantization error for nothing.

    `link` is a timing.LinkParams. Returns the chosen compress_dtype
    (DataType.none = stay uncompressed); callers hand it to the facade's
    `compress_dtype=` seam unchanged. `quantized_ok=False` drops the
    blockwise lanes from the candidate set — pass
    `getattr(device, "supports_quantized_wire", False)` when selecting
    for a backend that may lack the quantized ring kernels, so the
    runner-up cast lane wins instead of the facade rejecting the pick.
    """
    from ..arithconfig import DEFAULT_ARITH_CONFIG
    from ..constants import dtype_nbytes
    from ..ops.compression import is_quantized
    from .timing import predict

    table = arith_table or DEFAULT_ARITH_CONFIG
    elem_bytes = dtype_nbytes(data_type)
    kw: dict = dict(max_eager_size=max_eager_size,
                    eager_rx_buf_size=eager_rx_buf_size, tuning=tuning)

    def cost(wire: DataType) -> float:
        comp = (CompressionFlags.ETH_COMPRESSED if wire != DataType.none
                else CompressionFlags.NO_COMPRESSION)
        plan = select_algorithm(scenario, count, elem_bytes, world_size,
                                comp, compress_dtype=wire, **kw)
        return predict(link, scenario, plan, count, elem_bytes, world_size,
                       rx_buf_bytes=rx_buf_bytes, aggregate=aggregate)

    t_none = cost(DataType.none)
    best, t_best = DataType.none, t_none
    for (unc, cmp_), row in table.items():
        if unc != data_type or cmp_ == unc:
            continue
        if not quantized_ok and is_quantized(row):
            continue
        t = cost(cmp_)
        if t < t_best and (t_none - t) > min_gain * t_none:
            best, t_best = cmp_, t
    return best


def select_tier_wires(
    count: int,
    data_type: DataType,
    topology: tuple[int, int],
    tier_links,
    *,
    arith_table: dict | None = None,
    min_gain: float = 0.05,
    quantized_ok: bool = True,
) -> tuple[DataType, DataType]:
    """Per-tier wire arbitration for the striped hierarchical allreduce:
    `select_wire`'s predicted-time decision, made ONCE PER LINK.

    The hierarchical cost decomposes by tier (timing.hier_phase_costs
    charges phases 1/3 to the inner link and phase 2 to the outer), so
    each tier's wire is chosen independently: the candidate set is the
    arithmetic-configuration rows for the payload dtype, each costed
    through predict_tiered with that tier's wire active and the other
    uncompressed, and a compressed wire wins only when it beats the
    tier's uncompressed baseline by `min_gain` of the TOTAL call time.
    The typical calibrated outcome is exactly HiCCL's: int8 codes on
    the slow DCN tier (where wire bytes dominate), fp32 kept exact on
    ICI (where the latency term dominates and quantization error buys
    nothing). Returns (inner_wire, outer_wire) — DataType.none = stay
    uncompressed — which callers hand to select_algorithm's
    `tier_wires=`."""
    from ..arithconfig import DEFAULT_ARITH_CONFIG
    from ..constants import dtype_nbytes
    from ..ops.compression import is_quantized
    from .timing import best_stripes, predict_tiered

    table = arith_table or DEFAULT_ARITH_CONFIG
    elem_bytes = dtype_nbytes(data_type)
    inner_w, outer_w = topology

    def cost(iw: DataType, ow: DataType) -> float:
        stripes = best_stripes(tier_links, count, elem_bytes, inner_w,
                               outer_w, inner_wire=iw, outer_wire=ow)
        plan = Plan(Protocol.EAGER, Algorithm.HIER_RS_AR_AG, count, 1,
                    inner_world=inner_w, outer_world=outer_w,
                    stripes=stripes, inner_wire_dtype=iw,
                    outer_wire_dtype=ow)
        return predict_tiered(tier_links, plan, count, elem_bytes)

    picks = []
    for tier in ("inner", "outer"):
        def with_tier(w: DataType) -> float:
            return cost(w, DataType.none) if tier == "inner" \
                else cost(DataType.none, w)

        t_none = with_tier(DataType.none)
        best, t_best = DataType.none, t_none
        for (unc, cmp_), row in table.items():
            if unc != data_type or cmp_ == unc:
                continue
            if not quantized_ok and is_quantized(row):
                continue
            t = with_tier(cmp_)
            if t < t_best and (t_none - t) > min_gain * t_none:
                best, t_best = cmp_, t
        picks.append(best)
    return picks[0], picks[1]
