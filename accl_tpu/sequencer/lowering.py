"""Lowering: call descriptor + plan -> compiled device program.

This is the TPU analog of the firmware's dispatch (ccl_offload_control.c:2374-2456)
combined with the move-instruction emission (.c:413-527): instead of
streaming move words into a hardware DMP at runtime, the whole collective
schedule is traced once per static descriptor signature, compiled by XLA
into a single device program over the mesh, and cached — subsequent calls
with the same signature are a dispatch-only cost, preserving ACCL's
"host only issues the call" property.

Operands enter as stacked per-rank buffers: a global array of shape
(world, n) sharded on the collective axis, so device r's shard is rank r's
local buffer (ACCL buffer semantics, not slices of one logical tensor).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax.sharding import Mesh, PartitionSpec

from ..arithconfig import DEFAULT_ARITH_CONFIG, ArithConfig
from ..constants import (
    CompressionFlags,
    DataType,
    Operation,
    ReduceFunction,
    to_numpy_dtype,
)
from ..descriptor import CallOptions
from ..ops.compression import wire_dtype
from . import schedules
from .plan import Algorithm, Plan


class ScheduleCompiler:
    """Compiles and caches collective programs for one mesh axis.

    The cache key is the descriptor's static signature + the plan, mirroring
    how the reference caches nothing but re-executes firmware per call — on
    TPU, tracing per call would forfeit all performance, so compilation is
    amortized exactly like XLA intends.
    """

    def __init__(
        self,
        mesh: Mesh,
        axis_name: str = "ccl",
        arith_table: dict | None = None,
        use_pallas_ring: bool | None = None,
        pallas_ring_overlap: bool | None = None,
        overlap_serialize: bool | None = None,
    ):
        self.mesh = mesh
        self.axis_name = axis_name
        self.arith_table = arith_table or DEFAULT_ARITH_CONFIG
        from ..ops.ring_allreduce import mesh_on_tpu

        self.on_tpu = mesh_on_tpu(mesh)
        if use_pallas_ring is None:
            # Auto: the fused ICI kernel on a TPU mesh, lax schedules on
            # the CPU emulation mesh (where interpret-mode kernels are
            # slower).
            use_pallas_ring = self.on_tpu
        self.use_pallas_ring = use_pallas_ring
        if pallas_ring_overlap is None:
            # segment-slot double-buffering for the large-payload pallas
            # ring (see _body's allreduce branch); the env knob keeps the
            # serialized baseline reachable for A/B measurement
            import os

            pallas_ring_overlap = (
                os.environ.get("ACCL_PALLAS_RING_SERIALIZE") != "1")
        self.pallas_ring_overlap = pallas_ring_overlap
        if overlap_serialize is None:
            # the serial dispatch->compute twin of a stripe-overlapped
            # allreduce plan (Plan.stripes > 1): order-only barriers
            # serialize the stripe chains, bitwise-identical to the
            # overlapped form — the A/B baseline bench --overlap-gate
            # measures against (same knob pattern as the pallas ring's
            # serialized baseline above)
            import os

            overlap_serialize = (
                os.environ.get("ACCL_OVERLAP_SERIALIZE") == "1")
        self.overlap_serialize = overlap_serialize
        self._cache: dict = {}
        # lookups of the cache above that found / built their program
        self.lower_hits = 0
        self.lower_misses = 0

    # Per-device payload ceiling for the VMEM-resident fused ring kernel;
    # larger transfers fall back to the segmented lax schedule.
    PALLAS_RING_MAX_BYTES = 4 * 1024 * 1024

    @property
    def world(self) -> int:
        return self.mesh.shape[self.axis_name]

    def _ring_devices(self) -> list | None:
        """The mesh's devices by collective-axis index, or None where the
        mesh has none or its other axes hold devices too."""
        if self.mesh.devices is None:
            return None
        devices = list(self.mesh.devices.flat)
        return devices if len(devices) == self.world else None

    @functools.cached_property
    def ring_order(self) -> tuple[int, ...]:
        """Mesh positions in the order the Pallas ring walks them: a cycle
        of torus neighbours where the devices' coordinates admit one
        (`ring_allreduce.torus_ring`), the mesh's own order otherwise."""
        from ..ops.ring_allreduce import torus_ring

        devices = self._ring_devices()
        if devices is None:
            return tuple(range(self.world))
        return torus_ring(devices)

    @functools.cached_property
    def ring_detours(self) -> int:
        """Hops of `ring_order` between chips with no link between them."""
        from ..ops.ring_allreduce import ring_detours

        devices = self._ring_devices()
        return 0 if devices is None else ring_detours(devices,
                                                      self.ring_order)

    def _wire(
        self,
        options: CallOptions,
        arithcfg: ArithConfig | None,
        func: ReduceFunction | None,
        compressed_domain: bool,
    ) -> schedules.Wire:
        """Resolve the datapath config: which compression lanes wrap each
        hop and which arith lane reductions use (prepare_call's dtype logic,
        reference accl.cpp:1236-1356). Blockwise-quantized rows (compressor
        lane 4) produce a Wire whose hops carry (int8 codes, per-block
        scales) and whose ring families fuse dequantize->reduce->requantize
        per step; because the call-sequence path composes the SAME _body
        lowerings, recorded sequences fuse quantized steps bitwise-
        identically to eager dispatch (pinned by the quantized sequence
        fuzz)."""
        arith_lane = None
        if arithcfg is not None and func is not None:
            arith_lane = arithcfg.arith_lanes[int(func)]
        eth = (
            arithcfg is not None
            and options.compression_flags & CompressionFlags.ETH_COMPRESSED
            and wire_dtype(arithcfg) is not None
        )
        # In compressed-domain execution the operand is cast once up front,
        # so per-hop lanes are disabled (payload already at wire width).
        cfg = arithcfg if (eth and not compressed_domain) else None
        return schedules.Wire(cfg, arith_lane)

    def compile(
        self,
        options: CallOptions,
        plan: Plan,
        arithcfg: ArithConfig | None = None,
    ) -> Callable:
        key = (options.signature(), plan, self.axis_name,
               self.use_pallas_ring, self.pallas_ring_overlap,
               self.overlap_serialize)
        fn = self._cache.get(key)
        if fn is not None:
            self.lower_hits += 1
        else:
            self.lower_misses += 1
            from ..utils.logging import Log

            Log.info("compiling %s: %s/%s world=%d count=%d",
                     options.scenario.name, plan.protocol.name,
                     plan.algorithm.name, self.world, options.count)
            fn = self._build(options, plan, arithcfg)
            self._cache[key] = fn
        return fn

    # -- construction -----------------------------------------------------

    def _build(self, options: CallOptions, plan: Plan, arithcfg) -> Callable:
        body, n_in = self._body(options, plan, arithcfg)
        return self._finalize(body, n_in)

    def _finalize(self, body, n_in: int, wrap=None) -> Callable:
        """shard_map + jit finalization shared by the per-call and
        call-sequence paths; `wrap` adapts the body's calling convention
        (single (1, n)-shard result by default, tuples for sequences)."""
        spec = PartitionSpec(self.axis_name)
        # vma checking is disabled because the pallas-lowered bodies carry
        # explicit vma annotations the checker cannot yet propagate through.
        shmapped = jax.shard_map(
            (wrap or _squeeze_wrap)(body, n_in),
            mesh=self.mesh,
            in_specs=(spec,) * n_in,
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(shmapped)

    def lower_streamed(
        self,
        options: CallOptions,
        plan: Plan,
        producer: Callable | None = None,
        consumer: Callable | None = None,
    ) -> Callable:
        """Streamed-operand collective (reference OP0_STREAM/RES_STREAM
        routing through any collective, ccl_offload_control.c:628-636 and
        the depacketizer's strm!=0 kernel-stream path,
        tcp_depacketizer.cpp:106-117): the operand comes from a traced
        on-device producer and/or the result is routed through a traced
        consumer, fused into the same compiled program."""
        from ..ops.streams import splice_consumer, splice_producer

        arithcfg = None
        if options.data_type != DataType.none:
            arithcfg = _arithcfg_for(self.arith_table, options)
        # the endpoint callables themselves are part of the key: holding a
        # strong reference prevents id-reuse after GC from resurrecting a
        # stale compiled program when an endpoint is re-registered
        key = (options.signature(), plan, self.axis_name,
               self.use_pallas_ring, self.pallas_ring_overlap,
               self.overlap_serialize, "streamed", producer, consumer)
        fn = self._cache.get(key)
        if fn is not None:
            self.lower_hits += 1
        else:
            self.lower_misses += 1
            body, n_in = self._body(options, plan, arithcfg)
            if producer is not None:
                if n_in != 1:
                    raise ValueError(
                        f"OP0_STREAM unsupported for {options.scenario.name}")
                # scatter-class inputs hold world stacked blocks per rank
                in_elems = options.count
                if options.scenario in (Operation.scatter,
                                        Operation.reduce_scatter,
                                        Operation.alltoall):
                    in_elems *= self.world
                body = splice_producer(body, producer, in_elems)
            if consumer is not None:
                body = splice_consumer(body, consumer)
            fn = self._finalize(body, n_in)
            self._cache[key] = fn
        return fn

    def _body(self, options: CallOptions, plan: Plan,
              arithcfg) -> tuple[Callable, int]:
        body: Callable
        axis, world = self.axis_name, self.world
        op = options.scenario
        root = options.root_src_dst

        if plan.algorithm == Algorithm.SYNTHESIZED:
            # A search-produced schedule from the committed library:
            # the certified hop-DAG is regenerated at this call's count
            # and lowered through the same wire primitives (ppermute
            # hops, blockwise int8 encode/decode, reduce-lane folds)
            # the Python bodies use — schedules as data end to end.
            # int8-wire entries carry their encode/decode lanes inside
            # the DAG, so the per-hop Wire built below stays off here.
            # TIERED entries (spec.tiers) validate their hop annotation
            # against the RankMap ring permutations and compile every
            # hop as ONE global ppermute over those pairs — inner hops
            # stay within a slice, outer hops cross — the same
            # ring=(pos, perm) embedding the HIER branch below rides.
            from . import synthesis

            return synthesis.lower_plan(plan, options, world, axis)

        if plan.algorithm == Algorithm.HIER_RS_AR_AG:
            # Striped two-tier allreduce: every hop is a GLOBAL permute
            # (inner hops stay within a slice, outer hops cross), so the
            # same body lowers on a flat axis, the DCN tuple axis, and
            # the analyzers' single-axis trace seam. Per-tier wires come
            # from the plan's frozen tier dtypes, resolved against the
            # arith table exactly like the flat wire path.
            from . import hierarchical

            func = ReduceFunction(options.function)

            def tier_wire(dt: DataType) -> schedules.Wire:
                cfg = (self.arith_table.get((options.data_type, dt))
                       if dt not in (DataType.none, options.data_type)
                       else None)
                lane = None
                if arithcfg is not None:
                    lane = arithcfg.arith_lanes[int(func)]
                return schedules.Wire(cfg, lane)

            rm = hierarchical.RankMap(plan.inner_world, plan.outer_world,
                                      "outer_major")
            tw = hierarchical.TierWire(tier_wire(plan.inner_wire_dtype),
                                       tier_wire(plan.outer_wire_dtype))
            body = functools.partial(
                hierarchical.hierarchical_allreduce_striped_schedule,
                func=func, axis=axis, rankmap=rm, wire=tw,
                stripes=plan.stripes)
            return body, 1

        func = ReduceFunction(options.function) if op in (
            Operation.combine,
            Operation.reduce,
            Operation.allreduce,
            Operation.reduce_scatter,
        ) else None
        # Reductions whose arithconfig reduces in the compressed domain
        # (arith_is_compressed, arithconfig.hpp:55-57) cast the operand to
        # the wire dtype once and run the whole schedule there, avoiding a
        # decompress/recompress pair at every hop.
        compressed_domain = bool(
            func is not None
            and arithcfg is not None
            and options.compression_flags & CompressionFlags.ETH_COMPRESSED
            and arithcfg.arith_is_compressed
            and wire_dtype(arithcfg) is not None
        )
        wire = self._wire(options, arithcfg, func, compressed_domain)
        common = dict(axis=axis, world=world, wire=wire)

        if op == Operation.copy:
            body, n_in = functools.partial(schedules.copy_schedule, **common), 1
        elif op == Operation.combine:
            body = functools.partial(schedules.combine_schedule, func=func, **common)
            n_in = 2
        elif op in (Operation.send, Operation.recv):
            # On the SPMD path send/recv lower to one sendrecv program
            # executed by the whole axis (src/dst from the descriptor).
            src = options.root_src_dst & 0xFFFF
            dst = (options.root_src_dst >> 16) & 0xFFFF
            body = functools.partial(
                schedules.sendrecv_schedule, src=src, dst=dst, **common
            )
            n_in = 1
        elif op == Operation.bcast:
            if plan.algorithm == Algorithm.RNDZV_BIN_TREE:
                body = functools.partial(
                    schedules.bcast_bin_tree_schedule, root=root, **common
                )
            else:
                body = functools.partial(
                    schedules.bcast_flat_schedule, root=root, **common
                )
            n_in = 1
        elif op == Operation.scatter:
            body = functools.partial(schedules.scatter_schedule, root=root, **common)
            n_in = 1
        elif op == Operation.gather:
            if plan.algorithm == Algorithm.EAGER_RING:
                body = functools.partial(
                    schedules.gather_ring_schedule, root=root, **common
                )
            else:
                body = functools.partial(
                    schedules.gather_flat_schedule,
                    root=root,
                    fanin=plan.tree_fanin,
                    **common,
                )
            n_in = 1
        elif op == Operation.allgather:
            body = functools.partial(schedules.allgather_ring_schedule, **common)
            n_in = 1
        elif op == Operation.reduce:
            if plan.algorithm == Algorithm.EAGER_RING:
                body = functools.partial(
                    schedules.reduce_ring_schedule, root=root, func=func, **common
                )
            elif plan.algorithm == Algorithm.RNDZV_BIN_TREE:
                body = functools.partial(
                    schedules.reduce_bin_tree_schedule, root=root, func=func, **common
                )
            else:
                body = functools.partial(
                    schedules.reduce_flat_schedule, root=root, func=func, **common
                )
            n_in = 1
        elif op == Operation.reduce_scatter:
            if plan.algorithm == Algorithm.RNDZV_REDUCE_SCATTER:
                # Composition: reduce-to-0 then scatter (.c:1768-1781);
                # the reduce stage's tree shape comes from plan.stages.
                reduce_body = self._reduce_body(plan.stages[0], 0, func, common)

                def _rs_composed(x, *, _c=common, _rb=reduce_body):
                    return schedules.scatter_schedule(_rb(x), root=0, **_c)

                body = _rs_composed
            else:
                body = functools.partial(
                    schedules.reduce_scatter_ring_schedule, func=func, **common
                )
            n_in = 1
        elif op == Operation.allreduce:
            if plan.algorithm == Algorithm.RNDZV_REDUCE_BCAST:
                # Composition: reduce-to-0 then broadcast (.c:1878-1887);
                # both stage shapes were re-selected by plan.py with the
                # live tuning registers.
                reduce_body = self._reduce_body(plan.stages[0], 0, func, common)
                bcast_bin = plan.stages[1].algorithm == Algorithm.RNDZV_BIN_TREE

                def _ar_composed(x, *, _c=common, _rb=reduce_body,
                                 _bin=bcast_bin):
                    red = _rb(x)
                    if _bin:
                        return schedules.bcast_bin_tree_schedule(red, root=0, **_c)
                    return schedules.bcast_flat_schedule(red, root=0, **_c)

                body = _ar_composed
            else:
                elem_bytes = 1
                if options.data_type != DataType.none:
                    from ..constants import dtype_nbytes

                    elem_bytes = dtype_nbytes(options.data_type)
                eth_active = bool(
                    arithcfg is not None
                    and options.compression_flags & CompressionFlags.ETH_COMPRESSED
                    and wire_dtype(arithcfg) is not None
                )
                # the dtype the fused kernel would run in: the wire dtype
                # under compressed-domain execution, the payload dtype
                # otherwise. On real TPU, dtypes Mosaic rejects (f16) must
                # take the lax schedule — XLA carries f16 natively, so the
                # requested wire compression keeps its bandwidth meaning
                # (the kernel-level _compiled_f16_detour would silently
                # widen the wire back to fp32).
                from ..ops.pallas_kernels import _mosaic_rejects

                from ..constants import to_numpy_dtype

                ring_dtype = (
                    wire_dtype(arithcfg) if compressed_domain
                    else (to_numpy_dtype(options.data_type)
                          if options.data_type != DataType.none else None)
                )
                mosaic_ok = not (
                    ring_dtype is not None
                    and _mosaic_rejects(ring_dtype)
                    and self.on_tpu
                )
                if (
                    self.use_pallas_ring
                    # per-hop compression with uncompressed-domain arithmetic
                    # cannot be fused into the single-dtype ring kernel —
                    # this also routes the blockwise-quantized wire (whose
                    # hops carry a scale side-channel) to the lax quantized
                    # ring below, where the fused dequant-reduce-requant
                    # kernels live
                    and (not eth_active or compressed_domain)
                    and mosaic_ok
                    # the degraded live-subset mode lowers through the lax
                    # ring, where the source mask is part of the traced
                    # body the certifier lifts (the VMEM kernel has no
                    # masked variant)
                    and not plan.live_ranks
                ):
                    from ..ops.ring_allreduce import (
                        NUM_RING_SLOTS,
                        interpret_for,
                        ring_allreduce_pallas_bidir,
                    )

                    # Kernel-resource chunking: the VMEM-resident kernel
                    # caps per-launch payload, so larger buffers run it per
                    # segment. The kernel's neighbor-barrier/credit
                    # semaphores and comm buffers are keyed per SEGMENT
                    # SLOT (collective_id per slot, ring_allreduce
                    # NUM_RING_SLOTS), so consecutive segments
                    # double-buffer and overlap like the reference's
                    # segmenter/rx-ring; only slot reuse is ordered
                    # (segmented_apply overlap_slots). The serialized
                    # baseline stays reachable for A/B measurement via
                    # ACCL_PALLAS_RING_SERIALIZE=1. (Protocol
                    # segmentation — plan.seg_count — stays plan-owned and
                    # governs the lax path.)
                    seg_elems = max(self.PALLAS_RING_MAX_BYTES // elem_bytes, 1)

                    def one_seg(y, slot=0, *, _c=common, _f=func,
                                _i=interpret_for(self.mesh),
                                _r=self.ring_order):
                        return ring_allreduce_pallas_bidir(
                            y, axis_name=_c["axis"], world=_c["world"],
                            func=_f, slot=slot, interpret=_i, ring=_r,
                        )

                    def _pallas_ring_body(x, *, _c=common, _seg=seg_elems,
                                          _overlap=self.pallas_ring_overlap):
                        y = _c["wire"].send(x)  # wire compression outside
                        if _overlap:
                            out = schedules.segmented_apply(
                                one_seg, y, _seg,
                                overlap_slots=NUM_RING_SLOTS,
                            )
                        else:
                            out = schedules.segmented_apply(
                                one_seg, y, _seg, serialize=True
                            )
                        return _c["wire"].recv(out, x.dtype)

                    body = _pallas_ring_body
                else:
                    body = functools.partial(
                        schedules.allreduce_ring_schedule,
                        func=func,
                        seg_count=plan.seg_count,
                        # the serial dispatch->compute twin: stripe
                        # chains of an OVERLAP plan barrier-ordered
                        # (plain rx-geometry segmentation is untouched
                        # — only cost-model-striped plans have a twin)
                        serialize=(self.overlap_serialize
                                   and plan.stripes > 1),
                        # degraded live-subset mode: the declared
                        # survivor set masks non-members' operands to
                        # zeros at the source (None = every rank
                        # contributes, the ordinary ring)
                        live_ranks=(plan.live_ranks or None),
                        **common,
                    )
            n_in = 1
        elif op == Operation.alltoall:
            if plan.algorithm == Algorithm.FLAT_ALLTOALLV:
                body = functools.partial(
                    schedules.alltoallv_schedule,
                    peer_counts=plan.peer_counts, **common)
            else:
                body = functools.partial(schedules.alltoall_schedule,
                                         **common)
            n_in = 1
        elif op == Operation.barrier:
            body = functools.partial(schedules.barrier_schedule, **common)
            n_in = 1
        else:
            raise ValueError(f"cannot lower scenario {op!r}")

        if compressed_domain:
            inner, wd = body, wire_dtype(arithcfg)

            def _domain_cast_body(*args, _inner=inner, _wd=wd):
                orig = args[0].dtype
                out = _inner(*(a.astype(_wd) for a in args))
                return out.astype(orig)

            body = _domain_cast_body
        return body, n_in

    def _reduce_body(self, stage_plan: Plan, root: int, func, common):
        """The reduce stage of a composed collective, shaped by its
        re-selected plan (flat vs binomial, .c:1531 vs .c:1603)."""
        if stage_plan.algorithm == Algorithm.RNDZV_BIN_TREE:
            return functools.partial(
                schedules.reduce_bin_tree_schedule, root=root, func=func, **common
            )
        if stage_plan.algorithm == Algorithm.EAGER_RING:
            return functools.partial(
                schedules.reduce_ring_schedule, root=root, func=func, **common
            )
        return functools.partial(
            schedules.reduce_flat_schedule, root=root, func=func, **common
        )

    # -- call sequences ----------------------------------------------------

    def compile_sequence(self, seq) -> Callable:
        """Lower a SequencePlan into ONE compiled device program: every
        step's schedule body composed over the batch's buffer table inside
        a single jit(shard_map(...)). Cached under the batch's composite
        signature alongside the per-call entries, so re-recording the same
        shapes+dataflow compiles nothing."""
        key = seq.cache_key(self.axis_name, self.use_pallas_ring,
                            self.pallas_ring_overlap,
                            self.overlap_serialize)
        fn = self._cache.get(key)
        if fn is not None:
            self.lower_hits += 1
        else:
            self.lower_misses += 1
            from ..utils.logging import Log

            Log.info(
                "compiling sequence of %d steps: %s world=%d",
                len(seq.steps),
                "+".join(s.options.scenario.name for s in seq.steps),
                self.world,
            )
            body, n_in = seq.build(self)
            fn = self._finalize_sequence(body, n_in)
            self._cache[key] = fn
        return fn

    def _finalize_sequence(self, body, n_in: int) -> Callable:
        # kept as a distinct seam (tests pin it to detect re-traces)
        return self._finalize(body, n_in, wrap=_tuple_wrap)

    # -- convenience: full pipeline from descriptor ------------------------

    def lower(self, options: CallOptions, plan: Plan) -> Callable:
        arithcfg = None
        if options.data_type != DataType.none:
            arithcfg = _arithcfg_for(self.arith_table, options)
        return self.compile(options, plan, arithcfg)


class AxisOnlyMesh:
    """The minimal mesh surface `ScheduleCompiler._body` consumes (axis
    size lookup); tracing under make_jaxpr's axis env needs no
    devices."""

    devices = None  # lowers for no platform

    def __init__(self, axis_name: str, world: int):
        self.shape = {axis_name: world}


def analysis_body(options: CallOptions, plan: Plan, world: int,
                  axis_name: str = "ccl",
                  arith_table: dict | None = None) -> tuple[Callable, int]:
    """The IR-extraction hook for the static analyzers: build the SAME
    schedule body the compiler would lower — nothing re-modeled — for
    abstract evaluation under an axis environment. Pallas lowering is
    forced off (the lax family expresses the identical wire pattern
    through ppermute, which is the surface the analyses read); the
    protocol pass collects the traced body's ppermute perms and the
    semantic certifier lifts its full hop DAG from it."""
    comp = ScheduleCompiler(AxisOnlyMesh(axis_name, world), axis_name,
                            arith_table=arith_table,
                            use_pallas_ring=False)
    arithcfg = None
    if options.data_type != DataType.none:
        arithcfg = _arithcfg_for(comp.arith_table, options)
    return comp._body(options, plan, arithcfg)


def _arithcfg_for(table, options: CallOptions):
    dt = options.data_type
    if options.compress_dtype != DataType.none:
        # The caller named a wire dtype (prepare_call's compressed-operand
        # resolution): the row must match exactly.
        return table.get((dt, options.compress_dtype))
    if options.compression_flags & CompressionFlags.ETH_COMPRESSED:
        for (unc, cmp_), cfg in table.items():
            if unc == dt and unc != cmp_:
                return cfg
    return table.get((dt, dt))


def _squeeze_wrap(body, n_in):
    """shard_map hands each rank a (1, n) shard of the stacked (world, n)
    operand; schedules work on flat (n,) buffers."""

    def wrapped(*args):
        flat = [a.reshape(a.shape[-1]) for a in args]
        out = body(*flat)
        return out.reshape(1, out.shape[-1])

    return wrapped


def _tuple_wrap(body, n_in):
    """The call-sequence calling convention: the fused body returns one
    flat buffer per written address; each reshapes back to a (1, n)
    shard."""

    def wrapped(*args):
        flat = [a.reshape(a.shape[-1]) for a in args]
        outs = body(*flat)
        return tuple(o.reshape(1, o.shape[-1]) for o in outs)

    return wrapped
