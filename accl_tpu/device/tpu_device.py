"""TPUDevice: descriptor execution as compiled mesh programs.

The hardware backend (reference XRTDevice, driver/xrt/src/xrtdevice.cpp):
where XRTDevice latches descriptor words into the hostctrl kernel and an
on-FPGA firmware loop interprets them, TPUDevice resolves the descriptor's
buffer addresses against its buffer registry, asks the sequencer for a
plan, and launches the cached compiled schedule — one device program per
collective, with XLA's async dispatch standing in for the hardware call
FIFO. Single-controller SPMD replaces per-rank MPI processes: one call
executes the collective for every rank in the communicator.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import numpy as np
import jax

from ..constants import (
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_MAX_RENDEZVOUS_SIZE,
    CfgFunc,
    DataType,
    ErrorCode,
    Operation,
    TAG_ANY,
    TuningParams,
    dtype_nbytes,
)
from ..descriptor import CallOptions
from ..request import BaseRequest, ParkedRecvRequest, TPURequest
from ..sequencer.lowering import ScheduleCompiler
from ..sequencer.plan import select_algorithm
from ..telemetry import get_tracer
from .base import CCLOAddr, CCLODevice


class TPUDevice(CCLODevice):
    # the blockwise int8 wire (compressor lanes 4/5) is implemented in
    # the XLA schedule tier only; backends without the quantized ring
    # kernels leave this unset so the facade rejects the request up
    # front instead of letting a lane-less executor degrade it silently
    supports_quantized_wire = True
    # the capacity-masked alltoallv rotation
    # (schedules.alltoallv_schedule) is likewise XLA-schedule-tier only:
    # the native emulator's alltoall knows nothing about per-peer valid
    # counts, so the facade rejects uneven vectors on lane-less backends
    supports_alltoallv = True
    # the ALLTOALL_COMPRESS_MIN_COUNT register auto-applies the int8
    # wire to eligible fp32 alltoall(v) calls on this device (backends
    # whose alltoall is not the flat exchange the crossover was
    # calibrated for — DCNDevice's two-tier composition — opt out)
    auto_alltoall_wire = True
    # the degraded live-subset allreduce (source-masked ring,
    # schedules.allreduce_ring_schedule live_ranks=) is an XLA-tier
    # schedule like alltoallv: the native emulator's ring knows nothing
    # about a declared survivor set (its degraded path is membership
    # change — a recovery sub-communicator over the survivors)
    supports_live_subset = True

    def __init__(self, mesh, axis_name: str = "ccl",
                 hier_topology: tuple[int, int] | None = None):
        super().__init__()
        self.mesh = mesh
        self.axis_name = axis_name
        self.compiler = ScheduleCompiler(mesh, axis_name)
        # Two-tier (inner_world, outer_world) shape for the hierarchical
        # compositions: DCNDevice sets it from its (ici, dcn) mesh; a
        # flat mesh may declare a VIRTUAL factoring (the bench's
        # 8-ranks-as-4x2 emulated world). None = flat — and even with a
        # topology, hierarchical plans stay unreachable until the
        # HIER_ALLREDUCE_MIN_COUNT register is tuned on.
        self.hier_topology = hier_topology
        # Per-tier wire dtypes for hierarchical plans, set by
        # ACCL.autotune from plan.select_tier_wires (int8 on DCN / fp32
        # on ICI under the shipped calibration); default exact on both
        # tiers. Arbitrated for the canonical fp32 payload, so
        # _resolve_step applies them to fp32 calls only.
        self.hier_wires: tuple[DataType, DataType] = (DataType.none,
                                                      DataType.none)
        self.buffers: dict[int, Any] = {}  # address -> TPUBuffer
        self.timeout = 1_000_000
        self.max_eager_size = DEFAULT_MAX_EAGER_SIZE
        self.max_rendezvous_size = DEFAULT_MAX_RENDEZVOUS_SIZE
        self.eager_rx_buf_size = DEFAULT_EAGER_RX_BUF_SIZE
        self.pkt_enabled = False
        # Pending sends awaiting their recv partner (single-controller
        # pairing of the MPI-style send/recv API) and recvs parked until
        # their send arrives (the firmware retry-queue contract,
        # ccl_offload_control.c:2460-2479 — a recv with no matching
        # message is requeued, not failed, until the timeout).
        # Each signature keys a FIFO of (arrival_seq, options): every
        # notification parks, none is dropped, and TAG_ANY matching picks
        # the globally oldest across signatures — arrival order, like the
        # reference's in-order notification queue scan (rxbuf_seek.cpp:20-79).
        # Total parked sends are capped at the reference's 512-notification
        # park limit (rxbuf_seek.cpp:47-50); beyond that the send errors.
        self._pending_sends: dict[tuple, list[tuple[int, CallOptions]]] = {}
        self._park_seq = 0
        self._parked_send_count = 0
        self.MAX_PARKED_SENDS = 512
        # BOTH pending maps are guarded by _recv_mu: mutated by driver
        # threads (match-or-enqueue on send, match-or-park on recv) and
        # by waiter threads firing timeouts (unpark)
        self._recv_mu = threading.Lock()
        # XLA's CPU cross-module collectives rendezvous per device SET,
        # not per executable: two collective programs launched
        # concurrently over the same mesh interleave their participants
        # in one rendezvous and deadlock. The emulated CCLO has a single
        # sequencer anyway, so executable launches serialize here —
        # concurrent dispatches interleave at PROGRAM granularity, the
        # exact model certify_concurrent proves order-equivalence for.
        self._launch_mu = threading.Lock()
        self._pending_recvs: dict[tuple, list[ParkedRecvRequest]] = {}
        # Kernel-stream endpoints (strm != 0 routing, SURVEY.md §3.4).
        from ..ops.streams import StreamRegistry

        self.streams = StreamRegistry()
        self._stream_cache: dict = {}
        # composite-signature -> lint diagnostics (sequence lint stage)
        self._lint_cache: dict = {}
        # comm_addr -> resolved communicator context (the firmware caches
        # the addressed communicator per call, ccl_offload_control.c:2317-2372)
        self._comm_cache: dict[int, "_CommCtx"] = {}
        self._comm_extents: dict[int, int] = {}  # comm_addr -> table end
        self._group_cache: dict[tuple, "_CommCtx"] = {}  # members -> ctx
        # per-program timing.predict estimates with the link they used
        self._predictions: dict = {}
        # results written into a wider buffer: each runs its own program
        self.place_copies = 0

    # -- registry ---------------------------------------------------------

    @property
    def world(self) -> int:
        return self.mesh.shape[self.axis_name]

    def register_buffer(self, buf) -> None:
        self.buffers[buf.address] = buf

    def unregister_buffer(self, buf) -> None:
        self.buffers.pop(buf.address, None)

    def _buf(self, addr: int):
        if addr == 0:
            return None
        try:
            return self.buffers[addr]
        except KeyError:
            raise KeyError(f"no buffer registered at address {addr:#x}") from None

    # -- tuning registers (exchange-memory backed) ------------------------

    def tuning(self) -> TuningParams:
        rd = self.read
        defaults = TuningParams.default(self.max_rendezvous_size)
        return TuningParams(
            gather_flat_tree_max_fanin=rd(CCLOAddr.GATHER_FLAT_TREE_MAX_FANIN)
            or defaults.gather_flat_tree_max_fanin,
            gather_flat_tree_max_count=rd(CCLOAddr.GATHER_FLAT_TREE_MAX_COUNT)
            or defaults.gather_flat_tree_max_count,
            bcast_flat_tree_max_ranks=rd(CCLOAddr.BCAST_FLAT_TREE_MAX_RANKS)
            or defaults.bcast_flat_tree_max_ranks,
            reduce_flat_tree_max_ranks=rd(CCLOAddr.REDUCE_FLAT_TREE_MAX_RANKS)
            or defaults.reduce_flat_tree_max_ranks,
            reduce_flat_tree_max_count=rd(CCLOAddr.REDUCE_FLAT_TREE_MAX_COUNT)
            or defaults.reduce_flat_tree_max_count,
            # 0 is this register's meaningful default (ring everywhere),
            # so no `or defaults` fallback
            allreduce_composition_max_count=rd(
                CCLOAddr.ALLREDUCE_COMPOSITION_MAX_COUNT),
            # likewise 0 = synthesized schedules off
            synth_allreduce_max_count=rd(
                CCLOAddr.SYNTH_ALLREDUCE_MAX_COUNT),
            synth_allgather_max_count=rd(
                CCLOAddr.SYNTH_ALLGATHER_MAX_COUNT),
            synth_reduce_scatter_max_count=rd(
                CCLOAddr.SYNTH_REDUCE_SCATTER_MAX_COUNT),
            # and 0 = hierarchical composition off
            hier_allreduce_min_count=rd(
                CCLOAddr.HIER_ALLREDUCE_MIN_COUNT),
            # and 0 = quantized alltoall wire off
            alltoall_compress_min_count=rd(
                CCLOAddr.ALLTOALL_COMPRESS_MIN_COUNT),
            # and 0 = stripe-overlapped allreduce off (serial form)
            overlap_min_count=rd(CCLOAddr.OVERLAP_MIN_COUNT),
            # and 0 = latency-window synthesized schedules off
            synth_latency_max_count=rd(
                CCLOAddr.SYNTH_LATENCY_MAX_COUNT),
        )

    # -- communicator resolution (comm_addr -> rank group) -----------------

    def _comm_ctx(self, comm_addr: int) -> "_CommCtx":
        """Resolve a descriptor's comm_addr into an execution context by
        reading the rank table back from exchange memory — the same
        caching the firmware does per call (ccl_offload_control.c:2317-2372).
        comm_addr 0 or a full-world identity table is the default axis."""
        ctx = self._comm_cache.get(comm_addr)
        if ctx is not None:
            return ctx
        rows = None
        table_words = 0
        if comm_addr != 0:
            from ..communicator import Communicator

            size = self.read(comm_addr)
            if not 0 < size <= self.world:
                raise ValueError(
                    f"invalid communicator at {comm_addr:#x}: size={size}")
            nwords = 2 + size * Communicator.WORDS_PER_RANK
            table_words = nwords
            words = [self.read(comm_addr + 4 * i) for i in range(nwords)]
            comm = Communicator.from_exchmem_words(words, comm_addr)
            members = tuple(r.device_index for r in comm.ranks)
            if any(not 0 <= d < self.world for d in members):
                raise ValueError(
                    f"communicator at {comm_addr:#x} references device "
                    f"indices {members} outside world {self.world}")
            if len(set(members)) != len(members):
                raise ValueError(
                    f"communicator at {comm_addr:#x} has duplicate "
                    f"members {members}")
            if members != tuple(range(self.world)):
                rows = members
        if rows is None:
            ctx = _CommCtx(self.world, self.mesh, self.compiler, None)
        else:
            # identical member sets at different table addresses share one
            # context, so re-splits reuse the compiled schedules
            ctx = self._group_cache.get(rows)
            if ctx is None:
                ctx = self._make_group_ctx(rows)
                self._group_cache[rows] = ctx
        self._comm_cache[comm_addr] = ctx
        if table_words:
            self._comm_extents[comm_addr] = comm_addr + 4 * table_words
        return ctx

    def _make_group_ctx(self, rows: tuple) -> "_CommCtx":
        """Build the execution context for a sub-communicator (overridden
        by backends with a different mesh topology)."""
        from jax.sharding import Mesh

        devices = self.mesh.devices.reshape(-1)
        sub_mesh = Mesh(np.array([devices[r] for r in rows]),
                        (self.axis_name,))
        compiler = ScheduleCompiler(
            sub_mesh, self.axis_name,
            arith_table=self.compiler.arith_table,
            use_pallas_ring=self.compiler.use_pallas_ring,
            pallas_ring_overlap=self.compiler.pallas_ring_overlap,
            overlap_serialize=self.compiler.overlap_serialize,
        )
        return _CommCtx(len(rows), sub_mesh, compiler, rows)

    def write(self, addr: int, value: int) -> None:
        # a write into a cached communicator table invalidates that cache
        # entry (the firmware re-reads exchange memory per call; the cache
        # must not outlive the table it mirrors)
        for start, end in list(self._comm_extents.items()):
            if start <= addr < end:
                self._comm_cache.pop(start, None)
                self._comm_extents.pop(start, None)
        super().write(addr, value)

    def validate_split(self, rows: tuple) -> None:
        """Reject an unsupported rank group BEFORE the facade allocates
        exchange memory for it (backends with topology constraints
        override; the base single-controller mesh accepts any subset)."""

    def _rows_to_submesh(self, arr, ctx: "_CommCtx", n: int):
        """View the member rows of a full-world stacked buffer as a
        (group, n) array on the sub-mesh. Each row already lives on its
        member device, so this is shard re-labelling, not data movement.
        Non-addressable devices (remote hosts on a multi-process backend)
        contribute their own shards from their own processes."""
        from jax.sharding import NamedSharding, PartitionSpec

        by_dev = {s.device: s.data for s in arr.addressable_shards}
        shards = [by_dev[d][..., :n]
                  for d in ctx.mesh.devices.reshape(-1) if d in by_dev]
        sharding = NamedSharding(ctx.mesh, PartitionSpec(self.axis_name))
        return jax.make_array_from_single_device_arrays(
            (ctx.world, n), sharding, shards)

    def _scatter_rows(self, full, ctx: "_CommCtx", out):
        """Write a sub-communicator result back into the member rows of a
        full-world buffer, leaving non-member rows (and remote hosts'
        rows, which their own processes assemble) untouched."""
        by_dev = {s.device: s.data for s in full.addressable_shards}
        out_by_dev = {s.device: s.data for s in out.addressable_shards}
        shards = []
        for d in self.mesh.devices.reshape(-1):
            if d not in by_dev:
                continue  # remote device on a multi-process backend
            cur = by_dev[d]
            if d in out_by_dev:
                new = out_by_dev[d].astype(cur.dtype)
                if new.shape[-1] != cur.shape[-1]:
                    new = cur.at[..., : new.shape[-1]].set(new)
                shards.append(new)
            else:
                shards.append(cur)
        return jax.make_array_from_single_device_arrays(
            full.shape, full.sharding, shards)

    # -- execution --------------------------------------------------------

    def start(self, options: CallOptions) -> BaseRequest:
        if options.scenario == Operation.config:
            return self._config(options)
        if options.scenario == Operation.nop:
            req = BaseRequest("nop")
            req.running()
            req.complete(0)
            return req
        if options.scenario == Operation.send:
            return self._enqueue_send(options)
        if options.scenario == Operation.recv:
            return self._match_recv(options)
        return self._launch(options)

    def _apply_alltoall_wire(self, options: CallOptions,
                             tuning: TuningParams) -> CallOptions:
        """The ALLTOALL_COMPRESS_MIN_COUNT register, applied where the
        hier wires are: per-descriptor, in front of plan selection, for
        BOTH the eager path and the call-sequence path. An uncompressed
        unstreamed-or-streamed fp32 alltoall(v) whose payload clears the
        register ships the blockwise int8 wire (compress_dtype=int8 +
        ETH_COMPRESSED, exactly the descriptor the facade's explicit
        `compress_dtype=` seam would have produced — same plan, same
        compiled program, same cache key). Register 0 — the default —
        returns the descriptor untouched, so selection stays bit-for-bit
        the fp32 wire. Applied to fp32 calls only (the dtype the
        crossover was calibrated for) on devices that ship the quantized
        lanes."""
        reg = tuning.alltoall_compress_min_count
        if (reg <= 0
                or options.scenario != Operation.alltoall
                or options.data_type != DataType.float32
                or options.compress_dtype != DataType.none
                or int(options.compression_flags) != 0
                or not getattr(self, "auto_alltoall_wire", False)
                or not getattr(self, "supports_quantized_wire", False)):
            return options
        # what actually crosses each hop: the dense slot for alltoall,
        # max(peer_counts) elements for the capacity-bounded alltoallv —
        # the same payload the FLAT_ALLTOALLV cost shape charges, so a
        # heavily-capped exchange is not quantized in the regime the
        # calibration says the exact wire wins
        hop_elems = (max(options.peer_counts) if options.peer_counts
                     else options.count)
        if hop_elems * dtype_nbytes(options.data_type) < reg:
            return options
        if (DataType.float32, DataType.int8) not in self.compiler.arith_table:
            return options
        import dataclasses

        from ..constants import CompressionFlags

        return dataclasses.replace(
            options, compress_dtype=DataType.int8,
            compression_flags=CompressionFlags.ETH_COMPRESSED)

    def _resolve_step(self, options: CallOptions, ctx: "_CommCtx",
                      tuning: TuningParams | None = None):
        """Per-descriptor plan selection + stream-endpoint resolution —
        ONE source for both the eager path and the call-sequence path, so
        the fused program can never silently diverge from what eager
        execution would run. Returns (plan, producer, consumer)."""
        # the two-tier topology applies only to the full-world
        # communicator: a sub-communicator is its own (usually flat)
        # world and selects flat schedules
        topo = self.hier_topology if (
            self.hier_topology is not None and ctx.rows is None) else None
        plan = select_algorithm(
            options.scenario,
            options.count,
            dtype_nbytes(options.data_type),
            ctx.world,
            options.compression_flags,
            options.stream_flags,
            max_eager_size=self.max_eager_size,
            eager_rx_buf_size=self.eager_rx_buf_size,
            tuning=tuning if tuning is not None else self.tuning(),
            # the wire rides the Plan so timing.predict on recorded
            # plans charges compressed widths (+ scale side-channel)
            compress_dtype=options.compress_dtype,
            topology=topo,
            # arbitrated for fp32 (the canonical payload); other dtypes
            # stay exact on both tiers — their arith rows may not exist
            tier_wires=(self.hier_wires
                        if options.data_type == DataType.float32
                        else (DataType.none, DataType.none)),
            # alltoallv: the static per-peer capacity vector rides the
            # descriptor into the Plan (frozen, cache-keyed)
            peer_counts=options.peer_counts,
            # degraded live-subset allreduce: the declared survivor set
            # rides the descriptor into the Plan the same way
            live_ranks=options.live_ranks,
        )
        # stream ids ride dedicated descriptor bytes (word 8), so the tag
        # stays available for matching
        from ..constants import StreamFlags

        producer = consumer = None
        if options.stream_flags & StreamFlags.OP0_STREAM:
            producer = self.streams.producer(options.op0_stream_id)
        if options.stream_flags & StreamFlags.RES_STREAM:
            consumer = self.streams.consumer(options.res_stream_id,
                                             strict=True)
        return plan, producer, consumer

    def _launch(self, options: CallOptions) -> BaseRequest:
        # the facade's call span while spans are collected, else None:
        # each child span below then costs one test
        tracer = get_tracer()
        call = tracer.current()
        ctx = self._comm_ctx(options.comm_addr)
        # send/recv arrive here already PAIRED (start() routes the raw
        # halves through the parking maps; _pair merged their endpoint ids)
        sp = call.begin("plan") if call else None
        tuning = self.tuning()
        options = self._apply_alltoall_wire(options, tuning)
        plan, producer, consumer = self._resolve_step(options, ctx, tuning)
        if sp:
            sp.end(algorithm=plan.algorithm.name)
        sp = call.begin("lower") if call else None
        compiler = ctx.compiler
        misses = compiler.lower_misses
        if options.stream_flags:
            fn = compiler.lower_streamed(options, plan, producer, consumer)
        else:
            fn = compiler.lower(options, plan)
        if sp:
            if compiler.lower_misses == misses:
                sp.end(hit=True)
            else:  # the ring the new program walks
                sp.end(hit=False, ring_order=list(compiler.ring_order),
                       ring_detours=compiler.ring_detours)

        op0 = self._buf(options.addr_0)
        op1 = self._buf(options.addr_1)
        res = self._buf(options.addr_2)
        args = []
        scen = options.scenario
        # single source for the wide-operand width rule, shared with the
        # call-sequence dataflow resolution
        from ..sequencer.sequence import step_in_elems

        in_n = step_in_elems(options, ctx.world)
        if scen == Operation.barrier:
            from jax.sharding import NamedSharding, PartitionSpec

            token_sharding = NamedSharding(ctx.mesh, PartitionSpec(self.axis_name))
            args.append(
                jax.device_put(np.ones((ctx.world, 1), np.float32), token_sharding)
            )
        elif ctx.rows is None:
            args.append(_slice_to(op0.device, in_n))
            if scen == Operation.combine:
                args.append(_slice_to(op1.device, in_n))
        else:
            args.append(self._rows_to_submesh(op0.device, ctx, in_n))
            if scen == Operation.combine:
                args.append(self._rows_to_submesh(op1.device, ctx, in_n))

        # the duration register: from before the launch to the host
        # seeing the result ready (the launch and wait spans inside it)
        with self._launch_mu:  # one collective executable in flight
            t0 = time.perf_counter_ns()
            sp = call.begin("launch") if call else None
            out = fn(*args)
            if sp:
                sp.end()
            sp = call.begin("wait") if call else None
            jax.block_until_ready(out)
            if sp:
                sp.end()
            t1 = time.perf_counter_ns()

        def place(req):
            if res is None or scen == Operation.barrier:
                return
            # a child only while its call is still the open one (an
            # async request may complete after the call span closed)
            sp = (call.begin("place")
                  if call and tracer.current() is call else None)
            copies = self.place_copies
            if res.device is None:  # host-only result: materialize first
                res.sync_to_device()
            if ctx.rows is None:
                res.device = self._place(res.device, out)
            else:
                res.device = self._scatter_rows(res.device, ctx, out)
            if sp:
                sp.end(copied=self.place_copies != copies)

        req = TPURequest(options.scenario.name, [out], on_complete=place,
                         duration_ns=t1 - t0)
        req.plan = plan
        if tracer.active:
            # the facade span drains this: every traced call carries its
            # timing.predict estimate next to the measured duration
            req.predicted_s = self._predict_call(options, plan, ctx.world,
                                                 fn)
        return req

    def _place(self, dst, out):
        """`_place_into`, counting in `place_copies` the writes into a
        wider buffer, each of which runs a program of its own."""
        if dst.shape != out.shape:
            self.place_copies += 1
        return _place_into(dst, out)

    def _predict_call(self, options: CallOptions, plan, world: int,
                      program=None) -> float | None:
        """timing.predict estimate for one resolved call under the
        shipped default link (telemetry.feedback.default_link, the same
        calibration autotune consults); None when no timing model is
        committed or the plan has no cost shape. Uses the aggregate
        cost shape — the regime the shipped emulator fit calibrates.

        With the call's compiled `program`, evaluated once per program
        and link: the lowering cache keys a program by the op, count,
        dtype, plan and world the estimate is a pure function of, and
        its identity is a far cheaper key than those."""
        from ..telemetry.feedback import default_link

        link = default_link()
        if link is None or plan is None:
            return None
        key = (program, self.eager_rx_buf_size)
        if program is not None:
            hit = self._predictions.get(key)
            if hit is not None and hit[0] is link:
                return hit[1]
        from ..sequencer.timing import predict

        try:
            pred = predict(link, options.scenario, plan, options.count,
                           dtype_nbytes(options.data_type), world,
                           rx_buf_bytes=self.eager_rx_buf_size,
                           aggregate=True)
        except (ValueError, KeyError, ZeroDivisionError):
            pred = None
        if program is not None:
            self._predictions[key] = (link, pred)
        return pred

    def predict_sequence_cost(self, prepared) -> float | None:
        """Predicted steady-state seconds for ONE dispatch of a
        prepared batch under the shipped default link — the admission
        price the multi-tenant scheduler budgets a tenant's program at
        BEFORE dispatching it (timing.predict_prepared over the frozen
        steps + plans, aggregate cost shape). None when no calibration
        is committed or the batch has no priceable step (the scheduler
        then falls back to its bytes proxy rather than admitting for
        free)."""
        from ..sequencer.timing import predict_prepared
        from ..telemetry.feedback import default_link

        link = default_link()
        if link is None:
            return None
        try:
            return predict_prepared(
                link, prepared.desc.steps, prepared.plans,
                prepared.ctx.world,
                rx_buf_bytes=self.eager_rx_buf_size, aggregate=True)
        except (ValueError, KeyError, ZeroDivisionError):
            return None

    # -- call sequences (device-resident descriptor batches) ---------------

    def start_sequence(self, options_list, lint: str = "error",
                       persistent=frozenset()) -> BaseRequest:
        """Execute a recorded batch of call descriptors as ONE compiled
        device program (sequencer.sequence.SequencePlan): a single
        dispatch for the whole chain, intermediate results threaded
        on-device between stages instead of re-crossing the host. Plans
        are selected per step with the live tuning registers, exactly as
        the eager path would.

        `lint` gates the batch through the static analyzer
        (accl_tpu/analysis/) BEFORE anything compiles: "error" rejects
        hazardous batches with a typed LintError, "warn" logs the
        diagnostics and proceeds, "off" skips the stage, "deep" adds
        the exhaustive-interleaving model checker (ACCL205/206,
        budgeted) on top of "error" enforcement. Results are cached
        under the same composite signature the compiled program is —
        keyed per tier, so a re-recorded batch re-lints nothing and
        the default tier never pays for the deep one.

        `persistent` (buffer addresses) declares device-resident state
        the batch refreshes partial-width by design — the hazard pass
        waives ACCL101 for those buffers only (docs/lint.md)."""
        return self.dispatch_sequence(
            self.prepare_sequence(options_list, lint,
                                  persistent=persistent))

    def prepare_sequence(self, options_list, lint: str = "error",
                         persistent=frozenset()) -> "_PreparedSequence":
        """The resolve half of `start_sequence`: wire-register rewrite,
        per-step plan selection, lint gate, dataflow resolution and
        compile — everything whose result is a pure function of the
        descriptor batch and the live registers — captured in a
        re-dispatchable handle. `dispatch_sequence(prepared)` then runs
        the compiled program over the bound buffers' CURRENT contents:
        steady-state cost is one dispatch, none of the per-call
        re-resolution (the facade's SequenceRecorder.compile() /
        SequenceProgram ride this seam). The handle pins the registers
        it was resolved under — re-prepare after retuning."""
        from ..descriptor import SequenceDescriptor
        from ..sequencer.sequence import SequencePlan

        desc = SequenceDescriptor(tuple(options_list))
        ctx = self._comm_ctx(desc.comm_addr)
        tuning = self.tuning()  # read the registers once for the batch
        # the alltoall wire register rewrites descriptors BEFORE the
        # batch signature / lint / compile pipeline sees them, so the
        # fused program is keyed, traced and certified on what actually
        # runs (register 0 leaves every descriptor untouched)
        steps = tuple(self._apply_alltoall_wire(o, tuning)
                      for o in desc.steps)
        if steps != desc.steps:
            desc = SequenceDescriptor(steps)
        tracer = get_tracer()
        # the composite signature tags every phase/step span, so one
        # batch's record -> lint -> compile -> dispatch pipeline can be
        # followed across tracks in the exported trace, and it keys the
        # per-pair interference-verdict cache. A content digest, not
        # hash(): enum hashes are PYTHONHASHSEED-salted, and the
        # signature must match across runs so archived traces correlate.
        # Computed unconditionally — a program prepared with tracing OFF
        # must still dispatch with its signature (a tracer enabled later,
        # and certify_concurrent, both need it).
        import hashlib

        sig = hashlib.sha256(
            repr(desc.signature()).encode()).hexdigest()[:16]
        with tracer.span("record", cat="phase", track="device") as sp:
            sp.set(signature=sig, n_steps=len(desc.steps))
            plans = []
            endpoints = []
            for opts in desc.steps:
                plan, producer, consumer = self._resolve_step(opts, ctx,
                                                              tuning)
                plans.append(plan)
                endpoints.append((producer, consumer))

        if lint != "off":
            with tracer.span("lint", cat="phase", track="device") as sp:
                sp.set(signature=sig, tier=lint)
                self._lint_batch(desc, tuple(plans), ctx, lint,
                                 persistent=frozenset(persistent))

        with tracer.span("compile", cat="phase", track="device") as sp:
            sp.set(signature=sig)
            seq = SequencePlan(desc, plans, ctx.world, endpoints)
            bufs = {addr: self._buf(addr) for addr in seq.buffer_addrs}
            for addr, need in seq.min_widths().items():
                have = bufs[addr].shape[-1]
                if have < need:
                    raise ValueError(
                        f"sequence needs {need} elements in buffer "
                        f"{addr:#x}, which holds {have}")
            fn = ctx.compiler.compile_sequence(seq)
        # the interference summary rides every prepared program — pure
        # Python over the descriptors (the exact-event thunk defers any
        # tracing to an escalated pair), so extraction is O(steps)
        from ..analysis.interference import footprint_from_steps

        footprint = footprint_from_steps(
            desc.steps, ctx.world,
            persistent=frozenset(persistent),
            use_pallas_ring=ctx.compiler.use_pallas_ring,
            pallas_ring_overlap=ctx.compiler.pallas_ring_overlap,
            plans=tuple(plans), axis_name=self.axis_name,
            signature=sig)
        return _PreparedSequence(desc=desc, plans=tuple(plans), seq=seq,
                                 fn=fn, bufs=bufs, ctx=ctx, sig=sig,
                                 footprint=footprint)

    def dispatch_sequence(self, prepared: "_PreparedSequence") -> BaseRequest:
        """The dispatch half of `start_sequence`: run a prepared batch's
        compiled program over its bound buffers' current device contents
        and place the results. Safe to call repeatedly on one handle —
        each call is an independent request."""
        from ..request import SequenceRequest

        desc, seq, ctx = prepared.desc, prepared.seq, prepared.ctx
        plans, fn, bufs = prepared.plans, prepared.fn, prepared.bufs
        sig = prepared.sig
        tracer = get_tracer()
        with tracer.span("dispatch", cat="phase", track="device") as sp:
            sp.set(signature=sig)
            if prepared.cert is not None:
                # a certify_concurrent-stamped tenant: the flight
                # recorder can name which admitted set this dispatch
                # belonged to when it wedges
                sp.set(interference_cert=prepared.cert)
            call = tracer.current()
            if call is not None:  # the facade's sequence span
                sp.set(call_id=call.args["call_id"])
            args = []
            for addr in seq.buffer_addrs:
                buf = bufs[addr]
                if buf.device is None:  # host-only buffer not yet staged
                    buf.sync_to_device()
                arr = buf.device
                if ctx.rows is None:
                    args.append(arr)
                else:
                    args.append(self._rows_to_submesh(arr, ctx,
                                                      arr.shape[-1]))
            # serialize the launch (see _launch_mu): async dispatch must
            # not let a second tenant's collectives enter the rendezvous
            # before this program's have all arrived, so block inside
            with self._launch_mu:
                t0 = time.perf_counter_ns()
                child = sp.begin("launch") if sp.keep else None
                outs = fn(*args)
                if child:
                    child.end()
                child = sp.begin("wait") if sp.keep else None
                jax.block_until_ready(outs)
                if child:
                    child.end()
                t1 = time.perf_counter_ns()

        out_bufs = [bufs[a] for a in seq.out_addrs]

        def place(req):
            for buf, out in zip(out_bufs, outs):
                if buf.device is None:  # host-only result: materialize
                    buf.sync_to_device()
                if ctx.rows is None:
                    buf.device = self._place(buf.device, out)
                else:
                    buf.device = self._scatter_rows(buf.device, ctx, out)

        req = SequenceRequest(list(outs), list(plans), on_complete=place,
                              duration_ns=t1 - t0)
        # the signature names the program on the request whether or not
        # a tracer is live — telemetry attached later (or a debugger
        # poking a wedged request) must still see which program owns it
        req.signature = sig
        if prepared.cert is not None:
            req.interference_cert = prepared.cert
        if tracer.active:
            # per-step marker spans: the fused program executes the steps
            # inside ONE dispatch, so each step carries its timing.predict
            # estimate (and the batch signature) rather than a host-
            # measured duration — instants, not intervals, honestly.
            # Predictions are a pure function of the frozen (steps,
            # plans), so they are computed once per handle, not per
            # dispatch (the re-resolution cost prepare/dispatch splits
            # out must not sneak back in through telemetry).
            if prepared.preds is None:
                prepared.preds = [self._predict_call(o, p, ctx.world)
                                  for o, p in zip(desc.steps, plans)]
            preds = prepared.preds
            known = [p for p in preds if p is not None]
            req.predicted_s = sum(known) if known else None
            now = time.perf_counter_ns()
            for i, (o, p, pred) in enumerate(zip(desc.steps, plans, preds)):
                step_args = {
                    "op": o.scenario.name,
                    "count": o.count,
                    "step": i,
                    "world": ctx.world,
                    "algorithm": p.algorithm.name,
                    "protocol": p.protocol.name,
                    "signature": sig,
                }
                if pred is not None:
                    step_args["predicted_s"] = pred
                tracer.emit(f"step{i}:{o.scenario.name}", "step", "device",
                            ts_ns=now, dur_ns=0, args=step_args)
        return req

    def _lint_batch(self, desc, plans, ctx, mode: str,
                    persistent: frozenset = frozenset()) -> None:
        """The opt-out static gate in front of compile_sequence: lint
        diagnostics are cached by the batch's composite signature (the
        same canonical renaming the compile cache keys on), so steady
        state pays a dict lookup. Buffer widths come from the registry
        where registered, enabling the static underflow check."""
        from ..analysis.diagnostics import enforce
        from ..analysis.linter import SequenceLinter

        widths = {}
        canon: list[int] = []  # widths in canonical (renamed) order, so
        # the cache can never alias two batches whose buffers differ
        rename: dict[int, int] = {}  # addr -> canonical index, for the
        # persistent-annotation part of the key (addresses are arena-
        # unique, so the raw set would defeat cross-buffer cache hits)
        for opts in desc.steps:
            for addr in (opts.addr_0, opts.addr_1, opts.addr_2):
                if addr and addr not in rename:
                    rename[addr] = len(rename)
                buf = self.buffers.get(addr)
                if addr and buf is not None and addr not in widths:
                    widths[addr] = buf.shape[-1]
                    canon.append(widths[addr])
        deep = mode == "deep"
        canon_persist = tuple(sorted(
            rename[a] for a in persistent if a in rename))
        key = (desc.signature(), plans, ctx.world, tuple(canon),
               ctx.compiler.use_pallas_ring,
               ctx.compiler.pallas_ring_overlap, canon_persist, deep)
        diags = self._lint_cache.get(key)
        if diags is None:
            linter = SequenceLinter(
                ctx.world,
                use_pallas_ring=ctx.compiler.use_pallas_ring,
                pallas_ring_overlap=ctx.compiler.pallas_ring_overlap,
                deep=deep,
                axis_name=self.axis_name,
                # lint against the lanes this device will LOWER with: a
                # custom arith_config's extra rows must not be rejected,
                # and its removed rows must not slip through
                arith_table=ctx.compiler.arith_table,
            )
            diags = tuple(linter.lint(desc.steps, plans,
                                      buffer_widths=widths,
                                      persistent_addrs=persistent))
            self._lint_cache[key] = diags
        enforce(diags, mode)

    # -- send/recv pairing ------------------------------------------------

    def _enqueue_send(self, options: CallOptions) -> BaseRequest:
        """Single-controller pairing: a send parks its descriptor until the
        matching recv arrives, the role the eager rx-ring notification
        queue plays per-rank in the reference (rxbuf_seek.cpp:20-79)."""
        src = options.root_src_dst & 0xFFFF
        dst = (options.root_src_dst >> 16) & 0xFFFF
        # match-or-enqueue is ATOMIC under _recv_mu (which guards BOTH
        # pending maps): otherwise a concurrent recv could scan the send
        # map before this insert while this scan misses its parking —
        # both sides parked, lost wakeup. The claimed recv resolves
        # outside the lock (launch may compile).
        parked = None
        with self._recv_mu:
            while parked is None:
                # oldest-parked-first across ALL matching signatures: a
                # TAG_ANY send must pair with the earliest-arrived recv
                # even when several tag keys match (arrival-order scan,
                # rxbuf_seek.cpp:20-79); per-queue heads are each queue's
                # minimum, so comparing heads finds the global minimum
                best_key = None
                for key, queue in self._pending_recvs.items():
                    ca, s, d, tag = key
                    if ca == options.comm_addr and s == src and d == dst and (
                        tag == options.tag or TAG_ANY in (tag, options.tag)
                    ) and (
                        best_key is None
                        or queue[0]._park_seq
                        < self._pending_recvs[best_key][0]._park_seq
                    ):
                        best_key = key
                if best_key is None:
                    break
                queue = self._pending_recvs[best_key]
                candidate = queue.pop(0)
                if not queue:
                    self._pending_recvs.pop(best_key, None)
                if candidate.claim():  # skip already-timed-out
                    parked = candidate
            if parked is None:
                if self._parked_send_count >= self.MAX_PARKED_SENDS:
                    # park backlog full: fail loudly instead of growing
                    # without bound (reference caps parked notifications
                    # at 512, rxbuf_seek.cpp:47-50)
                    req = BaseRequest("send")
                    req.running()
                    req.complete(int(
                        ErrorCode.DEQUEUE_BUFFER_SPARE_BUFFER_STATUS_ERROR))
                    return req
                self._park_seq += 1
                self._parked_send_count += 1
                self._pending_sends.setdefault(
                    (options.comm_addr, src, dst, options.tag), []
                ).append((self._park_seq, options))
        if parked is not None:
            parked.resolve(self._launch(self._pair(parked.options, options)))
        req = BaseRequest("send")
        req.running()
        req.complete(0)
        return req

    def _pair(self, recv_opts: CallOptions, send_opts: CallOptions) -> CallOptions:
        src = recv_opts.root_src_dst & 0xFFFF
        dst = (recv_opts.root_src_dst >> 16) & 0xFFFF
        # stream endpoints merge from the side that owns them: the send
        # contributes OP0 (its operand may come from a producer kernel,
        # reference accl.hpp:190 stream-send overload), the recv RES (its
        # result may feed a consumer kernel, accl.hpp:278)
        from ..constants import StreamFlags

        flags = StreamFlags.NO_STREAM
        op0_id = res_id = 0
        if send_opts.stream_flags & StreamFlags.OP0_STREAM:
            flags |= StreamFlags.OP0_STREAM
            op0_id = send_opts.op0_stream_id
        if recv_opts.stream_flags & StreamFlags.RES_STREAM:
            flags |= StreamFlags.RES_STREAM
            res_id = recv_opts.res_stream_id
        return CallOptions(
            scenario=Operation.send,
            count=recv_opts.count,
            comm_addr=recv_opts.comm_addr,
            root_src_dst=src | (dst << 16),
            tag=send_opts.tag,
            compression_flags=recv_opts.compression_flags,
            stream_flags=flags,
            op0_stream_id=op0_id,
            res_stream_id=res_id,
            data_type=recv_opts.data_type,
            addr_0=send_opts.addr_0,
            addr_2=recv_opts.addr_2,
        )

    def _match_recv(self, options: CallOptions) -> BaseRequest:
        src = options.root_src_dst & 0xFFFF
        dst = (options.root_src_dst >> 16) & 0xFFFF
        # match-or-park is ATOMIC under _recv_mu, mirroring _enqueue_send:
        # scanning the send map and parking must not interleave with a
        # concurrent send's scan-and-insert (lost wakeup / mutation during
        # iteration)
        with self._recv_mu:
            # oldest-send-first across ALL matching signatures (see
            # _enqueue_send): a TAG_ANY recv drains sends in arrival
            # order even when they parked under different tag keys
            match = None
            for key, queue in self._pending_sends.items():
                ca, s, d, tag = key
                if ca == options.comm_addr and s == src and d == dst and (
                    tag == options.tag or TAG_ANY in (tag, options.tag)
                ) and (
                    match is None
                    or queue[0][0] < self._pending_sends[match][0][0]
                ):
                    match = key
            if match is None:
                # park until the send arrives or the configured timeout
                # lapses (reference: unmatched recvs ride the retry queue
                # until HOUSEKEEP_TIMEOUT, ccl_offload_control.c:2460-2479)
                req = ParkedRecvRequest(options, self.timeout / 1e6)
                self._park_seq += 1
                req._park_seq = self._park_seq
                key = (options.comm_addr, src, dst, options.tag)
                self._pending_recvs.setdefault(key, []).append(req)

                def unpark(_key=key, _req=req):
                    with self._recv_mu:
                        queue = self._pending_recvs.get(_key)
                        if queue is not None:
                            try:
                                queue.remove(_req)  # by identity of self
                            except ValueError:
                                pass
                            if not queue:
                                self._pending_recvs.pop(_key, None)

                req._unpark = unpark
                return req
            queue = self._pending_sends[match]
            _seq, send_opts = queue.pop(0)
            self._parked_send_count -= 1
            if not queue:
                self._pending_sends.pop(match, None)
        return self._launch(self._pair(options, send_opts))

    # -- kernel streams (stream_put flow, vadd_put analog) -----------------

    def stream_put(self, options: CallOptions) -> BaseRequest:
        """Producer -> collective fused in one program: the operand comes
        from the stream producer registered under the descriptor's
        op0_stream_id byte (the reference's strm routing, dma_mover.cpp:497)
        and the payload lands in the destination's result buffer after its
        consumer kernel."""
        from ..ops.streams import splice_consumer, splice_producer
        from ..sequencer import schedules

        sid = options.op0_stream_id
        src = options.root_src_dst & 0xFFFF
        dst = (options.root_src_dst >> 16) & 0xFFFF
        res = self._buf(options.addr_2)
        prod = self.streams.producer(sid)
        cons = self.streams.consumer(sid)
        key = (sid, options.count, options.root_src_dst, options.data_type,
               id(prod), id(cons))
        prog = self._stream_cache.get(key)
        if prog is None:
            import functools

            from jax.sharding import PartitionSpec

            body = functools.partial(
                schedules.sendrecv_schedule,
                src=src,
                dst=dst,
                axis=self.axis_name,
                world=self.world,
                wire=schedules.Wire(None),
            )
            body = splice_producer(body, prod, options.count)
            body = splice_consumer(body, cons)

            def wrapped(x):
                out = body(x.reshape(x.shape[-1]))
                return out.reshape(1, out.shape[-1])

            spec = PartitionSpec(self.axis_name)
            prog = jax.jit(
                jax.shard_map(
                    wrapped, mesh=self.mesh, in_specs=(spec,),
                    out_specs=spec, check_vma=False,
                )
            )
            self._stream_cache[key] = prog
        placeholder = res.device[..., : options.count]
        out = prog(placeholder)

        def place(req):
            res.device = self._place(res.device, out)

        return TPURequest("stream_put", [out], on_complete=place)

    def dump_eager_rx_buffers(self) -> str:
        """The XLA executor's analog of the rx-ring dump
        (accl.cpp:964-1012): this backend has no spare-buffer ring — XLA
        owns the data plane — so the parked recv/send queues (its
        rx-notification parking, rxbuf_seek.cpp role) are the observable
        eager state."""
        with self._recv_mu:
            lines = [
                f"eager rx (XLA executor): buf_size {self.eager_rx_buf_size}"
                f", parked sends {self._parked_send_count}"
                f"/{self.MAX_PARKED_SENDS}"
            ]
            for (ca, s, d, tag), q in sorted(self._pending_recvs.items()):
                for parked in q:
                    lines.append(
                        f"parked recv: comm {ca:#x} src {s} dst {d} "
                        f"tag {tag} seq {parked._park_seq}")
            for (ca, s, d, tag), q in sorted(self._pending_sends.items()):
                for seq, opts in q:
                    lines.append(
                        f"parked send: comm {ca:#x} src {s} dst {d} "
                        f"tag {tag} seq {seq} count {opts.count}")
        return "\n".join(lines)

    def wire_stats(self) -> dict:
        """The stats2 counter surface mirrored onto the XLA tier
        (EmuRank.wire_stats's schema, every field zero): XLA owns this
        backend's data plane — there is no native wire, so there are no
        native wire faults to count — but consumers (telemetry wire-
        health export, the resilience manager's lossy-vs-dark
        classifier) read one stable dict shape across device kinds."""
        from .emu_device import STATS2_FIELDS

        return {name: 0 for name in STATS2_FIELDS}

    # -- config calls (ACCL_CONFIG switch, .c:2416-2452) -------------------

    def _config(self, options: CallOptions) -> BaseRequest:
        req = BaseRequest(f"config/{CfgFunc(options.function).name}")
        req.running()
        fn = CfgFunc(options.function)
        if fn == CfgFunc.reset_periph:
            with self._recv_mu:
                self._pending_sends.clear()
                self._parked_send_count = 0
                queues = [q for q in self._pending_recvs.values()]
                self._pending_recvs.clear()
            for queue in queues:
                for parked in queue:
                    if parked.claim():
                        parked._timeout_fire()
            self.compiler._cache.clear()
            self._predictions.clear()
            self._lint_cache.clear()
            self._comm_cache.clear()
            self._comm_extents.clear()
            self._group_cache.clear()
        elif fn == CfgFunc.enable_pkt:
            self.pkt_enabled = True
        elif fn == CfgFunc.set_timeout:
            self.timeout = options.count
        elif fn == CfgFunc.set_max_eager_msg_size:
            # value arrives in the count field (.c:2432-2439)
            if options.count > self.eager_rx_buf_size:
                req.complete(int(ErrorCode.EAGER_THRESHOLD_INVALID))
                return req
            self.max_eager_size = options.count
        elif fn == CfgFunc.set_max_rendezvous_msg_size:
            self.max_rendezvous_size = options.count
        req.complete(0)
        return req


class _PreparedSequence:
    """A resolved + compiled descriptor batch, ready to dispatch any
    number of times (TPUDevice.prepare_sequence / dispatch_sequence):
    the descriptor batch post wire-register rewrite, its per-step
    plans, the fused SequencePlan, the compiled program, and the bound
    buffer objects (re-read per dispatch, so their current device
    contents flow in)."""

    __slots__ = ("desc", "plans", "seq", "fn", "bufs", "ctx", "sig",
                 "preds", "footprint", "cert")

    def __init__(self, desc, plans, seq, fn, bufs, ctx, sig,
                 footprint=None):
        self.desc = desc
        self.plans = plans
        self.seq = seq
        self.fn = fn
        self.bufs = bufs
        self.ctx = ctx
        self.sig = sig
        # per-step timing.predict estimates, computed lazily on the
        # first traced dispatch and reused (pure function of the frozen
        # steps + plans)
        self.preds = None
        # the cross-program interference summary (analysis/interference
        # ProgramFootprint) and, once ACCL.certify_concurrent admits
        # this program into a pairwise-clean set, the certificate id
        # naming that set — threaded through dispatch spans/requests
        self.footprint = footprint
        self.cert = None


class _CommCtx:
    """Resolved communicator: group size, the mesh it executes on, its
    schedule compiler, and the member rows of full-world buffers (None for
    the default full-axis communicator)."""

    __slots__ = ("world", "mesh", "compiler", "rows", "_member_here")

    def __init__(self, world, mesh, compiler, rows):
        self.world = world
        self.mesh = mesh
        self.compiler = compiler
        self.rows = rows
        self._member_here = None  # lazy per-process membership cache


def _slice_to(arr, n: int):
    return arr if arr.shape[-1] == n else arr[..., :n]


def _place_into(dst, out):
    """Write a program result into a (possibly wider) result buffer."""
    if dst.shape == out.shape:
        return out
    return jax.jit(
        lambda d, o: jax.lax.dynamic_update_slice_in_dim(
            d, o.astype(d.dtype), 0, axis=-1
        )
    )(dst, out)
