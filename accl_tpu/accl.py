"""The ACCL driver facade: the user-facing API of the framework.

Reference: driver/xrt/include/accl.hpp:45-1131 / src/accl.cpp — the
facade owns initialization (buffer rings, communicator, arithmetic
configs, tuning registers), exposes every collective in sync and async
forms with host/device sync control and optional wire compression, and
routes calls to an interchangeable device backend.

TPU shape of the API: one controller drives a communicator whose ranks
are devices on a mesh axis. Buffers are stacked (world, n) arrays
sharded across the axis. `from_device`/`to_device` mirror the
reference's from_fpga/to_fpga: they skip the host<->HBM syncs so chained
collectives stay on-device.
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .arithconfig import DEFAULT_ARITH_CONFIG, validate_arith_config
from .buffers import BaseBuffer, DummyBuffer, TPUBuffer
from .communicator import Communicator, Rank
from .constants import (
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_MAX_RENDEZVOUS_SIZE,
    DEFAULT_NUM_EAGER_RX_BUFS,
    CfgFunc,
    CompressionFlags,
    DataType,
    HostFlags,
    Operation,
    ReduceFunction,
    StreamFlags,
    TAG_ANY,
    TuningParams,
    dtype_nbytes,
    to_numpy_dtype,
)
from .descriptor import CallOptions, normalize_live_ranks
from .device.base import CCLOAddr
from .errors import (
    DtypeMismatchError,
    InvalidRootError,
    SequenceReuseError,
    ZeroLengthBufferError,
)
from .device.tpu_device import TPUDevice
from .request import BaseRequest
from .telemetry import get_tracer
from .utils.logging import Log

if TYPE_CHECKING:
    from .resilience.manager import ResilienceManager


class ACCL:
    """Driver facade over a device backend (reference ACCL class)."""

    def __init__(
        self,
        mesh: Mesh | None = None,
        axis_name: str = "ccl",
        device=None,
        n_egr_rx_bufs: int = DEFAULT_NUM_EAGER_RX_BUFS,
        egr_rx_buf_size: int = DEFAULT_EAGER_RX_BUF_SIZE,
        max_eager_size: int = DEFAULT_MAX_EAGER_SIZE,
        max_rendezvous_size: int = DEFAULT_MAX_RENDEZVOUS_SIZE,
        arith_config: dict | None = None,
    ):
        if device is None:
            if mesh is None:
                raise ValueError("provide a mesh or an explicit device backend")
            device = TPUDevice(mesh, axis_name)
        self.cclo = device
        self.mesh = getattr(device, "mesh", mesh)
        self.axis_name = getattr(device, "axis_name", axis_name)
        self.arith_config = validate_arith_config(arith_config or DEFAULT_ARITH_CONFIG)
        self._config = dict(
            n_egr_rx_bufs=n_egr_rx_bufs,
            egr_rx_buf_size=egr_rx_buf_size,
            max_eager_size=max_eager_size,
            max_rendezvous_size=max_rendezvous_size,
        )
        self.communicators: list[Communicator] = []
        self._initialized = False
        self._last_request: BaseRequest | None = None
        # armed resilience manager (accl_tpu/resilience/): when set,
        # every synchronous data-plane call is checked against its
        # model-derived deadline post-completion (one perf_counter pair
        # + a cached policy lookup; None = zero overhead)
        self._resilience: ResilienceManager | None = None
        # lazily-built cross-program interference certifier (see
        # certify_concurrent): long-lived so its per-pair verdict cache
        # spans admissions of a stable tenant set
        self._interference = None
        # placeholder rank buffers backing the buffer-less stream forms
        # (reference send/recv/copy overloads that take only a dataType,
        # accl.hpp:190,278,349): one per (count, dtype), reused
        self._stream_scratch: dict = {}
        self.initialize()

    # ------------------------------------------------------------------ #
    # bring-up (reference ACCL::initialize, accl.cpp:1066-1114)
    # ------------------------------------------------------------------ #

    def initialize(self):
        if self._initialized:
            raise RuntimeError("ACCL already initialized (CFGRDY set)")
        cfg = self._config
        dev = self.cclo
        # rx-ring + threshold config words (setup_eager_rx_buffers analog,
        # accl.cpp:1131-1172: descriptor table first, count written last).
        dev.write(CCLOAddr.EGR_RX_BUF_SIZE, cfg["egr_rx_buf_size"])
        dev.write(CCLOAddr.NUM_EGR_RX_BUFS, cfg["n_egr_rx_bufs"])
        dev.eager_rx_buf_size = cfg["egr_rx_buf_size"]
        # default communicator over the whole axis; re-initialization
        # invalidates all prior communicator handles (their exchange-memory
        # addresses are reallocated), so the list starts fresh
        self.communicators.clear()
        self._split_cache: dict[tuple[int, ...], Communicator] = {}
        world = dev.world
        ranks = [Rank(device_index=i, session_id=i) for i in range(world)]
        self.communicators.append(Communicator(ranks, 0, CCLOAddr.DYNAMIC_BASE))
        self._write_communicator(self.communicators[0])
        # arithmetic configs -> exchange memory (configure_arithmetic,
        # accl.cpp:1116-1125)
        addr = CCLOAddr.DYNAMIC_BASE + 4 * (2 + world * Communicator.WORDS_PER_RANK)
        for key, ac in self.arith_config.items():
            ac.set_exchmem(addr)
            for i, w in enumerate(ac.exchmem_words()):
                dev.write(addr + 4 * i, w)
            addr += 4 * ac.WORDS_PER_ROW
        # dynamic exchange-memory allocator tail: later communicators
        # (split) are laid out from here
        self._exchmem_alloc: int = addr
        # tuning registers (configure_tuning_parameters, accl.cpp:1198-1208)
        self.configure_tuning_parameters(
            TuningParams.default(cfg["max_rendezvous_size"]))
        # thresholds via config calls (accl.cpp:1096-1109)
        self._config_call(CfgFunc.set_max_eager_msg_size, cfg["max_eager_size"])
        self._config_call(CfgFunc.set_max_rendezvous_msg_size, cfg["max_rendezvous_size"])
        self._config_call(CfgFunc.enable_pkt, 0)
        dev.write(CCLOAddr.CFGRDY, 1)
        self._initialized = True

    def _config_call(self, fn: CfgFunc, value: int):
        req = self.cclo.call(
            CallOptions(scenario=Operation.config, function=int(fn), count=value)
        )
        req.check()

    def deinit(self):
        self._config_call(CfgFunc.reset_periph, 0)
        self.cclo.write(CCLOAddr.CFGRDY, 0)
        self._initialized = False

    def _write_communicator(self, comm: Communicator):
        for i, w in enumerate(comm.exchmem_words()):
            self.cclo.write(comm.exchmem_addr + 4 * i, w)

    # ------------------------------------------------------------------ #
    # buffers
    # ------------------------------------------------------------------ #

    @property
    def world(self) -> int:
        return self.cclo.world

    def _sharding(self):
        return NamedSharding(self.mesh, PartitionSpec(self.axis_name))

    def create_buffer(
        self, count: int, dtype=np.float32, data: np.ndarray | None = None,
        host_only: bool = False,
    ) -> TPUBuffer:
        """Allocate a stacked (world, count) rank buffer in HBM (the
        reference's create_buffer factories, accl.hpp:760-987).
        host_only buffers live in host memory and are staged to HBM around
        each call (the reference's host-only XRTBuffer / OP*_HOST flags)."""
        if isinstance(dtype, DataType):
            dtype = to_numpy_dtype(dtype)
        if data is None:
            data = np.zeros((self.world, count), dtype)
        else:
            # always copy: the buffer owns its memory (reference buffer
            # semantics), and backends may update the host mirror in place
            data = np.array(data, dtype).reshape(self.world, count)
        buf_cls = getattr(self.cclo, "buffer_class", TPUBuffer)
        buf = buf_cls(data, self._sharding(), host_only=host_only)
        self.cclo.register_buffer(buf)
        return buf

    def create_device_buffer(self, array) -> TPUBuffer:
        """A rank buffer over a (world, count) array made on the device:
        row r lands on rank r's device, and no host mirror is built."""
        if getattr(self.cclo, "buffer_class", TPUBuffer) is not TPUBuffer:
            raise NotImplementedError(
                f"{type(self.cclo).__name__} makes no device buffers")
        if array.ndim != 2 or array.shape[0] != self.world:
            raise ValueError(f"need a ({self.world}, count) array, got "
                             f"{tuple(array.shape)}")
        buf = TPUBuffer.on_device(array, self._sharding())
        self.cclo.register_buffer(buf)
        return buf

    def free_buffer(self, buf: BaseBuffer):
        self.cclo.unregister_buffer(buf)

    # ------------------------------------------------------------------ #
    # prepare_call: dtype/compression resolution (accl.cpp:1236-1356)
    # ------------------------------------------------------------------ #

    def _prepare(
        self,
        scenario: Operation,
        op0: BaseBuffer | None,
        op1: BaseBuffer | None,
        res: BaseBuffer | None,
        count: int,
        root_src_dst: int = 0,
        function: int = 0,
        tag: int = TAG_ANY,
        compress_dtype: DataType | None = None,
        comm: Communicator | None = None,
    ) -> CallOptions:
        if comm is None:
            comm = self.communicators[0]
        elif comm not in self.communicators:
            raise ValueError("communicator does not belong to this ACCL")
        # roots and src/dst ranks are communicator-relative; an out-of-range
        # rank would compile a schedule in which nobody is root
        if scenario in (Operation.bcast, Operation.scatter, Operation.gather,
                        Operation.reduce):
            if not 0 <= root_src_dst < comm.size:
                raise InvalidRootError(
                    f"root {root_src_dst} outside communicator of {comm.size}")
        elif scenario in (Operation.send, Operation.recv):
            src, dst = root_src_dst & 0xFFFF, (root_src_dst >> 16) & 0xFFFF
            if src >= comm.size or dst >= comm.size:
                raise InvalidRootError(
                    f"src/dst ({src},{dst}) outside communicator of {comm.size}")
        # a zero-length payload would compile a shape-degenerate schedule
        # and, dispatched device-resident, fail with no host-side symptom
        if count <= 0 and scenario not in (Operation.barrier,
                                           Operation.config, Operation.nop):
            raise ZeroLengthBufferError(
                f"{scenario.name} with count {count}: data-plane calls "
                "need a positive element count")
        dtype = None
        for b in (op0, op1, res):
            if b is not None and not isinstance(b, DummyBuffer):
                if dtype is None:
                    dtype = b.data_type
                elif b.data_type != dtype:
                    raise DtypeMismatchError(
                        "mixed-dtype operands: use compress_dtype for wire "
                        "compression instead"
                    )
        comp = CompressionFlags.NO_COMPRESSION
        host = HostFlags.NO_HOST
        for b, flag in ((op0, HostFlags.OP0_HOST), (op1, HostFlags.OP1_HOST),
                        (res, HostFlags.RES_HOST)):
            if b is not None and getattr(b, "host_only", False):
                host |= flag
        arithcfg_addr = 0
        if dtype is not None:
            pair = (dtype, compress_dtype or dtype)
            if pair not in self.arith_config:
                raise ValueError(f"no arithmetic configuration for {pair}")
            if compress_dtype is not None and compress_dtype != dtype:
                from .ops.compression import is_quantized

                # quantized lanes exist only where the backend ships the
                # blockwise ring kernels (the XLA schedule tier); a
                # lane-less executor would degrade the request to a cast
                # — 2 B/elem on a wire billed at ~1 B — so fail host-side
                if is_quantized(self.arith_config[pair]) and not getattr(
                        self.cclo, "supports_quantized_wire", False):
                    raise NotImplementedError(
                        f"{type(self.cclo).__name__} has no blockwise-"
                        f"quantized wire lanes ({pair[0].name} -> "
                        f"{pair[1].name}); quantized compression is "
                        "XLA-schedule-tier only")
                comp |= CompressionFlags.ETH_COMPRESSED
            arithcfg_addr = self.arith_config[pair].addr()
        return CallOptions(
            scenario=scenario,
            count=count,
            comm_addr=comm.exchmem_addr,
            root_src_dst=root_src_dst,
            function=function,
            tag=tag,
            arithcfg_addr=arithcfg_addr,
            compression_flags=comp,
            stream_flags=StreamFlags.NO_STREAM,
            host_flags=host,
            addr_0=0 if op0 is None else op0.address,
            addr_1=0 if op1 is None else op1.address,
            addr_2=0 if res is None else res.address,
            data_type=dtype or DataType.none,
            compress_dtype=compress_dtype or DataType.none,
        )

    def _stage_in(self, sync_in: list[BaseBuffer], from_device: bool,
                  call=None):
        """Pre-launch host->HBM staging: host-only operands always stage;
        device buffers only when the caller didn't claim from_device
        residence. `call`: the collected call span, which then gets a
        `stage_in` child when something stages."""
        staged = [b for b in sync_in
                  if not from_device or getattr(b, "host_only", False)]
        if not staged:
            return
        sp = (call.begin("stage_in", bytes=sum(b.nbytes for b in staged))
              if call else None)
        for b in staged:
            b.sync_to_device()
        if sp:
            sp.end()

    def _complete(self, req, sync_out: list[BaseBuffer], to_device: bool,
                  run_async: bool, call=None):
        """Post-launch completion contract shared by single calls and
        recorded sequences: async defers sync-out to wait() (host-only
        results still need their copy-back even under to_device), sync
        waits/checks and pulls results (a `stage_out` child of the
        collected call span `call`, when something stages)."""
        self._last_request = req
        if run_async:
            if to_device:
                req._accl_sync_out = [
                    b for b in sync_out if getattr(b, "host_only", False)
                ]
            else:
                req._accl_sync_out = sync_out
            return req
        req.wait()
        req.check()
        staged = [b for b in sync_out
                  if not to_device or getattr(b, "host_only", False)]
        if staged:
            sp = (call.begin("stage_out",
                             bytes=sum(b.nbytes for b in staged))
                  if call else None)
            for b in staged:
                b.sync_from_device()
            if sp:
                sp.end()
        return req

    def _execute(
        self,
        sp,
        opts: CallOptions,
        sync_in: list[BaseBuffer],
        sync_out: list[BaseBuffer],
        from_device: bool,
        to_device: bool,
        run_async: bool,
    ):
        """Stage, start, complete one prepared call inside its call
        span `sp`, which the public method opened at its entry
        (get_tracer().call: the shared no-op when telemetry is off,
        one predicate)."""
        # armed deadlines (resilience seam): time the synchronous call
        # end to end so the manager can check it against its
        # model-derived deadline after completion. async calls complete
        # in wait() where no end-to-end wall time exists host-side.
        mgr = self._resilience
        t0 = (time.perf_counter()
              if mgr is not None and not run_async else None)
        # children (stage_in/out here, plan/lower/launch/wait/place in
        # the device) only while spans are collected
        call = sp if sp.keep else None
        self._stage_in(sync_in, from_device, call)
        Log.debug("call %s count=%d flags=c%x/s%x", opts.scenario.name,
                  opts.count, int(opts.compression_flags),
                  int(opts.stream_flags))
        req = self.cclo.start(opts)
        ret = self._complete(req, sync_out, to_device, run_async, call)
        if mgr is not None and t0 is not None:
            mgr.observe_call(opts.scenario, opts.count,
                             dtype_nbytes(opts.data_type)
                             if opts.data_type != DataType.none else 4,
                             time.perf_counter() - t0)
        if sp:  # a live span: attach what the device resolved
            sp.set(op=opts.scenario.name, count=opts.count,
                   retcode=req.retcode)
            if run_async:
                sp.set(dispatch_only=True)
            plan = getattr(req, "plan", None)
            if plan is not None:
                sp.set(algorithm=plan.algorithm.name,
                       protocol=plan.protocol.name)
            pred = getattr(req, "predicted_s", None)
            if pred is not None:
                sp.set(predicted_s=pred)
        return ret

    def wait(self, req: BaseRequest):
        """Complete an async request (sync-out deferred at start time)."""
        try:
            req.wait()
            req.check()
            for b in getattr(req, "_accl_sync_out", []):
                b.sync_from_device()
        finally:
            # release the private placeholder a run_async stream form rode
            # (fresh _scratch) even when check() raises on a failed op:
            # it was registered like any user buffer and would otherwise
            # leak one (world, count) array per failed async call
            sc = getattr(req, "_accl_scratch", None)
            if sc is not None:
                self.free_buffer(sc)
                req._accl_scratch = None
        return req

    def get_duration_ns(self, req: BaseRequest | None = None) -> int:
        req = req or self._last_request
        return 0 if req is None else req.get_duration_ns()

    # ------------------------------------------------------------------ #
    # primitives & collectives (reference accl.cpp:122-944)
    # ------------------------------------------------------------------ #

    def nop(self):
        return self.cclo.call(CallOptions(scenario=Operation.nop))

    # Every data-plane method opens its call span at entry, so building
    # the descriptor (_prepare, _live_subset, _stream_opts) falls inside.

    def copy(self, srcbuf, dstbuf, count, *, from_device=False, to_device=False,
             run_async=False):
        with get_tracer().call("copy") as sp:
            opts = self._prepare(Operation.copy, srcbuf, None, dstbuf, count)
            return self._execute(sp, opts, [srcbuf], [dstbuf], from_device,
                                 to_device, run_async)

    def _scratch(self, count, dtype, fresh=False):
        """Internal placeholder buffer for a buffer-less stream endpoint
        (the dataType-only overloads of the reference driver). The cache is
        keyed by (count, dtype), so two in-flight calls of the same shape
        would DMA through the same placeholder — callers with run_async
        pass fresh=True to get a private buffer instead of the cached one."""
        if isinstance(dtype, DataType):
            dtype = to_numpy_dtype(dtype)
        if fresh:
            return self.create_buffer(count, dtype)
        key = (int(count), str(np.dtype(dtype)))
        buf = self._stream_scratch.get(key)
        if buf is None:
            buf = self.create_buffer(count, dtype)
            self._stream_scratch[key] = buf
        return buf

    def copy_from_stream(self, dstbuf, count, *, op0_stream, to_device=False,
                         run_async=False):
        """Operand arrives from a registered producer stream, result lands
        in dstbuf (reference copy_from_stream, accl.hpp:317)."""
        with get_tracer().call("copy") as sp:
            opts = self._prepare(Operation.copy, dstbuf, None, dstbuf, count)
            self._stream_opts(opts, op0_stream, None)
            return self._execute(sp, opts, [dstbuf], [dstbuf], True,
                                 to_device, run_async)

    def copy_to_stream(self, srcbuf, count, *, res_stream, dstbuf=None,
                       from_device=False, to_device=False,
                       run_async=False):
        """srcbuf routes through a registered consumer stream (reference
        copy_to_stream, accl.hpp:334). The consumer's return value
        materializes into dstbuf when given (the observable form; the
        reference's PL-kernel sink has no host-visible landing spot),
        else into an internal placeholder. `to_device=True` skips the
        device->host result sync even with a dstbuf — the chained
        on-device form (the eager train-step twin keeps its gradient
        intermediate resident between stages)."""
        with get_tracer().call("copy") as sp:
            fresh = dstbuf is None and run_async
            dst = dstbuf if dstbuf is not None else self._scratch(
                count, srcbuf.np_dtype, fresh=run_async)
            opts = self._prepare(Operation.copy, srcbuf, None, dst, count)
            self._stream_opts(opts, None, res_stream)
            # to_device=True (skip the device->host result sync) for the
            # unobserved internal placeholder, or on caller request
            req = self._execute(sp, opts, [srcbuf], [dst], from_device,
                                to_device or dstbuf is None, run_async)
            if fresh:
                req._accl_scratch = dst
            return req

    def copy_from_to_stream(self, data_type, count, *, op0_stream, res_stream,
                            dstbuf=None, run_async=False):
        """Producer stream -> consumer stream, no host buffers (reference
        copy_from_to_stream, accl.hpp:349); dstbuf optionally captures the
        consumer output."""
        with get_tracer().call("copy") as sp:
            scratch = self._scratch(count, data_type, fresh=run_async)
            dst = dstbuf if dstbuf is not None else scratch
            opts = self._prepare(Operation.copy, scratch, None, dst, count)
            self._stream_opts(opts, op0_stream, res_stream)
            req = self._execute(sp, opts, [scratch], [dst], True,
                                dstbuf is None, run_async)
            if run_async:
                req._accl_scratch = scratch
            return req

    def combine(self, count, function, op0, op1, res, *, from_device=False,
                to_device=False, run_async=False):
        with get_tracer().call("combine") as sp:
            opts = self._prepare(Operation.combine, op0, op1, res, count,
                                 function=int(function))
            return self._execute(sp, opts, [op0, op1], [res], from_device,
                                 to_device, run_async)

    def send(self, srcbuf, count, src, dst, tag=TAG_ANY, *, from_device=False,
             run_async=False, compress_dtype=None, comm=None,
             op0_stream=None):
        """srcbuf may be a DataType when op0_stream is set (the reference's
        stream-send overload, accl.hpp:190: the payload comes from the
        producer kernel, not a buffer)."""
        with get_tracer().call("send") as sp:
            fresh = False
            if isinstance(srcbuf, DataType):
                if op0_stream is None:
                    raise ValueError("dataType-only send requires op0_stream")
                srcbuf = self._scratch(count, srcbuf, fresh=run_async)
                from_device = True
                fresh = run_async
            opts = self._prepare(Operation.send, srcbuf, None, None, count,
                                 root_src_dst=src | (dst << 16), tag=tag,
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, None)
            req = self._execute(sp, opts, [srcbuf], [], from_device, True,
                                run_async)
            if fresh:
                req._accl_scratch = srcbuf
            return req

    def recv(self, dstbuf, count, src, dst, tag=TAG_ANY, *, to_device=False,
             run_async=False, compress_dtype=None, comm=None,
             res_stream=None):
        """dstbuf may be a DataType when res_stream is set (the reference's
        stream-recv overload, accl.hpp:278: the payload feeds the consumer
        kernel; pass a real buffer to also capture the consumer output)."""
        with get_tracer().call("recv") as sp:
            fresh = False
            if isinstance(dstbuf, DataType):
                if res_stream is None:
                    raise ValueError("dataType-only recv requires res_stream")
                dstbuf = self._scratch(count, dstbuf, fresh=run_async)
                to_device = True  # nothing observes the placeholder
                fresh = run_async
            opts = self._prepare(Operation.recv, None, None, dstbuf, count,
                                 root_src_dst=src | (dst << 16), tag=tag,
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, None, res_stream)
            req = self._execute(sp, opts, [], [dstbuf], True, to_device,
                                run_async)
            if fresh:
                req._accl_scratch = dstbuf
            return req

    def _stream_opts(self, opts, op0_stream, res_stream):
        """Arm OP0_STREAM/RES_STREAM on a prepared descriptor (reference:
        streams route through any collective, ccl_offload_control.c:628-636).
        Stream ids ride dedicated descriptor bytes (word 8), leaving the
        tag free for matching."""
        if op0_stream is None and res_stream is None:
            return opts
        if not hasattr(self.cclo, "streams"):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} does not support streamed "
                "collectives")
        from .ops.streams import check_stream_id

        flags = StreamFlags.NO_STREAM
        if op0_stream is not None:
            flags |= StreamFlags.OP0_STREAM
            opts.op0_stream_id = check_stream_id(op0_stream)
        if res_stream is not None:
            flags |= StreamFlags.RES_STREAM
            opts.res_stream_id = check_stream_id(res_stream)
        opts.stream_flags = flags
        return opts

    def bcast(self, buf, count, root, *, from_device=False, to_device=False,
              run_async=False, compress_dtype=None, comm=None,
              op0_stream=None, res_stream=None):
        with get_tracer().call("bcast") as sp:
            opts = self._prepare(Operation.bcast, buf, None, buf, count,
                                 root_src_dst=root,
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [buf], [buf], from_device,
                                 to_device, run_async)

    def scatter(self, sendbuf, recvbuf, count, root, *, from_device=False,
                to_device=False, run_async=False, compress_dtype=None,
                comm=None, op0_stream=None, res_stream=None):
        with get_tracer().call("scatter") as sp:
            opts = self._prepare(Operation.scatter, sendbuf, None, recvbuf,
                                 count, root_src_dst=root,
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def gather(self, sendbuf, recvbuf, count, root, *, from_device=False,
               to_device=False, run_async=False, compress_dtype=None,
               comm=None, op0_stream=None, res_stream=None):
        with get_tracer().call("gather") as sp:
            opts = self._prepare(Operation.gather, sendbuf, None, recvbuf,
                                 count, root_src_dst=root,
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def allgather(self, sendbuf, recvbuf, count, *, from_device=False,
                  to_device=False, run_async=False, compress_dtype=None,
                  comm=None, op0_stream=None, res_stream=None):
        with get_tracer().call("allgather") as sp:
            opts = self._prepare(Operation.allgather, sendbuf, None, recvbuf,
                                 count, compress_dtype=compress_dtype,
                                 comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def reduce(self, sendbuf, recvbuf, count, root, function, *,
               from_device=False, to_device=False, run_async=False,
               compress_dtype=None, comm=None, op0_stream=None,
               res_stream=None):
        with get_tracer().call("reduce") as sp:
            opts = self._prepare(Operation.reduce, sendbuf, None, recvbuf,
                                 count, root_src_dst=root,
                                 function=int(function),
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def allreduce(self, sendbuf, recvbuf, count, function, *,
                  from_device=False, to_device=False, run_async=False,
                  compress_dtype=None, comm=None,
                  op0_stream=None, res_stream=None,
                  mode="all", live_ranks=None):
        """`mode="live_subset"` is the CERTIFIED degraded form
        (docs/resilience.md): `live_ranks` declares the
        surviving-contributor set, every other rank's operand is masked
        to exact zeros at the source inside the schedule, and the
        semantic certifier proves the answer sums exactly the declared
        survivors (the alltoallv drop-to-zeros posture generalized to
        the reduction — a dead rank's stale buffer can never leak a
        ghost contribution). SUM only, exact wire only. A full
        survivor set normalizes to the ordinary allreduce bit-for-bit
        (one compiled program, like the all-full alltoallv vector)."""
        with get_tracer().call("allreduce") as sp:
            opts = self._prepare(Operation.allreduce, sendbuf, None, recvbuf,
                                 count, function=int(function),
                                 compress_dtype=compress_dtype, comm=comm)
            opts.live_ranks = self._live_subset(mode, live_ranks, function,
                                                compress_dtype, comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def _live_subset(self, mode, live_ranks, function, compress_dtype,
                     comm) -> tuple:
        """Validate the degraded-mode arguments at the host seam (the
        _prepare posture: a bad survivor set fails before anything
        compiles or dispatches). Returns the normalized live_ranks
        tuple for the descriptor — () for the ordinary collective."""
        if mode not in ("all", "live_subset"):
            raise ValueError(
                f"allreduce mode must be 'all'|'live_subset', got {mode!r}")
        if mode == "all":
            if live_ranks is not None:
                raise ValueError(
                    "live_ranks requires mode='live_subset'")
            return ()
        if not live_ranks:
            raise ValueError(
                "mode='live_subset' needs a non-empty live_ranks set")
        comm_size = (comm or self.communicators[0]).size
        lr = normalize_live_ranks(live_ranks, comm_size)
        if ReduceFunction(function) != ReduceFunction.SUM:
            raise ValueError(
                "live-subset allreduce is SUM-only: the zero mask is "
                "the fold identity for SUM, nothing else is certified")
        if compress_dtype is not None:
            raise NotImplementedError(
                "live-subset allreduce is exact-wire only")
        if lr == tuple(range(comm_size)):
            # every rank lives: the ordinary allreduce, shared program
            return ()
        if not getattr(self.cclo, "supports_live_subset", False):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} has no masked live-subset "
                "ring; degraded allreduce is XLA-schedule-tier only")
        return lr

    def reduce_scatter(self, sendbuf, recvbuf, count, function, *,
                       from_device=False, to_device=False, run_async=False,
                       compress_dtype=None, comm=None, op0_stream=None,
                       res_stream=None):
        with get_tracer().call("reduce_scatter") as sp:
            opts = self._prepare(Operation.reduce_scatter, sendbuf, None,
                                 recvbuf, count, function=int(function),
                                 compress_dtype=compress_dtype, comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def alltoall(self, sendbuf, recvbuf, count, *, from_device=False,
                 to_device=False, run_async=False, compress_dtype=None,
                 comm=None, op0_stream=None, res_stream=None):
        with get_tracer().call("alltoall") as sp:
            opts = self._prepare(Operation.alltoall, sendbuf, None, recvbuf,
                                 count, compress_dtype=compress_dtype,
                                 comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def alltoallv(self, sendbuf, recvbuf, count, send_counts, *,
                  from_device=False, to_device=False, run_async=False,
                  compress_dtype=None, comm=None, op0_stream=None,
                  res_stream=None):
        """Capacity-bounded all-to-all: the buffer keeps alltoall's
        uniform world-slot layout (`count` elements per peer slot), but
        peer p receives only the first `send_counts[p]` elements of each
        source's slot p — the per-peer capacity (the MoE dispatch's
        expert capacity) — and the overflow tail is dropped to zeros ON
        THE WIRE (schedules.alltoallv_schedule; each hop moves
        max(send_counts) elements, so an under-capacity exchange ships
        fewer bytes than the dense one). An all-`count` vector is the
        dense alltoall, bit-for-bit. XLA-schedule-tier only: executors
        without the capacity-masked rotation reject up front."""
        # named by the descriptor's op, which alltoallv shares
        with get_tracer().call("alltoall") as sp:
            opts = self._prepare_alltoallv(sendbuf, recvbuf, count,
                                           send_counts,
                                           compress_dtype=compress_dtype,
                                           comm=comm)
            self._stream_opts(opts, op0_stream, res_stream)
            return self._execute(sp, opts, [sendbuf], [recvbuf], from_device,
                                 to_device, run_async)

    def _prepare_alltoallv(self, sendbuf, recvbuf, count, send_counts, *,
                           compress_dtype=None, comm=None) -> CallOptions:
        """The alltoallv descriptor: a dense-alltoall descriptor plus the
        static per-peer capacity vector (validated here, the host seam,
        so a bad vector fails before anything compiles or dispatches)."""
        comm_size = (comm or self.communicators[0]).size
        pc = tuple(int(c) for c in send_counts)
        if len(pc) != comm_size:
            raise ValueError(
                f"alltoallv needs one send count per rank: got {len(pc)} "
                f"for communicator of {comm_size}")
        if any(c <= 0 for c in pc):
            raise ZeroLengthBufferError(
                f"alltoallv send counts {pc} include a non-positive "
                "capacity; every peer needs a positive valid prefix")
        if any(c > count for c in pc):
            raise ValueError(
                f"alltoallv send counts {pc} exceed the {count}-element "
                "peer slot")
        if all(c == count for c in pc):
            # an all-full vector IS the dense alltoall: normalize at the
            # descriptor seam too (not just in select_algorithm), so the
            # signature — and with it the compiled program — is SHARED
            # with the plain alltoall at the same shape
            pc = ()
        if pc and not getattr(self.cclo, "supports_alltoallv", False):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} has no capacity-masked "
                "alltoallv rotation; alltoallv is XLA-schedule-tier only")
        opts = self._prepare(Operation.alltoall, sendbuf, None, recvbuf,
                             count, compress_dtype=compress_dtype, comm=comm)
        opts.peer_counts = pc
        return opts

    # ------------------------------------------------------------------ #
    # call sequences: record a batch, dispatch ONE fused program
    # ------------------------------------------------------------------ #

    def sequence(self, comm: Communicator | None = None,
                 lint: str = "error",
                 persistent=()) -> "SequenceRecorder":
        """Start recording a call sequence: collective/copy/combine calls
        on the returned recorder queue descriptors host-side (nothing
        executes), then `run()` lowers the WHOLE batch into one compiled
        device program — a single dispatch, intermediates threaded
        on-device between stages, stream endpoints spliced at the seams.
        Usable as a context manager (the batch runs on clean exit)::

            with accl.sequence() as seq:
                seq.reduce_scatter(a, b, n, ReduceFunction.SUM)
                seq.allgather(b, c, n)
            # one dispatch happened; results are in b and c

        Results are bitwise-identical to issuing the same calls eagerly
        back to back (the cross-executor fuzz pins this).

        `lint` runs the batch through the static analyzer
        (accl_tpu/analysis/, docs/lint.md) before it compiles:
        "error" (default) raises errors.LintError on hazardous batches,
        "warn" logs the diagnostics and proceeds, "off" opts out, and
        "deep" adds the exhaustive-interleaving tier (wildcard races
        and schedule-dependent deadlocks over every legal match order,
        ACCL205/206 — budgeted, enforced like "error").

        `persistent` declares DEVICE-RESIDENT STATE buffers: buffers
        whose tails carry results from one dispatch of the compiled
        program to the next (a KV cache, an optimizer state), refreshed
        partial-width inside the batch by design. The hazard pass
        waives ACCL101 (read wider than the in-sequence producer wrote)
        for exactly those buffers — every other diagnostic, including
        WAR/WAW ordering and the static width check, still applies."""
        if lint not in ("error", "warn", "off", "deep"):
            raise ValueError(
                f"lint must be 'error'|'warn'|'off'|'deep', got {lint!r}")
        if not hasattr(self.cclo, "start_sequence"):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} does not support call "
                "sequences")
        return SequenceRecorder(self, comm, lint=lint,
                                persistent=persistent)

    def certify_concurrent(self, programs, mode: str = "error"):
        """Prove a set of compiled SequencePrograms safe to dispatch
        CONCURRENTLY: pairwise non-interference over their footprint
        summaries (O(N^2) dict-sized checks), escalating a pair to the
        bounded cross-program product model check only when its
        summaries overlap (analysis/interference.py, ACCL601-604).

        A clean verdict means any interleaving of the set is equivalent
        to its serial composition — the admission criterion the
        multi-tenant sequencer (ROADMAP item 1) checks certificates
        against. On success every program is stamped with the set's
        certificate id (`SequenceProgram.certificate`), which then
        rides its dispatch spans so the flight recorder can name the
        admitted set a wedged dispatch belonged to.

        `programs` may mix SequenceProgram handles and raw
        ProgramFootprint summaries (a remote tenant's shipped
        footprint). `mode` follows the lint gate: "error" raises
        LintError on findings, "warn" logs them, "off" skips
        enforcement; all modes return the diagnostic list. Verdicts are
        cached per pair on this ACCL, keyed by the two composite
        signatures."""
        from .analysis.diagnostics import enforce
        from .analysis.interference import (InterferenceCertifier,
                                            ProgramFootprint,
                                            certificate_id)

        if self._interference is None:
            self._interference = InterferenceCertifier()
        footprints = []
        handles = []
        for p in programs:
            if isinstance(p, ProgramFootprint):
                footprints.append(p)
                continue
            fp = getattr(p, "footprint", None)
            if fp is None:
                raise ValueError(
                    f"{type(p).__name__} carries no interference "
                    "footprint (pass SequenceProgram handles or "
                    "ProgramFootprint summaries)")
            footprints.append(fp)
            handles.append(p)
        diags = self._interference.certify(footprints)
        if not diags:
            cert = certificate_id(footprints)
            for h in handles:
                h._prepared.cert = cert
        enforce(diags, mode)
        return diags

    def scheduler(self, **kwargs) -> "MultiTenantScheduler":
        """Build a multi-tenant scheduler over this facade
        (scheduler/MultiTenantScheduler, docs/scheduler.md): admission
        control with live interference certificates (the scheduler
        shares THIS facade's long-lived certifier, so verdicts cached
        by certify_concurrent serve admission and vice versa), strict
        priority classes with weighted fair queueing over predicted
        cost, typed backpressure, and per-tenant accountability
        through the metrics registry. Kwargs forward to the
        MultiTenantScheduler constructor (capacity_s, registry, ...)."""
        from .scheduler import MultiTenantScheduler

        return MultiTenantScheduler(self, **kwargs)

    def split(self, rank_indices: list[int]) -> Communicator:
        """Create a sub-communicator over a subset of ranks (reference
        multi-communicator support: the firmware caches the addressed
        communicator per call from the descriptor's comm_addr,
        ccl_offload_control.c:2317-2372). The new communicator's rank
        table is written to exchange memory and its handle can be passed
        as `comm=` to any collective — no new ACCL, no new device, no new
        compile caches. Buffers stay full-world stacked arrays; a
        sub-communicator collective touches only its member rows."""
        if not getattr(self.cclo, "supports_split", True):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} does not support "
                "sub-communicators yet")
        if len(set(rank_indices)) != len(rank_indices):
            raise ValueError("duplicate ranks in split")
        if not all(0 <= r < self.world for r in rank_indices):
            raise ValueError(f"split ranks outside world of {self.world}")
        # repeated splits of the same member list reuse the existing table
        # (the allocator only grows; the device-side group cache already
        # dedups the execution context, so a fresh table would only burn
        # exchange memory)
        cached = self._split_cache.get(tuple(rank_indices))
        if cached is not None and cached in self.communicators:
            return cached
        import dataclasses

        parent = self.communicators[0].ranks
        # backend topology constraints fail HERE, before any exchange
        # memory is allocated for the group
        validate = getattr(self.cclo, "validate_split", None)
        if validate is not None:
            validate(tuple(parent[r].device_index for r in rank_indices))
        ranks = [
            dataclasses.replace(parent[r], inbound_seq=0, outbound_seq=0)
            for r in rank_indices
        ]
        nwords = 2 + len(ranks) * Communicator.WORDS_PER_RANK
        if self._exchmem_alloc + 4 * nwords > CCLOAddr.DYNAMIC_END:
            raise MemoryError("exchange memory exhausted by communicators")
        comm = Communicator(ranks, 0, self._exchmem_alloc)
        self._exchmem_alloc += 4 * nwords
        self.communicators.append(comm)
        self._write_communicator(comm)
        self._split_cache[tuple(rank_indices)] = comm
        return comm

    def register_stream_producer(self, stream_id: int, fn):
        """Attach a device-side producer to a kernel stream (the PL
        kernel's data_to_cclo port, accl_hls.h ACCLData)."""
        self.cclo.streams.register_producer(stream_id, fn)

    def register_stream_consumer(self, stream_id: int, fn, operands=()):
        """Attach a device-side consumer to a kernel stream (the PL
        kernel's data_from_cclo port). `operands` are buffers the
        consumer reads, as a kernel owns its memory ports: `fn(x, *rows)`
        receives each operand's own rank row inside the compiled
        program, where they are arguments, never constants."""
        if operands and not hasattr(self.cclo, "prepare_sequence"):
            raise NotImplementedError(
                f"{type(self.cclo).__name__} binds no consumer operands")
        self.cclo.streams.register_consumer(stream_id, fn, operands)

    def stream_put(self, count, stream_id, src, dst, recvbuf, *,
                   dtype=DataType.float32, run_async=False):
        """Device-autonomous send: the payload is produced on-device by
        the registered stream producer and lands in recvbuf at dst after
        dst's consumer kernel — no host data path (reference stream_put
        flow, SURVEY.md §3.4 / vadd_put.cpp:55-72)."""
        opts = CallOptions(
            scenario=Operation.send,
            count=count,
            root_src_dst=src | (dst << 16),
            op0_stream_id=stream_id,
            stream_flags=StreamFlags.OP0_STREAM,
            data_type=dtype,
            addr_2=recvbuf.address,
        )
        req = self.cclo.stream_put(opts)
        self._last_request = req
        if run_async:
            req._accl_sync_out = [recvbuf]
            return req
        req.wait()
        req.check()
        recvbuf.sync_from_device()
        return req

    def barrier(self, comm=None):
        opts = self._prepare(Operation.barrier, None, None, None, 0, comm=comm)
        req = self.cclo.start(opts)
        req.wait()
        req.check()
        return req

    # ------------------------------------------------------------------ #
    # housekeeping / observability
    # ------------------------------------------------------------------ #

    def set_timeout(self, value: int):
        self._config_call(CfgFunc.set_timeout, value)

    def set_max_eager_size(self, value: int):
        self._config_call(CfgFunc.set_max_eager_msg_size, value)

    def set_max_rendezvous_size(self, value: int):
        self._config_call(CfgFunc.set_max_rendezvous_msg_size, value)

    def dump_exchange_memory(self) -> str:
        return self.cclo.dump_exchange_memory()

    def dump_communicator(self, index: int = 0) -> str:
        return self.communicators[index].dump()

    def dump_eager_rx_buffers(self) -> str:
        """Snapshot of the eager rx machinery (reference
        dump_eager_rx_buffers, accl.cpp:964-1012): the native executor
        reports its rx ring slot-by-slot; the XLA executor reports its
        parked recv/send queues (the rx-notification parking that plays
        the ring's role there)."""
        return self.cclo.dump_eager_rx_buffers()

    def configure_tuning_parameters(self, tuning: TuningParams):
        """Write the algorithm-tuning registers (the reference's six
        plus the three synthesized-schedule crossovers) to exchange memory
        (reference configure_tuning_parameters, accl.cpp:1198-1208); both
        executors read them per call."""
        dev = self.cclo
        dev.write(CCLOAddr.GATHER_FLAT_TREE_MAX_FANIN,
                  tuning.gather_flat_tree_max_fanin)
        dev.write(CCLOAddr.GATHER_FLAT_TREE_MAX_COUNT,
                  tuning.gather_flat_tree_max_count)
        dev.write(CCLOAddr.BCAST_FLAT_TREE_MAX_RANKS,
                  tuning.bcast_flat_tree_max_ranks)
        dev.write(CCLOAddr.REDUCE_FLAT_TREE_MAX_RANKS,
                  tuning.reduce_flat_tree_max_ranks)
        dev.write(CCLOAddr.REDUCE_FLAT_TREE_MAX_COUNT,
                  tuning.reduce_flat_tree_max_count)
        dev.write(CCLOAddr.ALLREDUCE_COMPOSITION_MAX_COUNT,
                  tuning.allreduce_composition_max_count)
        dev.write(CCLOAddr.SYNTH_ALLREDUCE_MAX_COUNT,
                  tuning.synth_allreduce_max_count)
        dev.write(CCLOAddr.SYNTH_ALLGATHER_MAX_COUNT,
                  tuning.synth_allgather_max_count)
        dev.write(CCLOAddr.SYNTH_REDUCE_SCATTER_MAX_COUNT,
                  tuning.synth_reduce_scatter_max_count)
        dev.write(CCLOAddr.HIER_ALLREDUCE_MIN_COUNT,
                  tuning.hier_allreduce_min_count)
        dev.write(CCLOAddr.ALLTOALL_COMPRESS_MIN_COUNT,
                  tuning.alltoall_compress_min_count)
        dev.write(CCLOAddr.OVERLAP_MIN_COUNT, tuning.overlap_min_count)
        dev.write(CCLOAddr.SYNTH_LATENCY_MAX_COUNT,
                  tuning.synth_latency_max_count)

    def autotune(self, link=None, timing_model_path=None,
                 tier: str = "emulator",
                 wire_dtype: DataType = DataType.none,
                 tier_links=None, compute_fit=None) -> TuningParams:
        """Derive the switch-point tuning registers — the reference's
        four, the synth windows, and (on a device that declares a
        two-tier topology) HIER_ALLREDUCE_MIN_COUNT — from the
        calibrated timing model and apply them (gather fan-in keeps its
        structural default): the measured-performance closure of the
        reference's hand-picked defaults. When the hierarchical window
        opens, the device's per-tier wire dtypes (`hier_wires`) are
        also set from `plan.select_tier_wires` under the same per-tier
        calibration (the int8-on-DCN / fp32-on-ICI arbitration), so
        subsequent fp32 allreduces in the window ship the arbitrated
        wires. `link` is a
        sequencer.timing.LinkParams; absent, it is loaded from
        `timing_model_path` (default accl_log/timing_model.json, written
        by tools/timing_model.py). tier="tpu" uses the on-chip
        calibration tier instead of the emulator link fit (dispatch alpha
        + HBM-bounded beta — a projection until ICI is measured on a
        multi-chip slice); the shipped model has none until a chip run
        refits it, and tier="tpu" then raises ValueError. `wire_dtype` tunes for a workload running
        that compression lane on its collectives (e.g. DataType.int8 for
        the blockwise-quantized wire): crossover arithmetic happens in
        wire bytes, so byte-threshold registers stretch by the
        compression ratio — the registers MOVE when quantized lanes are
        enabled. Returns the applied TuningParams."""
        from .sequencer.timing import (
            LinkParams,
            emulator_link,
            tuning_crossovers,
        )

        if tier not in ("emulator", "tpu"):
            raise ValueError(f"unknown autotune tier {tier!r}")
        if link is not None and tier != "emulator":
            raise ValueError("pass either link= or tier=, not both")
        if link is None:
            import json
            import pathlib

            path = pathlib.Path(
                timing_model_path
                or pathlib.Path(__file__).parent.parent
                / "accl_log" / "timing_model.json")
            model = json.loads(path.read_text())
            if tier == "tpu":
                t = model.get("tpu_tier")
                if not t or not t.get("hbm_stream_gbps"):
                    raise ValueError(
                        "timing model has no usable tpu_tier; re-run "
                        "tools/timing_model.py with an on-chip profile")
                link = LinkParams(alpha=t["dispatch_alpha_us"] * 1e-6,
                                  beta=t["hbm_stream_gbps"] * 1e9)
            else:
                link = emulator_link(model)
        # Per-tier crossover: with a per-tier calibration (passed in, or
        # the shipped link_tiers fit) AND a device that declares a
        # two-tier topology, the hierarchical-allreduce register moves
        # to the predicted hier-beats-flat window; otherwise it stays 0
        # (off) and selection is unchanged.
        topology = getattr(self.cclo, "hier_topology", None)
        if tier_links is None:
            from .telemetry.feedback import default_tier_links

            tier_links = default_tier_links(timing_model_path)
        # the overlap register needs a measured compute term next to
        # the link fit (timing.ComputeFit); absent one the crossover
        # stays 0 and streamed-allreduce selection is untouched
        if compute_fit is None:
            from .telemetry.feedback import default_compute_fit

            compute_fit = default_compute_fit(timing_model_path)
        cross = tuning_crossovers(link, world=self.world,
                                  wire_dtype=wire_dtype,
                                  tier_links=tier_links,
                                  topology=topology,
                                  compute_fit=compute_fit)
        tuning = TuningParams.from_crossovers(cross)
        self.configure_tuning_parameters(tuning)
        # per-tier wire arbitration rides the same tune: with the
        # window open, arbitrate each tier's wire at a clearly
        # bandwidth-bound payload (>= 1 MiB, never below the window
        # floor — the floor itself can sit in the latency regime where
        # no compression clears the min-gain bar) for the canonical
        # fp32 payload; _resolve_step applies these only to fp32
        # calls, the dtype they were arbitrated for
        if (tuning.hier_allreduce_min_count > 0 and topology is not None
                and tier_links is not None
                and hasattr(self.cclo, "hier_wires")):
            from .sequencer.plan import select_tier_wires

            cnt = max(tuning.hier_allreduce_min_count, 1 << 20) // 4
            self.cclo.hier_wires = select_tier_wires(
                cnt, DataType.float32, topology, tier_links,
                arith_table=self.arith_config,
                quantized_ok=getattr(self.cclo,
                                     "supports_quantized_wire", False))
        return tuning

    def arm_resilience(self, manager: ResilienceManager | None) -> None:
        """Arm per-call deadlines on this facade
        (resilience.ResilienceManager with a DeadlinePolicy): every
        synchronous data-plane call is checked against its
        model-derived deadline after completion — a miss produces the
        structured DeadlineMissed verdict (flight-recorder post-mortem
        attached) on the manager, it never fails the completed call.
        Disarm with ``arm_resilience(None)``; disarmed cost is one
        attribute check per call (the no-fault control run is pinned
        bit-for-bit identical with the seam armed)."""
        self._resilience = manager

    def soft_reset(self):
        """reset_periph config call (reference soft_reset, accl.cpp:57-69):
        drains parked/pending call state and compiled-schedule caches but
        leaves the device configured (unlike deinit, which also clears
        CFGRDY)."""
        self._config_call(CfgFunc.reset_periph, 0)
        # the compiled-schedule caches are gone: an armed resilience
        # manager must re-exempt every shape's next (recompiling)
        # dispatch, or the compile time reads as a deadline miss
        if self._resilience is not None:
            self._resilience.reset_warmup()

    def get_comm_group(self, comm: Communicator | None = None) -> list[Rank]:
        """Round-trip the communicator's rank table from exchange memory
        (reference get_comm_group, accl.hpp readback path): returns what
        the DEVICE holds, not the facade's cached object, so drift between
        the two is observable."""
        comm = comm or self.communicators[0]
        n_words = 2 + Communicator.WORDS_PER_RANK * comm.size
        words = [self.cclo.read(comm.exchmem_addr + 4 * i)
                 for i in range(n_words)]
        return Communicator.from_exchmem_words(
            words, exchmem_addr=comm.exchmem_addr).ranks


class SequenceRecorder:
    """Records a batch of collective/copy/combine descriptors host-side
    (the thin-client half of the device-resident call-sequence contract):
    each method queues the SAME descriptor its eager ACCL counterpart
    would dispatch, and `run()` hands the whole batch to the device for
    one fused compile+dispatch (TPUDevice.start_sequence). Collective
    methods return the recorder, so chains compose fluently; send/recv
    and barrier cannot ride a sequence (host-paired / payload-free)."""

    def __init__(self, accl: ACCL, comm: Communicator | None = None,
                 lint: str = "error", persistent=()):
        self._accl = accl
        self._comm = comm
        self._lint = lint
        # declared device-resident state buffers (ACCL101 waiver) — kept
        # as addresses: that's the layer the hazard pass renames from
        self._persistent = frozenset(b.address for b in persistent)
        self.calls: list[CallOptions] = []
        self._reads: list[BaseBuffer] = []  # per-step operand buffers
        self._writes: list[BaseBuffer] = []  # per-step result buffers
        self._ran = False

    def __len__(self) -> int:
        return len(self.calls)

    def __enter__(self) -> "SequenceRecorder":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self.calls and not self._ran:
            self.run()
        return False

    def _record(self, opts: CallOptions, reads, writes) -> "SequenceRecorder":
        if self._ran:
            raise SequenceReuseError(
                "sequence already executed; record a new one")
        self.calls.append(opts)
        self._reads.append(list(reads))
        self._writes.append(list(writes))
        return self

    def _prep(self, scenario, op0, op1, res, count, **kw):
        return self._accl._prepare(scenario, op0, op1, res, count,
                                   comm=self._comm, **kw)

    # -- recorded forms of the facade's data-plane calls -------------------

    def copy(self, srcbuf, dstbuf, count, *, op0_stream=None,
             res_stream=None):
        """Recorded copy; `res_stream` routes the result through a
        registered consumer before it lands in dstbuf (the recorded
        form of copy_to_stream — the seam the fused train step splices
        its forward+backward compute through)."""
        opts = self._prep(Operation.copy, srcbuf, None, dstbuf, count)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [srcbuf], [dstbuf])

    def combine(self, count, function, op0, op1, res):
        opts = self._prep(Operation.combine, op0, op1, res, count,
                          function=int(function))
        return self._record(opts, [op0, op1], [res])

    def bcast(self, buf, count, root, *, compress_dtype=None,
              op0_stream=None, res_stream=None):
        opts = self._prep(Operation.bcast, buf, None, buf, count,
                          root_src_dst=root, compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [buf], [buf])

    def scatter(self, sendbuf, recvbuf, count, root, *, compress_dtype=None,
                op0_stream=None, res_stream=None):
        opts = self._prep(Operation.scatter, sendbuf, None, recvbuf, count,
                          root_src_dst=root, compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def gather(self, sendbuf, recvbuf, count, root, *, compress_dtype=None,
               op0_stream=None, res_stream=None):
        opts = self._prep(Operation.gather, sendbuf, None, recvbuf, count,
                          root_src_dst=root, compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def allgather(self, sendbuf, recvbuf, count, *, compress_dtype=None,
                  op0_stream=None, res_stream=None):
        opts = self._prep(Operation.allgather, sendbuf, None, recvbuf, count,
                          compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def reduce(self, sendbuf, recvbuf, count, root, function, *,
               compress_dtype=None, op0_stream=None, res_stream=None):
        opts = self._prep(Operation.reduce, sendbuf, None, recvbuf, count,
                          root_src_dst=root, function=int(function),
                          compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def allreduce(self, sendbuf, recvbuf, count, function, *,
                  compress_dtype=None, op0_stream=None, res_stream=None,
                  mode="all", live_ranks=None):
        opts = self._prep(Operation.allreduce, sendbuf, None, recvbuf, count,
                          function=int(function),
                          compress_dtype=compress_dtype)
        opts.live_ranks = self._accl._live_subset(
            mode, live_ranks, int(function), compress_dtype, self._comm)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def reduce_scatter(self, sendbuf, recvbuf, count, function, *,
                       compress_dtype=None, op0_stream=None,
                       res_stream=None):
        opts = self._prep(Operation.reduce_scatter, sendbuf, None, recvbuf,
                          count, function=int(function),
                          compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def alltoall(self, sendbuf, recvbuf, count, *, compress_dtype=None,
                 op0_stream=None, res_stream=None):
        opts = self._prep(Operation.alltoall, sendbuf, None, recvbuf, count,
                          compress_dtype=compress_dtype)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    def alltoallv(self, sendbuf, recvbuf, count, send_counts, *,
                  compress_dtype=None, op0_stream=None, res_stream=None):
        opts = self._accl._prepare_alltoallv(
            sendbuf, recvbuf, count, send_counts,
            compress_dtype=compress_dtype, comm=self._comm)
        self._accl._stream_opts(opts, op0_stream, res_stream)
        return self._record(opts, [sendbuf], [recvbuf])

    # -- execution ---------------------------------------------------------

    def _sync_sets(self):
        """(sync_in, sync_out): external inputs = buffers read before any
        in-sequence write (intermediates chain on-device); outputs =
        every written buffer, first-write order — the same sets eager
        back-to-back calls would sync."""
        written: set[int] = set()
        sync_in: list[BaseBuffer] = []
        sync_out: list[BaseBuffer] = []
        for reads, writes in zip(self._reads, self._writes):
            for b in reads:
                if id(b) not in written and all(b is not x for x in sync_in):
                    sync_in.append(b)
            for b in writes:
                written.add(id(b))
                if all(b is not x for x in sync_out):
                    sync_out.append(b)
        return sync_in, sync_out

    def compile(self) -> "SequenceProgram":
        """Freeze the recorded batch into a re-dispatchable
        SequenceProgram: the descriptor resolution, lint gate, dataflow
        analysis and compile all happen ONCE here, and every
        `program.run()` afterwards is stage-in + one dispatch +
        completion — none of the per-call re-resolution a fresh
        recorder pays. The recorder is consumed (same one-shot contract
        as run()). This is the steady-state form of the device-resident
        call sequence: one compiled program per recorded step shape,
        dispatched per iteration (the MoE layer step rides it)."""
        if self._ran:
            raise SequenceReuseError(
                "sequence already executed; record a new one")
        if not self.calls:
            raise ValueError("empty sequence: record at least one call")
        if not hasattr(self._accl.cclo, "prepare_sequence"):
            raise NotImplementedError(
                f"{type(self._accl.cclo).__name__} does not support "
                "prepared call sequences")
        self._ran = True
        return SequenceProgram(self._accl, self)

    def run(self, *, from_device=False, to_device=False, run_async=False):
        """Dispatch the recorded batch as ONE compiled device program.
        from_device/to_device skip the host<->HBM syncs around the WHOLE
        sequence (per-call syncs between stages never happen: that seam
        is what the fusion removes); run_async returns the request, to be
        completed with accl.wait()."""
        if self._ran:
            raise SequenceReuseError(
                "sequence already executed; record a new one")
        if not self.calls:
            raise ValueError("empty sequence: record at least one call")
        self._ran = True
        accl = self._accl
        sync_in, sync_out = self._sync_sets()
        with get_tracer().call("sequence", cat="sequence") as sp:
            call = sp if sp.keep else None
            accl._stage_in(sync_in, from_device, call)
            Log.debug("sequence of %d: %s", len(self.calls),
                      "+".join(o.scenario.name for o in self.calls))
            req = accl.cclo.start_sequence(self.calls, lint=self._lint,
                                           persistent=self._persistent)
            ret = accl._complete(req, sync_out, to_device, run_async, call)
            if sp:
                sp.set(n_steps=len(self.calls),
                       ops="+".join(o.scenario.name for o in self.calls))
                if run_async:
                    sp.set(dispatch_only=True)
                sig = getattr(req, "signature", None)
                if sig is not None:
                    sp.set(signature=sig)
                pred = getattr(req, "predicted_s", None)
                if pred is not None:
                    sp.set(predicted_s=pred)
            return ret


class SequenceProgram:
    """A recorded call sequence frozen into its steady-state form:
    resolve + lint + compile happened once (at SequenceRecorder.compile),
    and every `run()` is stage-in + ONE device dispatch + completion —
    the per-iteration cost profile of a device-resident descriptor
    batch (no re-recording, no re-planning, no signature hashing).

    The program binds the buffers the recorder referenced: each run
    reads their CURRENT device contents and places results back, so the
    caller's loop is `write inputs -> program.run() -> read outputs`.
    The plans were resolved under the tuning registers live at compile
    time — retune, then re-record, to pick up new registers."""

    def __init__(self, accl: ACCL, recorder: SequenceRecorder):
        self._accl = accl
        self._sync_in, self._sync_out = recorder._sync_sets()
        self.n_steps = len(recorder.calls)
        self._ops = "+".join(o.scenario.name for o in recorder.calls)
        self._prepared = accl.cclo.prepare_sequence(
            recorder.calls, lint=recorder._lint,
            persistent=recorder._persistent)

    @property
    def plans(self):
        """The per-step Plans the batch resolved to (frozen)."""
        return self._prepared.plans

    @property
    def signature(self):
        """Composite-signature digest of the recorded batch: the
        compile/lint cache key and the interference-verdict cache key
        half — available whether or not a tracer was live at compile."""
        return self._prepared.sig

    @property
    def footprint(self):
        """The program's interference summary (ProgramFootprint), the
        input to ACCL.certify_concurrent."""
        return getattr(self._prepared, "footprint", None)

    @property
    def certificate(self):
        """Certificate id of the pairwise-clean concurrent set this
        program was last admitted into (None until certify_concurrent
        passes it)."""
        return getattr(self._prepared, "cert", None)

    def run(self, *, from_device=False, to_device=False, run_async=False):
        """Dispatch the compiled batch over the bound buffers' current
        contents; same sync semantics as SequenceRecorder.run()."""
        accl = self._accl
        with get_tracer().call("sequence", cat="sequence") as sp:
            call = sp if sp.keep else None
            accl._stage_in(self._sync_in, from_device, call)
            req = accl.cclo.dispatch_sequence(self._prepared)
            ret = accl._complete(req, self._sync_out, to_device, run_async,
                                 call)
            if sp:
                sp.set(n_steps=self.n_steps, ops=self._ops, prepared=True)
                if run_async:
                    sp.set(dispatch_only=True)
                sig = getattr(req, "signature", None)
                if sig is not None:
                    sp.set(signature=sig)
                cert = getattr(req, "interference_cert", None)
                if cert is not None:
                    sp.set(interference_cert=cert)
            return ret
