"""EmuDevice: ctypes binding to the native multi-rank emulator runtime.

The SimDevice analog (reference driver/xrt/src/simdevice.cpp talking ZMQ
to test/model/emulator): each EmuRank owns one native runtime instance —
a rank with its own sequencer thread, TCP links, eager rx ring and
rendezvous queues (native/src/runtime.cpp). Unlike the single-controller
TPUDevice, this backend is genuinely per-rank: N EmuRanks (threads or
processes) execute collectives against each other over sockets, which is
how the reference's emulator-based CI runs the gtest suite with no
hardware in the loop (SURVEY.md §4).
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import socket
import subprocess
import threading
from typing import Literal, overload

import numpy as np

from ..constants import (
    ACCLError,
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_NUM_EAGER_RX_BUFS,
    Operation,
    TAG_ANY,
    from_numpy_dtype,
)
from ..descriptor import CallOptions

_NATIVE_DIR = pathlib.Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libacclrt.so"
_lib = None
_lib_lock = threading.Lock()


# Field names of the versioned accl_rt_get_stats2 counter surface, in
# native index order (native/include/acclrt.h accl_rt_stat2). The first
# five are the classic sequencer counters; the rest are the reliability
# sublayer's wire-health counters (CRC/dup drops, selective-retransmit
# ack/nack traffic, seeded fault-injection tallies, cumulative ns of
# CRC+ack bookkeeping). The native return value may exceed
# len(STATS2_FIELDS) on a newer library — unknown trailing counters are
# ignored, never misnamed.
STATS2_FIELDS = (
    "passes", "parks", "park_ns", "seek_hit", "seek_miss",
    "tx_frames", "rx_frames", "crc_drops", "dup_drops",
    "retx_sent", "retx_miss", "nack_sent", "nack_rx",
    "ack_sent", "ack_rx", "rndzv_drops",
    "inj_loss", "inj_corrupt", "inj_dup", "inj_reorder", "rely_ns",
    # vectored-wire transmit shape: syscalls issued for frame transmit
    # and frames shipped inside a multi-frame writev/sendmmsg batch
    # (tx_syscalls / tx_frames is the per-frame syscall ratio)
    "tx_syscalls", "tx_batched",
)

# (The repair-activity subset the resilience escalation policy reads —
# lossy-link vs dead-rank classification — is single-sourced as
# telemetry.export.WIRE_FAULT_KEYS, next to the exporter that renders
# these counters.)


class NativeSpan(ctypes.Structure):
    """ctypes mirror of accl_rt_span_t (native/include/acclrt.h): one
    record of the device-resident trace ring per completed call."""

    _fields_ = [
        ("opcode", ctypes.c_uint32),
        ("retcode", ctypes.c_uint32),
        ("detail", ctypes.c_uint32),
        ("count", ctypes.c_uint32),
        ("bytes", ctypes.c_uint64),
        ("start_ns", ctypes.c_uint64),
        ("end_ns", ctypes.c_uint64),
        ("d_passes", ctypes.c_uint64),
        ("d_parks", ctypes.c_uint64),
        ("d_seek_hit", ctypes.c_uint64),
        ("d_seek_miss", ctypes.c_uint64),
    ]


def load_native():
    """Load (building if needed) the native runtime library.

    ACCL_NATIVE_LIB overrides the library path — the sanitizer CI lane
    points it at the ASan/UBSan build (native/libacclrt.san.so, `make
    -C native sanitize`) so the same test suite exercises the
    instrumented data plane without touching the default artifact."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        override = os.environ.get("ACCL_NATIVE_LIB")
        if override:
            lib_path = pathlib.Path(override).resolve()
        else:
            # always invoke make: a fresh build is a no-op, and a stale
            # .so silently shadowing source edits is worse than the
            # fork cost
            subprocess.run(["make", "-C", str(_NATIVE_DIR)], check=True,
                           capture_output=True)
            lib_path = _LIB_PATH
        lib = ctypes.CDLL(str(lib_path))
        lib.accl_rt_create.restype = ctypes.c_void_p
        lib.accl_rt_create.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint64,
        ]
        lib.accl_rt_create_ex.restype = ctypes.c_void_p
        lib.accl_rt_create_ex.argtypes = lib.accl_rt_create.argtypes + [
            ctypes.c_uint32,
        ]
        lib.accl_rt_destroy.argtypes = [ctypes.c_void_p]
        lib.accl_rt_start.restype = ctypes.c_int64
        lib.accl_rt_start.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.accl_rt_test.restype = ctypes.c_int
        lib.accl_rt_test.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.accl_rt_wait.restype = ctypes.c_int
        lib.accl_rt_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_uint64]
        lib.accl_rt_retcode.restype = ctypes.c_uint32
        lib.accl_rt_retcode.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.accl_rt_duration_ns.restype = ctypes.c_uint64
        lib.accl_rt_duration_ns.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.accl_rt_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.accl_rt_read.restype = ctypes.c_uint32
        lib.accl_rt_read.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.accl_rt_write.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                      ctypes.c_uint32]
        lib.accl_rt_get_stats.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_uint64)]
        lib.accl_rt_get_stats2.restype = ctypes.c_size_t
        lib.accl_rt_get_stats2.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
        ]
        lib.accl_rt_dump_rxbufs.restype = ctypes.c_size_t
        lib.accl_rt_dump_rxbufs.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_size_t]
        lib.accl_rt_trace_read.restype = ctypes.c_size_t
        lib.accl_rt_trace_read.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(NativeSpan), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.accl_rt_kill.argtypes = [ctypes.c_void_p]
        lib.accl_rt_flush_rx.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


@overload
def free_ports(n: int, hold: Literal[True]) -> tuple[list[int], list[socket.socket]]: ...
@overload
def free_ports(n: int, hold: Literal[False] = False) -> list[int]: ...
def free_ports(n, hold=False):
    """Reserve n free localhost ports (emulator launch helper, the role of
    test/model/emulator/run.py's port allocation).

    hold=True returns (ports, sockets) with the reserving sockets still
    bound: the "local" POE never binds these ports itself (they are pure
    registry keys into the native g_local_ports map), so without a live
    reservation the OS may hand the same numbers to a second
    concurrently-alive world and the native registry refuses the
    collision at bring-up — the caller keeps the sockets open for the
    world's lifetime."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    if hold:
        return ports, socks
    for s in socks:
        s.close()
    return ports


class EmuRank:
    """One rank of the native emulator (per-rank driver endpoint)."""

    def __init__(
        self,
        world: int,
        rank: int,
        ports: list[int],
        n_rx_bufs: int = DEFAULT_NUM_EAGER_RX_BUFS,
        rx_buf_bytes: int = DEFAULT_EAGER_RX_BUF_SIZE,
        max_eager: int = DEFAULT_MAX_EAGER_SIZE,
        # The driver default ceiling (32 KB, accl.hpp:104) is what apps
        # immediately raise at bring-up; the emulator defaults to a roomy
        # ceiling so rendezvous tests exercise real sizes. The limit stays
        # enforced (DMA_SIZE_ERROR past it).
        max_rndzv: int = 64 * 1024 * 1024,
        # "tcp" = session full mesh (EasyNet-class POE); "udp" = sessionless
        # datagram transport (VNX POE analog, eager-only)
        transport: str = "tcp",
    ):
        lib = load_native()
        self.world = world
        self.rank = rank
        self.transport = transport
        arr = (ctypes.c_uint16 * world)(*ports)
        # "local" is the intra-process POE (direct-call delivery, no
        # sockets): the intra-node fast-path transport beside the TCP
        # session mesh and the datagram POE
        tr = {"tcp": 0, "udp": 1, "local": 2}[transport]
        self._rt = lib.accl_rt_create_ex(
            world, rank, arr, n_rx_bufs, rx_buf_bytes, max_eager, max_rndzv,
            tr,
        )
        if not self._rt:
            raise RuntimeError(f"native runtime bring-up failed (rank {rank})")
        self._lib = lib
        self._keepalive: dict[int, tuple] = {}
        self._durations: dict[int, int] = {}
        # per-handle descriptor, so a failed wait can name the call in
        # the flight-recorder post-mortem (popped with the keepalive)
        self._call_opts: dict[int, CallOptions] = {}

    def close(self):
        if self._rt:
            self._lib.accl_rt_destroy(self._rt)
            self._rt = None

    def kill(self):
        """Permanently wedge this rank (accl_rt_kill — the programmatic
        ACCL_RT_FAULT_KILL_RANK): in-flight and future calls complete
        with a sticky RECEIVE_TIMEOUT retcode (a final trace-ring span
        when tracing is armed) and the rank's wire goes dark in both
        directions. The fault-injection primitive of the self-healing
        soak (tests/test_resilience.py)."""
        if self._rt:
            self._lib.accl_rt_kill(self._rt)

    def flush_rx(self, settle_s: float = 0.05):
        """Reconfiguration fence (accl_rt_flush_rx): drop stale landed
        frames of the old membership's aborted collectives and advance
        the per-peer seqn past them. Call QUIESCENT (no live calls on
        this rank, survivor threads joined) between excluding a dead
        rank and the first call on the recovery communicator — the
        seqn-ordered streamed matching would otherwise deliver old-
        world frames into the new world's first recv as data.

        The fence runs TWICE around a `settle_s` pause: quiescence
        means no peer is *sending* (their calls terminated before this
        rank's threads joined — sends happen synchronously inside
        calls), but a final frame may still be crossing the receive
        path (the rx thread mid-read of a socket buffer). Such a
        straggler lands with a seqn at-or-past the first flush's
        advance and would read as new-world data; the settle window
        lets it land and the second flush drops it. A frame delayed
        longer than `settle_s` after every sender terminated would
        need a transport that buffers outside both endpoints — not a
        property of the in-process/loopback POEs."""
        if self._rt:
            import time

            self._lib.accl_rt_flush_rx(self._rt)
            if settle_s > 0:
                time.sleep(settle_s)
                self._lib.accl_rt_flush_rx(self._rt)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- MMIO --------------------------------------------------------------

    def read(self, addr: int) -> int:
        return self._lib.accl_rt_read(self._rt, addr)

    def write(self, addr: int, value: int):
        self._lib.accl_rt_write(self._rt, addr, value)

    def sequencer_stats(self) -> dict:
        """Cumulative sequencer counters of this rank's runtime —
        execute passes, event-counter parks, nanoseconds parked, rx-seek
        hits/misses. The live form of the ACCL_RT_STATS destroy-time
        dump: diff two snapshots to profile one phase of a run
        (tools/rt_stats_sweep.py automates the per-config version)."""
        buf = (ctypes.c_uint64 * 5)()
        self._lib.accl_rt_get_stats(self._rt, buf)
        return {"passes": buf[0], "parks": buf[1], "park_ns": buf[2],
                "seek_hit": buf[3], "seek_miss": buf[4]}

    def wire_stats(self) -> dict:
        """Full versioned counter surface (accl_rt_get_stats2): the
        sequencer counters PLUS the reliability sublayer's wire-health
        counters — frames tx/rx, CRC-corrupt and duplicate drops,
        selective-retransmit ack/nack traffic, the seeded fault model's
        injection tallies, and the cumulative CRC+ack bookkeeping ns.
        Diff two snapshots to judge one phase of a run; the resilience
        manager consumes exactly that delta to tell a lossy link from a
        dark one (docs/resilience.md escalation policy)."""
        cap = len(STATS2_FIELDS)
        buf = (ctypes.c_uint64 * cap)()
        n = min(int(self._lib.accl_rt_get_stats2(self._rt, buf, cap)), cap)
        # schema-stable: every known field present (zero when the
        # library predates it), unknown trailing counters ignored
        return {name: int(buf[i]) if i < n else 0
                for i, name in enumerate(STATS2_FIELDS)}

    def trace_read(self, chunk: int = 4096) -> tuple[list[dict], int]:
        """Drain this rank's device-resident trace ring (ACCL_RT_TRACE=1;
        accl_rt_trace_read): returns (spans, dropped) where each span is
        a dict in the telemetry subsystem's native-span shape — opcode,
        count, payload bytes, start/end ns since runtime creation, the
        sticky retcode, the deferred-mismatch fault detail behind a
        RECEIVE_TIMEOUT, and the per-call sequencer-counter deltas.
        Loops until the ring is empty (a raised ACCL_RT_TRACE_CAP must
        not silently truncate at one chunk). `dropped` is the cumulative
        count of spans the ring overflowed (oldest first). Empty when
        tracing is disabled."""
        spans: list[dict] = []
        dropped = ctypes.c_uint64(0)
        while True:
            buf = (NativeSpan * chunk)()
            n = self._lib.accl_rt_trace_read(self._rt, buf, chunk,
                                             ctypes.byref(dropped))
            spans.extend(
                {
                    "opcode": s.opcode,
                    "retcode": s.retcode,
                    "detail": s.detail,
                    "count": s.count,
                    "bytes": s.bytes,
                    "start_ns": s.start_ns,
                    "end_ns": s.end_ns,
                    "d_passes": s.d_passes,
                    "d_parks": s.d_parks,
                    "d_seek_hit": s.d_seek_hit,
                    "d_seek_miss": s.d_seek_miss,
                    "rank": self.rank,
                }
                for s in buf[:n]
            )
            if n < chunk:
                return spans, int(dropped.value)

    def dump_eager_rx_buffers(self) -> str:
        """Slot-by-slot rx-ring snapshot from the native runtime
        (accl_rt_dump_rxbufs; reference accl.cpp:964-1012)."""
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            need = self._lib.accl_rt_dump_rxbufs(self._rt, buf, cap)
            if need < cap:  # re-loop if the ring grew between calls
                return buf.value.decode()
            cap = need + 4096

    # -- calls -------------------------------------------------------------

    @staticmethod
    def _ptr(arr):
        if arr is None:
            return None
        assert arr.flags["C_CONTIGUOUS"]
        return arr.ctypes.data_as(ctypes.c_void_p)

    def start(self, opts: CallOptions, op0=None, op1=None, res=None) -> int:
        words = (ctypes.c_uint32 * 15)(*[w & 0xFFFFFFFF for w in opts.to_words()])
        dt = int(opts.data_type)
        h = self._lib.accl_rt_start(
            self._rt, words, dt, self._ptr(op0), self._ptr(op1), self._ptr(res)
        )
        # operands must outlive the call (reference: buffers owned by caller
        # until request completion, acclrequest.hpp)
        self._keepalive[h] = (op0, op1, res)
        self._call_opts[h] = opts
        return h

    def wait(self, handle: int, timeout_ms: int = 0) -> None:
        ok = self._lib.accl_rt_wait(self._rt, handle, timeout_ms)
        if not ok:
            raise TimeoutError(f"rank {self.rank}: call {handle} timed out")
        rc = self._lib.accl_rt_retcode(self._rt, handle)
        # cache duration, then release the native completion record
        self._durations[handle] = self._lib.accl_rt_duration_ns(self._rt, handle)
        self._lib.accl_rt_release(self._rt, handle)
        self._keepalive.pop(handle, None)
        opts = self._call_opts.pop(handle, None)
        if rc:
            # dump-on-error: report the failing call (its descriptor's
            # op name + count, this rank, the sticky retcode) to the
            # armed flight recorder BEFORE the typed raise, so the
            # post-mortem names the span that died. The device trace
            # ring is deliberately NOT drained here — consuming it
            # would steal the wedged span from an explicit
            # trace_read()/drain_world a caller runs after the failure.
            from ..errors import notify_sticky_retcode

            notify_sticky_retcode(
                opts.scenario.name if opts is not None
                else f"emu rank {self.rank}", rc, rank=self.rank,
                count=opts.count if opts is not None else None)
            raise ACCLError(f"emu rank {self.rank}", rc)

    def test(self, handle: int) -> bool:
        return bool(self._lib.accl_rt_test(self._rt, handle))

    def duration_ns(self, handle: int) -> int:
        if handle in self._durations:
            return self._durations[handle]
        return self._lib.accl_rt_duration_ns(self._rt, handle)

    def call(self, opts: CallOptions, op0=None, op1=None, res=None) -> int:
        h = self.start(opts, op0, op1, res)
        self.wait(h)
        return h

    # -- communicators (multi-communicator support) -----------------------

    def write_communicator(self, comm) -> None:
        """Write a Communicator's rank table into this rank's exchange
        memory at comm.exchmem_addr; pass that address as comm_addr to any
        collective (the firmware reads the table back per call,
        ccl_offload_control.c:2317-2372). Membership is derived from each
        entry's device_index (= global transport rank)."""
        for i, w in enumerate(comm.exchmem_words()):
            self.write(comm.exchmem_addr + 4 * i, w)

    # -- convenience collective wrappers (per-rank ACCL-style API) --------

    def _opts(self, scenario, count, dtype, root=0, func=0, tag=TAG_ANY,
              comm_addr=0):
        return CallOptions(
            scenario=scenario, count=count, root_src_dst=root,
            function=int(func), tag=tag, comm_addr=comm_addr,
            data_type=from_numpy_dtype(dtype),
        )

    def send(self, buf, count, dst, tag=TAG_ANY, comm_addr=0):
        return self.call(self._opts(Operation.send, count, buf.dtype, dst,
                                    tag=tag, comm_addr=comm_addr), op0=buf)

    def recv(self, buf, count, src, tag=TAG_ANY, comm_addr=0):
        return self.call(self._opts(Operation.recv, count, buf.dtype, src,
                                    tag=tag, comm_addr=comm_addr), res=buf)

    def copy(self, src, dst, count):
        return self.call(self._opts(Operation.copy, count, src.dtype), op0=src, res=dst)

    def combine(self, count, func, op0, op1, res):
        return self.call(self._opts(Operation.combine, count, op0.dtype, func=func),
                         op0=op0, op1=op1, res=res)

    def bcast(self, buf, count, root, comm_addr=0):
        return self.call(self._opts(Operation.bcast, count, buf.dtype, root,
                                    comm_addr=comm_addr), op0=buf)

    def scatter(self, sendbuf, recvbuf, count, root, comm_addr=0):
        return self.call(self._opts(Operation.scatter, count, recvbuf.dtype,
                                    root, comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def gather(self, sendbuf, recvbuf, count, root, comm_addr=0):
        return self.call(self._opts(Operation.gather, count, sendbuf.dtype,
                                    root, comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def allgather(self, sendbuf, recvbuf, count, comm_addr=0):
        return self.call(self._opts(Operation.allgather, count, sendbuf.dtype,
                                    comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def reduce(self, sendbuf, recvbuf, count, root, func, comm_addr=0):
        return self.call(self._opts(Operation.reduce, count, sendbuf.dtype,
                                    root, func, comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def allreduce(self, sendbuf, recvbuf, count, func, comm_addr=0):
        return self.call(self._opts(Operation.allreduce, count, sendbuf.dtype,
                                    func=func, comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def reduce_scatter(self, sendbuf, recvbuf, count, func, comm_addr=0):
        return self.call(self._opts(Operation.reduce_scatter, count,
                                    sendbuf.dtype, func=func,
                                    comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def alltoall(self, sendbuf, recvbuf, count, comm_addr=0):
        return self.call(self._opts(Operation.alltoall, count, sendbuf.dtype,
                                    comm_addr=comm_addr),
                         op0=sendbuf, res=recvbuf)

    def barrier(self, comm_addr=0):
        return self.call(self._opts(Operation.barrier, 0, np.float32,
                                    comm_addr=comm_addr))


class EmuWorld:
    """Bring up N emulator ranks in one process (the in-process analog of
    run.py launching N emulator processes; rank bring-up is concurrent
    because link establishment blocks on peers)."""

    # worlds whose bring-up failed (a socket-transport port lost to a
    # colliding process, a refused link) are retried with FRESH ports —
    # bounded, so an environment-level flake costs a retry instead of a
    # failed run
    BRINGUP_ATTEMPTS = 3

    def __init__(self, world: int, **kw):
        self.ranks: list[EmuRank | None] = [None] * world
        self._port_holds: list = []
        last: Exception | None = None
        for _attempt in range(self.BRINGUP_ATTEMPTS):
            if kw.get("transport") == "local":
                # local mode uses port numbers only as registry keys —
                # hold the reserving sockets for the world's lifetime so
                # a second live world can never be assigned the same keys
                # (the port-registry collision that used to flake
                # concurrent local worlds)
                ports, self._port_holds = free_ports(world, hold=True)
            else:
                ports, self._port_holds = free_ports(world), []
            self.ports = list(ports)
            self.ranks = [None] * world
            errs: list[Exception] = []

            def mk(r):
                try:
                    self.ranks[r] = EmuRank(world, r, ports, **kw)
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=mk, args=(r,))
                       for r in range(world)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if not errs:
                return
            last = errs[0]
            self.close()  # tear down the half-up world before retrying
        assert last is not None  # loop body ran and every attempt failed
        raise last

    def close(self):
        for r in self.ranks:
            if r is not None:
                r.close()
        # release the local-mode port reservations only after every rank
        # has unregistered from the native registry
        for s in self._port_holds:
            s.close()
        self._port_holds = []

    def run(self, fn):
        """Execute fn(rank_obj, rank_idx) on every rank concurrently and
        return the list of results (MPI-program analog of the gtest
        fixture, test/host/xrt/include/fixture.hpp)."""
        results = [None] * len(self.ranks)
        errs = []

        def body(i):
            try:
                results[i] = fn(self.ranks[i], i)
            except Exception as e:
                errs.append(e)

        threads = [
            threading.Thread(target=body, args=(i,))
            for i in range(len(self.ranks))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return results
