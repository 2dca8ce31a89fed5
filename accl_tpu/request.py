"""Asynchronous request handles.

Reference semantics: driver/xrt/include/accl/acclrequest.hpp:40-120 — a
request owns an atomic operationStatus, a wait/timeout, the call's return
code and its device-measured duration; per-device queues serialize starts.

TPU mapping: XLA dispatch is already asynchronous — launching a compiled
schedule returns immediately with futures for its outputs — so a request
wraps the in-flight output array; wait() is block_until_ready. Durations
come from wall-clocking the device completion, the emulator analog of the
hardware cycle counter (ccl_offload_control.c:2279-2303).
"""

from __future__ import annotations

import threading
import time
from typing import Any

from .constants import ACCLError, ErrorCode, OperationStatus


class BaseRequest:
    """One in-flight collective call."""

    _next_id = iter(range(1, 1 << 62))

    def __init__(self, function_name: str = "call"):
        self.request_id = next(self._next_id)
        self.function_name = function_name
        self.status = OperationStatus.QUEUED
        self.retcode = 0
        self.duration_ns = 0
        self._done = threading.Event()
        # facade riders (ACCL._complete / ACCL.wait): buffers whose
        # device->host sync was deferred to wait(), and the private
        # stream placeholder to release once the request completes
        self._accl_sync_out: list = []
        self._accl_scratch: Any = None

    def running(self):
        self.status = OperationStatus.EXECUTING
        self._start_time = time.perf_counter_ns()

    def complete(self, retcode: int = 0):
        self.retcode = retcode
        if not self.duration_ns:  # else the backend measured the call
            self.duration_ns = time.perf_counter_ns() - getattr(
                self, "_start_time", time.perf_counter_ns()
            )
        self.status = OperationStatus.COMPLETED
        self._done.set()
        if retcode:
            # the sticky-error-word write point: the telemetry flight
            # recorder (when armed) freezes its span rings into a
            # post-mortem here, whether or not the caller ever check()s
            from .errors import notify_sticky_retcode

            notify_sticky_retcode(self.function_name, int(retcode))

    def wait(self, timeout: float | None = None) -> bool:
        """Block until completion; returns False on timeout (reference
        acclrequest.hpp wait variants)."""
        return self._done.wait(timeout)

    def test(self) -> bool:
        """Non-blocking completion probe (reference CCLO::test)."""
        return self.status == OperationStatus.COMPLETED

    def check(self):
        """Raise if the call returned a sticky error word (reference
        ACCL::check_return_value, accl.cpp:1210-1234)."""
        if self.retcode:
            raise ACCLError(self.function_name, self.retcode)

    def get_duration_ns(self) -> int:
        """Device-time duration of the call (reference get_duration,
        xrtdevice.cpp:242-249)."""
        return self.duration_ns


class TPURequest(BaseRequest):
    """Request whose completion is the readiness of jax output arrays
    (`block_until_ready`). `duration_ns`, where the device measured it,
    is the duration register: from before the launch to the host seeing
    the outputs ready."""

    def __init__(self, function_name: str, outputs, on_complete=None,
                 duration_ns: int = 0):
        super().__init__(function_name)
        self.duration_ns = duration_ns
        self.outputs = outputs
        self._on_complete = on_complete
        # set by the device after plan selection: the resolved Plan this
        # request executes, and its timing.predict estimate when tracing
        self.plan: Any = None
        self.predicted_s: float | None = None
        self.running()

    def wait(self, timeout: float | None = None) -> bool:
        if self.status == OperationStatus.COMPLETED:
            return True
        if timeout is not None:
            deadline = time.monotonic() + timeout
            while not all(_is_ready(o) for o in self.outputs):
                if time.monotonic() >= deadline:
                    return False
                time.sleep(0.001)
        try:
            for o in self.outputs:
                o.block_until_ready()
            self.complete(0)
        except Exception as e:
            # surface runtime failures through the sticky-error-word
            # contract (reference: every engine ORs its bits into the
            # retcode, ccl_offload_control.h:139-167) instead of an
            # unclassified -1; the original exception still propagates
            self.complete(_classify_runtime_error(e))
            raise
        if self._on_complete is not None:
            self._on_complete(self)
        return True

    def test(self) -> bool:
        if self.status == OperationStatus.COMPLETED:
            return True
        if all(_is_ready(o) for o in self.outputs):
            self.wait()
            return True
        return False


class SequenceRequest(TPURequest):
    """Request for a fused call sequence: ONE device dispatch covering a
    recorded batch of descriptors. Completion is the readiness of the
    batch's written buffers (the single program's outputs); `plans` and
    `num_steps` expose what the one dispatch covered, the sequence analog
    of TPURequest.plan."""

    def __init__(self, outputs, plans, on_complete=None,
                 duration_ns: int = 0):
        super().__init__("sequence", outputs, on_complete=on_complete,
                         duration_ns=duration_ns)
        self.plans = list(plans)
        self.num_steps = len(self.plans)
        # set by the device on every dispatch (tracing or not): content
        # hash of the recorded descriptor batch — the compile/lint cache
        # key, the interference-verdict cache key half, and the span tag
        self.signature: str | None = None
        # certificate id of the pairwise-clean tenant set this program
        # was admitted into by ACCL.certify_concurrent, if any
        self.interference_cert: str | None = None
        # exactly one device dispatch happened for the whole batch — the
        # observable inversion the sequence layer exists for (the
        # sequence tests read this)
        self.num_dispatches = 1


class ParkedRecvRequest(BaseRequest):
    """A recv issued before its matching send: parks until the send
    arrives (then mirrors the launched pair program) or the device's
    configured timeout lapses (then completes with RECEIVE_TIMEOUT_ERROR).
    The reference equivalent is the firmware retry queue re-running an
    unmatched recv until HOUSEKEEP_TIMEOUT (ccl_offload_control.c:2460-2479).

    The outcome is decided exactly once: pairing (the device thread) and
    timeout (any waiter/test thread) race through `claim()`, so a send
    arriving at the deadline can never be reported as a timeout after its
    transfer ran, and vice versa."""

    def __init__(self, options, timeout_s: float):
        super().__init__("recv")
        self.options = options
        self.running()
        self._deadline = time.monotonic() + timeout_s
        self._inner: BaseRequest | None = None
        self._paired = threading.Event()
        self._claim_lock = threading.Lock()
        self._claimed = False
        # device-side parking-slot sequence number (used to unpark the
        # right entry when recvs race)
        self._park_seq = 0
        # set by the device to drop the parking; a do-nothing callable,
        # not a def, so reassignment stays symmetric
        self._unpark = lambda: None  # noqa: E731

    def claim(self) -> bool:
        """Atomically claim the right to decide this request's outcome."""
        with self._claim_lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def resolve(self, inner: BaseRequest):
        """Called by the device (after a successful claim) when the
        matching send arrives."""
        self._inner = inner
        self._paired.set()

    def _timeout_fire(self) -> bool:
        self._unpark()
        self.complete(int(ErrorCode.RECEIVE_TIMEOUT_ERROR))
        return True

    def wait(self, timeout: float | None = None) -> bool:
        if self.status == OperationStatus.COMPLETED:
            return True
        caller_deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            # another thread (test(), reset) may decide the outcome
            if self.status == OperationStatus.COMPLETED:
                return True
            now = time.monotonic()
            if caller_deadline is not None and now >= caller_deadline:
                return False
            if self._paired.is_set():
                remain = (None if caller_deadline is None
                          else max(caller_deadline - time.monotonic(), 0))
                if not self._inner.wait(remain):
                    return False
                self.complete(self._inner.retcode)
                return True
            if now >= self._deadline:
                if self.claim():
                    return self._timeout_fire()
                # outcome claimed elsewhere: either a concurrent send is
                # pairing (resolve sets _paired) or another thread fired
                # the timeout (sets COMPLETED) — poll for whichever
                self._paired.wait(0.05)
                continue
            limit = self._deadline - now
            if caller_deadline is not None:
                limit = min(limit, caller_deadline - now)
            self._paired.wait(max(limit, 0))

    def test(self) -> bool:
        if self.status == OperationStatus.COMPLETED:
            return True
        if self._paired.is_set():
            if self._inner.test():
                self.complete(self._inner.retcode)
                return True
            return False
        if time.monotonic() >= self._deadline and self.claim():
            return self._timeout_fire()
        return False


def _classify_runtime_error(e: Exception) -> int:
    """Map an XLA/runtime exception onto the closest sticky error bits
    (the TPU path cannot set bits from inside a compiled program the way
    the firmware engines do, so host-visible failures are classified at
    completion time)."""
    msg = str(e).lower()
    if "resource_exhausted" in msg or "out of memory" in msg or "oom" in msg:
        return int(ErrorCode.DMA_SIZE_ERROR)
    if "deadline" in msg or "timeout" in msg or "timed out" in msg:
        return int(ErrorCode.DMA_TIMEOUT_ERROR
                   | ErrorCode.RECEIVE_TIMEOUT_ERROR)
    return int(ErrorCode.DMA_INTERNAL_ERROR)


def _is_ready(x) -> bool:
    try:
        return x.is_ready()
    except AttributeError:
        return True
