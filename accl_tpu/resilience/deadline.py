"""Per-call deadlines from the calibrated timing model.

The fixed ``RECEIVE_TIMEOUT`` posture (one configured number for every
call) is the reference driver's: honest for a hardware data plane with
one message size, wrong for a framework whose calibrated cost model
already knows what every planned call *should* take.  This module
replaces the constant with a DERIVED deadline:

    deadline(call) = predicted(call) * (1 + tolerance(op)) + floor_s

where ``predicted`` is ``timing.predict`` under the calibrated link for
the plan the shared selection rules resolve (the same estimate every
traced span carries), and ``tolerance`` reuses the drift sentinel's
band semantics (``telemetry.metrics.DriftSentinel``): a reference
median relative residual — the calibration's honest error in the
current regime, armed from measured spans — widened by the same
``max(ref * band_factor, ref + band_floor)`` rule the sentinel's
band-leave verdict uses.  A call that outlives its band-widened
prediction is not "slow": it is OUT OF MODEL, the same claim the
sentinel makes about a regime change — except here it is actionable
per call, while the data is still recoverable.  ``floor_s`` is an
absolute scheduling-noise floor so microsecond predictions never arm
microsecond deadlines.

A miss produces a structured :class:`DeadlineMissed` verdict — op,
count, predicted vs elapsed, the sticky retcode if the executor
produced one, straggler attribution naming the suspect — with the
flight-recorder post-mortem attached (``recorder.on_deadline_miss``
freezes the span rings on a HOST-side verdict, not only on sticky
native retcodes: a silent hang inside the old tolerance window used to
leave no artifact).

:class:`NativeDeadlineGuard` applies the policy to native EmuRank
calls: it points the rank's in-call recv deadline (``set_timeout``) at
the model-derived value and bounds the host-side wait the same way, so
a wedged peer surfaces as a typed :class:`DeadlineMissedError` within
one band-widened prediction instead of a fixed constant later.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

from ..constants import (
    CfgFunc,
    Operation,
    TuningParams,
    error_code_to_string,
)
from ..descriptor import CallOptions

# the sentinel's band constants are the one source of band semantics
from ..telemetry.metrics import (
    DEFAULT_SENTINEL_BAND_FACTOR,
    DEFAULT_SENTINEL_BAND_FLOOR,
)
from ..telemetry.export import median as _median

# unarmed tolerance reference: before any measured residuals exist the
# policy assumes the model may be off by its own magnitude (rel err
# 1.0) — deliberately loose, never a constant timeout in disguise; arm
# a measured reference to tighten it
DEFAULT_UNARMED_REFERENCE = 1.0
# absolute floor under every deadline: host scheduling noise exists at
# any payload size, so a microsecond prediction never arms a
# microsecond deadline
DEFAULT_DEADLINE_FLOOR_S = 0.05


@dataclasses.dataclass(frozen=True)
class DeadlineMissed:
    """Structured verdict for one missed per-call deadline."""

    op: str
    count: int
    predicted_s: float
    deadline_s: float
    elapsed_s: float
    rank: int | None = None
    retcode: int = 0
    suspect_rank: int | None = None
    attribution: str = ""
    post_mortem: dict | None = None

    def verdict(self) -> dict[str, Any]:
        """JSON-ready rendering (logs)."""
        out: dict[str, Any] = {
            "kind": "deadline_missed",
            "op": self.op,
            "count": self.count,
            "predicted_s": self.predicted_s,
            "deadline_s": self.deadline_s,
            "elapsed_s": self.elapsed_s,
        }
        if self.rank is not None:
            out["rank"] = self.rank
        if self.retcode:
            out["retcode"] = self.retcode
            out["retcode_str"] = error_code_to_string(self.retcode)
        if self.suspect_rank is not None:
            out["suspect_rank"] = self.suspect_rank
            out["attribution"] = self.attribution
        out["post_mortem_spans"] = (len(self.post_mortem.get("spans", []))
                                    if self.post_mortem else 0)
        return out

    def __str__(self) -> str:
        sus = (f"; suspect r{self.suspect_rank} ({self.attribution})"
               if self.suspect_rank is not None else "")
        rc = (f"; sticky {error_code_to_string(self.retcode)}"
              if self.retcode else "")
        return (f"DeadlineMissed: {self.op} count={self.count} elapsed "
                f"{self.elapsed_s * 1e3:.1f} ms > deadline "
                f"{self.deadline_s * 1e3:.1f} ms (predicted "
                f"{self.predicted_s * 1e3:.1f} ms){rc}{sus}")


class DeadlineMissedError(RuntimeError):
    """Typed raise carrying the structured verdict (guard waits)."""

    def __init__(self, miss: DeadlineMissed):
        self.miss = miss
        super().__init__(str(miss))


class DeadlinePolicy:
    """Derive per-call deadlines from the calibrated link + a residual
    tolerance band (module docstring for the formula).

    ``link`` is a ``timing.LinkParams`` (the calibrated fit the
    predictions and the drift sentinel already ride).  ``aggregate``
    selects the serialized-host cost shape (the emulator tier's
    calibration regime — the default, matching the native worlds the
    guard drives) vs the critical path.  Deadlines are cached per
    (op, count, elem_bytes): the armed hot path is a dict hit.
    """

    def __init__(self, link: Any, world: int, *,
                 rx_buf_bytes: int = 4096,
                 max_eager_size: int = 4096,
                 tuning: TuningParams | None = None,
                 aggregate: bool = True,
                 band_factor: float = DEFAULT_SENTINEL_BAND_FACTOR,
                 band_floor: float = DEFAULT_SENTINEL_BAND_FLOOR,
                 floor_s: float = DEFAULT_DEADLINE_FLOOR_S):
        if link is None:
            raise ValueError(
                "DeadlinePolicy needs a calibrated LinkParams — without "
                "one a 'derived' deadline would be a constant in "
                "disguise (calibrate_from_trace / default_link)")
        self.link = link
        self.world = int(world)
        self.rx_buf_bytes = int(rx_buf_bytes)
        self.max_eager_size = int(max_eager_size)
        self.tuning = tuning if tuning is not None else TuningParams.default()
        self.aggregate = bool(aggregate)
        self.band_factor = float(band_factor)
        self.band_floor = float(band_floor)
        self.floor_s = float(floor_s)
        self._reference: dict[str, float] = {}
        self._cache: dict[tuple, tuple[float, float]] = {}

    # -- tolerance band (the sentinel's semantics) -------------------------

    def arm_reference(self, op: str | Operation,
                      median_rel_err: float) -> None:
        """Pin an op's reference residual — the calibration's honest
        median |pred-meas|/meas in the current regime (the number the
        drift sentinel arms its frozen band from)."""
        self._reference[self._op_name(op)] = float(median_rel_err)
        self._cache.clear()

    def arm_from_residuals(self, op: str | Operation,
                           residuals: list[float]) -> float:
        """Arm from measured residual samples (their median)."""
        ref = float(_median(list(residuals)))
        self.arm_reference(op, ref)
        return ref

    def tolerance(self, op: str | Operation) -> float:
        """Relative tolerance above the prediction: the sentinel's
        ``max(ref * band_factor, ref + band_floor)`` widening of the
        armed reference (an unarmed op uses the deliberately loose
        DEFAULT_UNARMED_REFERENCE)."""
        ref = self._reference.get(self._op_name(op),
                                  DEFAULT_UNARMED_REFERENCE)
        return max(ref * self.band_factor, ref + self.band_floor)

    @staticmethod
    def _op_name(op: str | Operation) -> str:
        return op.name if isinstance(op, Operation) else str(op)

    @staticmethod
    def _op_enum(op: str | Operation) -> Operation:
        return op if isinstance(op, Operation) else Operation[str(op)]

    # -- prediction + deadline ---------------------------------------------

    def _predict_deadline(self, op: Operation, count: int,
                          elem_bytes: int) -> tuple[float, float]:
        key = (op, int(count), int(elem_bytes))
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        from ..sequencer.plan import select_algorithm
        from ..sequencer.timing import predict

        plan = select_algorithm(
            op, int(count), int(elem_bytes), self.world,
            max_eager_size=self.max_eager_size,
            eager_rx_buf_size=self.rx_buf_bytes,
            tuning=self.tuning)
        pred = predict(self.link, op, plan, int(count), int(elem_bytes),
                       self.world, rx_buf_bytes=self.rx_buf_bytes,
                       aggregate=self.aggregate)
        dl = pred * (1.0 + self.tolerance(op)) + self.floor_s
        self._cache[key] = (pred, dl)
        return pred, dl

    def predict_and_deadline(self, op: str | Operation, count: int,
                             elem_bytes: int = 4) -> tuple[float, float]:
        """(predicted_s, deadline_s) in one cached lookup — the armed
        hot path's single call (the <3% overhead budget is measured
        with this on every dispatch)."""
        return self._predict_deadline(self._op_enum(op), count,
                                      elem_bytes)

    def predict_s(self, op: str | Operation, count: int,
                  elem_bytes: int = 4) -> float:
        return self._predict_deadline(self._op_enum(op), count,
                                      elem_bytes)[0]

    def deadline_s(self, op: str | Operation, count: int,
                   elem_bytes: int = 4) -> float:
        return self._predict_deadline(self._op_enum(op), count,
                                      elem_bytes)[1]

    def deadline_ms(self, op: str | Operation, count: int,
                    elem_bytes: int = 4) -> int:
        return max(int(self.deadline_s(op, count, elem_bytes) * 1e3), 1)

    # -- the miss verdict --------------------------------------------------

    def check(self, op: str | Operation, count: int, elem_bytes: int,
              elapsed_s: float, *, rank: int | None = None,
              retcode: int = 0, suspect_rank: int | None = None,
              attribution: str = "") -> DeadlineMissed | None:
        """Post-hoc deadline check for one completed (or failed) call:
        returns the structured verdict when ``elapsed_s`` exceeded the
        derived deadline (with the flight-recorder post-mortem frozen
        and attached — the host-side dump-on-error trigger), else
        None."""
        pred, dl = self._predict_deadline(self._op_enum(op), count,
                                          elem_bytes)
        if elapsed_s <= dl and not retcode:
            return None
        return self.build_miss(op, count, pred, dl, elapsed_s, rank=rank,
                               retcode=retcode, suspect_rank=suspect_rank,
                               attribution=attribution)

    def build_miss(self, op: str | Operation, count: int,
                   predicted_s: float, deadline_s: float,
                   elapsed_s: float, *, rank: int | None = None,
                   retcode: int = 0, suspect_rank: int | None = None,
                   attribution: str = "") -> DeadlineMissed:
        """Assemble the verdict + fire the flight recorder's host-side
        dump (a silent hang leaves an artifact even with no sticky
        native retcode)."""
        from ..telemetry import recorder

        name = self._op_name(op)
        post = recorder.on_deadline_miss(
            name, rank=rank, count=count, predicted_s=predicted_s,
            deadline_s=deadline_s, elapsed_s=elapsed_s,
            suspect_rank=suspect_rank, retcode=retcode)
        return DeadlineMissed(
            op=name, count=int(count), predicted_s=predicted_s,
            deadline_s=deadline_s, elapsed_s=elapsed_s, rank=rank,
            retcode=int(retcode), suspect_rank=suspect_rank,
            attribution=attribution, post_mortem=post)


class NativeDeadlineGuard:
    """Model-derived deadlines applied to native EmuRank calls.

    ``arm(rank, op, count)`` points the rank's in-call recv deadline
    (the ``set_timeout`` config word — the fixed RECEIVE_TIMEOUT
    register of the reference) at the policy's derived value, so the
    sequencer itself times a stalled op out at the band-widened
    prediction.  ``wait(rank, handle, ...)`` bounds the host-side wait
    the same way (with a slack multiple for completion delivery) and
    converts EITHER failure shape — the native sticky RECEIVE_TIMEOUT
    or a host-side wall overrun — into a typed
    :class:`DeadlineMissedError` carrying the structured verdict (with
    the flight-recorder post-mortem attached).  A completing call past
    its deadline also produces a verdict (reported to the manager)
    without raising: the data arrived, the model was wrong — that is
    the drift sentinel's department, not the recovery loop's.
    """

    # host wait bound = slack * deadline: the native in-call deadline
    # fires first (it IS the deadline); the host bound is the backstop
    # for a sequencer that cannot even reach its own timeout check
    HOST_WAIT_SLACK = 3.0

    def __init__(self, policy: DeadlinePolicy, manager: Any = None):
        self.policy = policy
        self.manager = manager

    def arm(self, emu_rank: Any, op: str | Operation, count: int,
            elem_bytes: int = 4) -> int:
        """Configure the rank's native recv deadline from the model;
        returns the applied milliseconds."""
        ms = self.policy.deadline_ms(op, count, elem_bytes)
        emu_rank.call(CallOptions(scenario=Operation.config,
                                  function=int(CfgFunc.set_timeout),
                                  count=ms))
        return ms

    def _notify(self, miss: DeadlineMissed) -> DeadlineMissed:
        if self.manager is not None:
            self.manager.record_miss(miss)
        return miss

    def wait(self, emu_rank: Any, handle: int, op: str | Operation,
             count: int, elem_bytes: int = 4) -> DeadlineMissed | None:
        """Deadline-bounded completion of one started native call.
        Returns None on an in-deadline success, the verdict (without
        raising) on a LATE success, and raises
        :class:`DeadlineMissedError` on a wedged/failed call."""
        from ..constants import ACCLError, ErrorCode

        pol = self.policy
        # ONE cached lookup per wait: this is the armed hot path, paid
        # on every dispatch
        pred, dl = pol.predict_and_deadline(op, count, elem_bytes)
        t0 = time.perf_counter()
        try:
            emu_rank.wait(handle,
                          timeout_ms=max(int(dl * 1e3 * self.HOST_WAIT_SLACK),
                                         1))
        except TimeoutError:
            elapsed = time.perf_counter() - t0
            miss = pol.build_miss(op, count, pred, dl, elapsed,
                                  rank=emu_rank.rank)
            raise DeadlineMissedError(self._notify(miss)) from None
        except ACCLError as e:
            elapsed = time.perf_counter() - t0
            if e.retcode & int(ErrorCode.RECEIVE_TIMEOUT_ERROR):
                miss = pol.build_miss(
                    op, count, pred, dl, elapsed, rank=emu_rank.rank,
                    retcode=e.retcode)
                raise DeadlineMissedError(self._notify(miss)) from None
            raise  # a non-timeout sticky error is not a deadline event
        elapsed = time.perf_counter() - t0
        if elapsed <= dl:
            return None
        miss = pol.build_miss(op, count, pred, dl, elapsed,
                              rank=emu_rank.rank)
        self._notify(miss)
        return miss

    def run(self, emu_rank: Any, opts: CallOptions, *, op0=None, op1=None,
            res=None, elem_bytes: int = 4) -> DeadlineMissed | None:
        """start + deadline-bounded wait of one descriptor."""
        h = emu_rank.start(opts, op0=op0, op1=op1, res=res)
        return self.wait(emu_rank, h, opts.scenario, opts.count,
                         elem_bytes)
