"""Self-healing collectives: predicted deadlines, rank-death detection,
and certified live reconfiguration (docs/resilience.md).

Three pieces, each reusing an existing proof or measurement surface
instead of growing a parallel one:

  - ``deadline``: per-call deadlines DERIVED from ``timing.predict``
    under the calibrated link plus the drift sentinel's residual
    tolerance band — the fixed ``RECEIVE_TIMEOUT`` posture replaced by
    the model the framework already trusts for selection.  A miss is a
    structured :class:`DeadlineMissed` verdict with the flight-recorder
    post-mortem attached and per-rank straggler attribution naming the
    suspect.

  - ``manager``: :class:`ResilienceManager` runs the
    detect -> exclude -> re-synthesize -> re-certify -> hot-swap loop —
    a retry/backoff budget distinguishes transient stragglers from dead
    peers; the recovery schedule over the surviving P-1 world comes
    from the committed synthesized library or the ring constructors and
    is re-proven through the EXISTING semantics + modelcheck stack
    before install (an uncertified recovery plan is a loud
    :class:`UncertifiedRecoveryError`, never a silent degrade).

  - the certified degraded mode rides the facade:
    ``ACCL.allreduce(mode="live_subset", live_ranks=...)`` declares the
    surviving-contributor set in the descriptor, the schedule masks
    non-members to exact zeros at the source, and the semantic
    certifier proves exactly which ranks' data is in the answer (the
    ACCL501-proven alltoallv drop-to-zeros posture generalized to the
    reduction).

Below the loop sits the transport's reliability sublayer (CRC32C
frames + selective retransmit, ``native/src/runtime.cpp``): transient
wire faults are repaired in microseconds at the transport, and the
manager's escalation policy (``assess_miss`` over per-rank wire-health
deltas) tells a LOSSY link — frames arriving-but-damaged, a structured
:class:`IntegrityFault`, no reconfiguration — from a genuinely DARK
one, which alone walks the retry→exclude→replan path.

Checked end to end on the native emulated world by
``tests/test_resilience.py`` (a mid-stream rank death recovers within
the bounded retry+reconfigure budget, bitwise against the survivor
oracle) and ``tests/test_fault_injection.py`` (the seeded
loss/corrupt/dup/reorder fuzz stays bitwise with no call surfacing an
error).
"""

from .deadline import (  # noqa: F401
    DEFAULT_DEADLINE_FLOOR_S,
    DEFAULT_UNARMED_REFERENCE,
    DeadlineMissed,
    DeadlineMissedError,
    DeadlinePolicy,
    NativeDeadlineGuard,
)
from .manager import (  # noqa: F401
    IntegrityFault,
    RecoveryPlan,
    ResilienceManager,
    RetryBudget,
    UncertifiedRecoveryError,
)

__all__ = [
    "DEFAULT_DEADLINE_FLOOR_S",
    "DEFAULT_UNARMED_REFERENCE",
    "DeadlineMissed",
    "DeadlineMissedError",
    "DeadlinePolicy",
    "IntegrityFault",
    "NativeDeadlineGuard",
    "RecoveryPlan",
    "ResilienceManager",
    "RetryBudget",
    "UncertifiedRecoveryError",
]
