"""QoS machinery: weighted fair queueing over predicted cost, strict
priority classes, preemption at program boundaries.

The quantum the scheduler arbitrates is one SequenceProgram dispatch —
exactly the granularity the interference certifier proves
order-equivalent (any interleaving of a certified set == its serial
composition), so reordering dispatches for fairness can never change a
result. Within a priority class the queue is start-time weighted fair
queueing (SFQ) over PREDICTED seconds (timing.predict_prepared — the
calibrated cost the admission control already priced the entry at):

    S(e) = max(V, F_prev(tenant))     # start tag at enqueue
    F(e) = S(e) + cost_s / weight     # finish tag; F_prev := F(e)

dispatch picks the eligible head with the smallest finish tag and
advances the class's virtual time V to the dispatched entry's start
tag. Long-run dispatched cost per backlogged tenant then tracks its
weight share (tests/test_scheduler.py pins the ratio). Across
classes priority is STRICT: class 0 drains before class 1 sees the
link; preemption happens at program boundaries because selection
re-runs before every dispatch (an arriving class-0 entry wins the next
boundary; nothing ever interrupts a dispatched program mid-flight —
there is no certified notion of "half a program").

Eligibility is a caller-supplied predicate: the scheduler passes the
concurrency discipline (an entry conflicting with an in-flight program
is skipped this round, i.e. serial-fallback entries wait for their
conflicts to drain while clean entries overtake them).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Iterable

from .tenant import Tenant


@dataclasses.dataclass
class QueueEntry:
    """One queued program dispatch."""

    tenant: str
    priority: int
    program: Any  # SequenceProgram (or any .run(**kwargs) handle)
    footprint: Any  # the program's ProgramFootprint
    cost_s: float  # predicted seconds (the WFQ currency)
    seq: int  # global FIFO tiebreak
    run_kwargs: dict = dataclasses.field(default_factory=dict)
    start_tag: float = 0.0
    finish_tag: float = 0.0
    # signatures this entry may NOT overlap with (non-clean pairwise
    # verdicts at admission time -> serial fallback)
    conflicts: frozenset = frozenset()
    submitted_t: float = 0.0


class FairQueue:
    """One priority class's SFQ state: per-tenant FIFOs + virtual time.
    Not thread-safe — the scheduler serializes access under its lock."""

    def __init__(self) -> None:
        self.virtual_time = 0.0
        self._fifos: dict[str, deque[QueueEntry]] = {}

    def push(self, tenant: Tenant, entry: QueueEntry) -> None:
        entry.start_tag = max(self.virtual_time, tenant.finish_tag)
        entry.finish_tag = (entry.start_tag
                            + entry.cost_s / tenant.weight)
        tenant.finish_tag = entry.finish_tag
        self._fifos.setdefault(entry.tenant, deque()).append(entry)

    def pop_best(self, eligible: Callable[[QueueEntry], bool]
                 ) -> QueueEntry | None:
        """Remove and return the eligible head with the smallest
        (finish tag, seq); None when no head is eligible. Heads only:
        within a tenant the FIFO order is part of the program's
        semantics (its dispatches may carry state between runs)."""
        best: QueueEntry | None = None
        for fifo in self._fifos.values():
            if not fifo:
                continue
            head = fifo[0]
            if not eligible(head):
                continue
            if (best is None
                    or (head.finish_tag, head.seq)
                    < (best.finish_tag, best.seq)):
                best = head
        if best is None:
            return None
        self._fifos[best.tenant].popleft()
        self.virtual_time = max(self.virtual_time, best.start_tag)
        return best

    def __len__(self) -> int:
        return sum(len(f) for f in self._fifos.values())

    def queued_cost(self) -> float:
        return sum(e.cost_s for f in self._fifos.values() for e in f)

    def entries(self) -> Iterable[QueueEntry]:
        for fifo in self._fifos.values():
            yield from fifo
