"""The detect -> exclude -> re-synthesize -> re-certify -> hot-swap loop.

At production scale a wedged peer is routine, not exceptional (the
ACCL+ operational report's dominant pain is exactly the post-dispatch
hang), and this repo has had every ingredient except the loop itself:
deadlines from the model (``resilience.deadline``), schedules as data
(the hop-DAG IR), a generator over arbitrary worlds
(``synthesis.search`` + the ring constructors), and the full certifier
stack (semantics ACCL501-504 + exhaustive-interleaving modelcheck
ACCL205-207).  :class:`ResilienceManager` is the loop:

  1. **detect** — deadline-miss verdicts stream in (``record_miss``);
     a retry/backoff budget distinguishes a transient straggler (the
     drift sentinel's department) from a dead peer, so the expensive
     membership change is paid only when retries keep missing;
  2. **exclude** — the suspect leaves the live set (suspect named by
     the verdict's straggler attribution, or by silence: the one live
     rank that never reported the wave every survivor reported);
  3. **re-plan** — a recovery schedule over the surviving P-1 world:
     the committed synthesized library / ``synthesis.search`` where
     the survivor world has entries (power-of-two worlds), else the
     ring constructors (any world extent) — schedules are data, so
     both land in the same certifiable form;
  4. **re-certify** — the winner runs the EXISTING prove stack
     (semantic certification against its declared collective + the
     canonical protocol simulation + the exhaustive-interleaving model
     checker; zero new checker code).  An uncertified recovery plan is
     NEVER installed: :class:`UncertifiedRecoveryError` is a loud
     failure, because shipping an unproven schedule to a cluster that
     just lost a rank is how one outage becomes two;
  5. **hot-swap** — ``install`` publishes the certified plan under the
     manager lock with a bumped generation; executors consult
     ``current_plan``/``generation`` at DISPATCH BOUNDARIES (between
     calls / between sequence dispatches), so in-flight programs drain
     on the old membership and the next dispatch runs the new one.

The SCCL prove-don't-test posture is what makes step 5 safe without a
validation soak: the recovery plan that was never run before is
*proven* to compute its declared collective before the first dispatch.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

from ..constants import Operation, ReduceFunction, TuningParams
from ..descriptor import CallOptions
from .deadline import DeadlineMissed, DeadlinePolicy


@dataclasses.dataclass(frozen=True)
class IntegrityFault:
    """Structured verdict for a LOSSY link: the suspect's frames are
    arriving-but-damaged (the observers' wire-health counters show CRC
    drops / retransmits / nack round-trips climbing), so the transport's
    reliability sublayer is absorbing the fault below this layer — the
    wrong response is a ~1 s certified reconfiguration.  Raised (as a
    recorded verdict, like :class:`DeadlineMissed`) instead of consuming
    the dead-rank retry budget; only a genuinely dark wire escalates to
    the exclude→replan path (docs/resilience.md escalation policy)."""

    op: str
    count: int
    suspect_rank: int | None
    crc_drops: int = 0
    dup_drops: int = 0
    retransmits: int = 0
    retx_misses: int = 0
    nack_round_trips: int = 0
    elapsed_s: float = 0.0
    post_mortem: dict | None = None

    def verdict(self) -> dict[str, Any]:
        """JSON-ready rendering (logs)."""
        out: dict[str, Any] = {
            "kind": "integrity_fault",
            "op": self.op,
            "count": self.count,
            "crc_drops": self.crc_drops,
            "dup_drops": self.dup_drops,
            "retransmits": self.retransmits,
            "retx_misses": self.retx_misses,
            "nack_round_trips": self.nack_round_trips,
            "elapsed_s": self.elapsed_s,
        }
        if self.suspect_rank is not None:
            out["suspect_rank"] = self.suspect_rank
        out["post_mortem_spans"] = (len(self.post_mortem.get("spans", []))
                                    if self.post_mortem else 0)
        return out

    def __str__(self) -> str:
        sus = (f" suspect r{self.suspect_rank};"
               if self.suspect_rank is not None else "")
        return (f"IntegrityFault: {self.op} count={self.count};{sus} "
                f"lossy link absorbed below the resilience layer "
                f"(crc_drops={self.crc_drops} dup_drops={self.dup_drops} "
                f"retransmits={self.retransmits} "
                f"nack_rtt={self.nack_round_trips}) — no reconfiguration")


class UncertifiedRecoveryError(RuntimeError):
    """A candidate recovery plan failed re-certification — refusing to
    install it is the whole point (loud failure, never a silent
    degrade to an unproven schedule)."""

    def __init__(self, message: str, diagnostics: tuple = ()):
        self.diagnostics = tuple(diagnostics)
        lines = [message]
        lines += [f"  {d}" for d in self.diagnostics]
        super().__init__("\n".join(lines))


@dataclasses.dataclass(frozen=True)
class RetryBudget:
    """How long a suspect stays a *straggler* before it is a *corpse*:
    ``max_retries`` re-attempts, each preceded by an exponential
    backoff (transient congestion clears; a dead peer keeps missing),
    before the manager recommends exclusion and the reconfiguration
    cost is paid."""

    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0

    def delay_s(self, attempt: int) -> float:
        return self.backoff_base_s * self.backoff_factor ** max(attempt, 0)


@dataclasses.dataclass(frozen=True)
class RecoveryPlan:
    """One certified recovery schedule over a survivor world.

    ``survivors`` are GLOBAL rank ids (the membership of the recovery
    communicator); ``world`` their count; ``plan`` the XLA-tier Plan
    selection resolved for the survivor world (``synth_key`` set when
    the committed synthesized library serves the cell);
    ``certificate`` records exactly which proofs ran clean — a plan
    object without a clean certificate cannot be constructed through
    ``ResilienceManager.replan``."""

    op: str
    survivors: tuple[int, ...]
    world: int
    count: int
    source: str  # "synthesized" | "ring"
    plan: Any
    synth_key: str = ""
    certificate: dict = dataclasses.field(default_factory=dict)
    generation: int = 0


class ResilienceManager:
    """Membership + recovery-plan state machine (module docstring).

    Thread-safe: verdicts arrive from whatever thread drove the failed
    wait; installs happen under the same lock the readers take."""

    def __init__(self, world: int, *, policy: DeadlinePolicy | None = None,
                 budget: RetryBudget | None = None,
                 rx_buf_bytes: int = 4096,
                 max_eager_size: int = 4096,
                 tuning: TuningParams | None = None,
                 integrity_budget: int = 3):
        self.world = int(world)
        self.policy = policy
        self.budget = budget if budget is not None else RetryBudget()
        self.rx_buf_bytes = int(rx_buf_bytes)
        self.max_eager_size = int(max_eager_size)
        self.tuning = tuning if tuning is not None else TuningParams.default()
        self._mu = threading.Lock()
        self._live: tuple[int, ...] = tuple(range(self.world))
        self._attempts: dict[int | None, int] = {}
        self._misses: list[DeadlineMissed] = []
        self._current: RecoveryPlan | None = None
        self._generation = 0
        # wire-health evidence (the stats2 surface): last snapshot per
        # OBSERVER rank + the lossy-link verdicts that never became
        # reconfigurations. integrity_budget bounds how many CONSECUTIVE
        # lossy verdicts one suspect may bank before assess_miss stops
        # crediting the transport and walks the dead-rank budget anyway:
        # the wire deltas are world-global, so a rank that dies while
        # OTHER links are lossy would otherwise classify lossy forever —
        # a livelock with no path to the certified reconfiguration.
        # Reset by note_recovery (a suspect whose retries succeed was a
        # genuinely lossy link doing its job).
        self.integrity_budget = int(integrity_budget)
        self._wire_snapshots: dict[int, dict] = {}
        self._integrity_faults: list[IntegrityFault] = []
        self._integrity_streak: dict[int | None, int] = {}
        # facade shapes whose first (possibly compiling) call has been
        # seen — observe_call's warm-up exemption
        self._warmed_shapes: set[tuple] = set()

    # -- state -------------------------------------------------------------

    @property
    def live_ranks(self) -> tuple[int, ...]:
        with self._mu:
            return self._live

    @property
    def generation(self) -> int:
        with self._mu:
            return self._generation

    @property
    def current_plan(self) -> RecoveryPlan | None:
        """The installed recovery plan, read at dispatch boundaries."""
        with self._mu:
            return self._current

    @property
    def misses(self) -> tuple[DeadlineMissed, ...]:
        with self._mu:
            return tuple(self._misses)

    # -- detect ------------------------------------------------------------

    def record_miss(self, miss: DeadlineMissed) -> str:
        """Feed one deadline-miss verdict; returns the recommended
        action: ``"retry"`` while the suspect's budget lasts (caller
        sleeps ``retry_delay_s()`` then re-attempts), ``"exclude"``
        once it is exhausted (the suspect is a corpse, pay the
        reconfiguration)."""
        with self._mu:
            self._misses.append(miss)
            key = miss.suspect_rank
            n = self._attempts.get(key, 0) + 1
            self._attempts[key] = n
            return "retry" if n <= self.budget.max_retries else "exclude"

    # -- escalation policy: lossy link vs dead rank ------------------------

    @property
    def integrity_faults(self) -> tuple[IntegrityFault, ...]:
        with self._mu:
            return tuple(self._integrity_faults)

    def observe_wire_health(self, rank: int, stats: dict) -> dict:
        """Feed one OBSERVER rank's wire-health counter snapshot
        (``EmuRank.wire_stats()`` / ``TPUDevice.wire_stats()``; the
        telemetry ``wire_health_report`` rows carry the same dicts) and
        return the delta since that rank's previous snapshot.  The
        deltas are the escalation policy's evidence: survivors watching
        a LOSSY suspect show repair activity (CRC drops, retransmits,
        nack round-trips) climbing; survivors watching a DEAD one show
        silence."""
        with self._mu:
            prev = self._wire_snapshots.get(rank, {})
            delta = {k: int(v) - int(prev.get(k, 0))
                     for k, v in stats.items()
                     if isinstance(v, (int, float))}
            self._wire_snapshots[rank] = dict(stats)
        return delta

    @staticmethod
    def classify_wire_delta(delta: dict | None) -> str:
        """``"lossy"`` when the delta window shows fault-REPAIR activity
        (the transport is absorbing damage: any of
        ``telemetry.export.WIRE_FAULT_KEYS`` moved), else ``"dark"`` —
        frames are not arriving damaged, they are not arriving at all,
        which is what a dead rank's silence looks like to a survivor."""
        from ..telemetry.export import WIRE_FAULT_KEYS

        if not delta:
            return "dark"
        return ("lossy"
                if any(int(delta.get(k, 0)) > 0 for k in WIRE_FAULT_KEYS)
                else "dark")

    def assess_miss(self, miss: DeadlineMissed,
                    wire_delta: dict | None = None) -> str:
        """The escalation decision for one deadline miss, wire-health
        aware (docs/resilience.md decision tree): a LOSSY delta raises
        a structured :class:`IntegrityFault` (flight-recorder
        post-mortem carried over from the miss) and returns
        ``"integrity"`` — the transport's retransmit budget is doing
        its job, the dead-rank retry budget is NOT consumed and no
        reconfiguration is recommended; a DARK delta falls through to
        :meth:`record_miss`'s retry/exclude budget.

        The lossy credit is BOUNDED per suspect (``integrity_budget``
        consecutive verdicts, reset by :meth:`note_recovery`): wire
        deltas are world-global evidence, so a rank that dies while
        other links are lossy would otherwise bank IntegrityFaults
        forever and the certified reconfiguration would never be
        reached — past the budget the miss walks the dead-rank
        retry/exclude path even under a lossy classification."""
        if self.classify_wire_delta(wire_delta) == "lossy":
            with self._mu:
                streak = self._integrity_streak.get(
                    miss.suspect_rank, 0) + 1
                self._integrity_streak[miss.suspect_rank] = streak
            if streak > self.integrity_budget:
                return self.record_miss(miss)
            d = wire_delta or {}
            fault = IntegrityFault(
                op=miss.op, count=miss.count,
                suspect_rank=miss.suspect_rank,
                crc_drops=int(d.get("crc_drops", 0)),
                dup_drops=int(d.get("dup_drops", 0)),
                retransmits=int(d.get("retx_sent", 0)),
                retx_misses=int(d.get("retx_miss", 0)),
                nack_round_trips=int(d.get("nack_rx", 0)),
                elapsed_s=miss.elapsed_s,
                post_mortem=miss.post_mortem)
            with self._mu:
                self._integrity_faults.append(fault)
                self._misses.append(miss)
            return "integrity"
        return self.record_miss(miss)

    def retry_delay_s(self, suspect_rank: int | None = None) -> float:
        with self._mu:
            return self.budget.delay_s(
                self._attempts.get(suspect_rank, 1) - 1)

    def note_recovery(self, suspect_rank: int | None = None) -> None:
        """A retry SUCCEEDED: the suspect was a transient straggler,
        not a corpse — its budget resets (the sentinel, not the
        recovery loop, owns chronic slowness), and so does its
        lossy-credit streak (a lossy link that keeps recovering is the
        transport doing its job, not a masked death)."""
        with self._mu:
            self._attempts.pop(suspect_rank, None)
            self._integrity_streak.pop(suspect_rank, None)

    def reset_warmup(self) -> None:
        """Forget the facade warm-up exemptions — call when compiled
        programs were invalidated (``ACCL.soft_reset`` does): the next
        dispatch of every shape recompiles, and timing it against a
        wire deadline would flag a healthy world."""
        with self._mu:
            self._warmed_shapes.clear()

    def attribute_silent(self, reporters) -> int | None:
        """Straggler attribution by SILENCE: the one live rank that
        never reported the wave every other survivor reported is the
        suspect (a dead peer produces no verdicts — absence is the
        signal).  None unless exactly one rank is silent."""
        with self._mu:
            silent = [r for r in self._live if r not in set(reporters)]
        return silent[0] if len(silent) == 1 else None

    # -- exclude -----------------------------------------------------------

    def exclude(self, rank: int) -> tuple[int, ...]:
        """Remove a dead rank from the live set; returns the
        survivors. At least two members must remain (a 1-rank
        'collective' needs no recovery plan — and losing quorum is an
        operator problem, not a schedule problem)."""
        with self._mu:
            if rank not in self._live:
                raise ValueError(f"rank {rank} is not live ({self._live})")
            survivors = tuple(r for r in self._live if r != rank)
            if len(survivors) < 2:
                raise ValueError(
                    f"excluding rank {rank} leaves {survivors}: below "
                    "the 2-rank floor a recovery plan is meaningless")
            self._live = survivors
            self._attempts.pop(rank, None)
            return survivors

    # -- re-plan + re-certify ----------------------------------------------

    def replan(self, op: Operation = Operation.allreduce, *,
               count: int, elem_bytes: int = 4,
               function: ReduceFunction = ReduceFunction.SUM,
               ) -> RecoveryPlan:
        """Build and CERTIFY a recovery schedule over the current
        survivor world.  The survivor world is dense (communicator-
        relative ranks 0..P'-1; the membership mapping to global ranks
        lives in the returned ``survivors`` — exactly what a recovery
        communicator's rank table encodes).  Selection: the committed
        synthesized library where a certified entry's committed
        winning window covers the (op, world, payload) cell, else the
        ring constructors (any world extent).  EVERY candidate —
        library entries included — re-runs the full prove stack here
        before the plan object exists; failure raises
        :class:`UncertifiedRecoveryError` and nothing is installed."""
        from ..sequencer import synthesis
        from ..sequencer.plan import Algorithm, Plan, Protocol, \
            select_algorithm

        with self._mu:
            survivors = self._live
            generation = self._generation + 1
        new_world = len(survivors)
        source, synth_key = "ring", ""
        plan: Any = None
        # 1. committed library: a certified entry whose window covers
        # the payload on the survivor world (power-of-two worlds ship
        # w2/4/8/16 entries)
        key = synthesis.select_entry(op, new_world, count * elem_bytes)
        if key is not None:
            plan = Plan(Protocol.EAGER, Algorithm.SYNTHESIZED, count, 1,
                        synth_key=key)
            source, synth_key = "synthesized", key
        if plan is None:
            plan = select_algorithm(
                op, count, elem_bytes, new_world,
                max_eager_size=self.max_eager_size,
                eager_rx_buf_size=self.rx_buf_bytes,
                tuning=self.tuning)
        certificate = self._certify(op, plan, new_world, count,
                                    function, source, synth_key)
        return RecoveryPlan(op=op.name, survivors=survivors,
                            world=new_world, count=count, source=source,
                            plan=plan, synth_key=synth_key,
                            certificate=certificate,
                            generation=generation)

    def _certify(self, op: Operation, plan: Any, world: int, count: int,
                 function: ReduceFunction, source: str,
                 synth_key: str) -> dict:
        """The existing prove stack over the candidate's hop-DAG: lift
        (or regenerate, for library entries) the schedule, certify the
        contribution sets against the declared collective
        (ACCL501-504), simulate the canonical protocol run, and
        model-check every legal match order (ACCL205-207). Returns the
        certificate record; raises on ANY diagnostic."""
        from ..analysis import semantics
        from ..analysis.hopdag import rank_programs, validate_order
        from ..analysis.linter import SequenceLinter
        from ..analysis.protocol import simulate
        from ..sequencer import synthesis

        opts = CallOptions(scenario=op, count=count,
                           function=int(function))
        opts.data_type = _f32()
        if source == "synthesized":
            spec = synthesis.entry_for_key(synth_key).spec
            cert_count = synthesis.canonical_count(spec)
            dag = synthesis.instantiate(
                spec, cert_count,
                func="max" if function == ReduceFunction.MAX else "sum")
            cert_opts = dataclasses.replace(opts, count=cert_count)
        else:
            cert_count = count
            dag = semantics.lift_call(opts, plan, world)
            cert_opts = opts
        diags = list(validate_order(dag))
        diags += semantics.certify(
            dag, semantics.collective_spec(cert_opts, world), op.name)
        programs = rank_programs(dag)
        diags += simulate(programs, blocking_sends=False)
        if not diags:
            diags += SequenceLinter(world).check_interleavings(programs)
        if diags:
            raise UncertifiedRecoveryError(
                f"recovery plan ({source}, {op.name} w{world}) failed "
                f"re-certification — NOT installed:",
                tuple(diags))
        return {
            "op": op.name,
            "world": world,
            "count": cert_count,
            "source": source,
            "synth_key": synth_key,
            "checks": ["order", "semantics(ACCL501-504)",
                       "protocol-simulate",
                       "modelcheck(ACCL205-207)"],
            "diagnostics": 0,
        }

    # -- hot-swap ----------------------------------------------------------

    def install(self, plan: RecoveryPlan) -> int:
        """Publish a certified recovery plan at a dispatch boundary:
        the generation bump is what tells executors mid-drain that the
        NEXT dispatch runs the new membership.  Only plans built by
        ``replan`` carry a certificate; installing anything without
        one is refused (the loud-failure contract end to end)."""
        if not plan.certificate or plan.certificate.get("diagnostics") != 0:
            raise UncertifiedRecoveryError(
                "refusing to install a recovery plan without a clean "
                "certificate")
        with self._mu:
            if tuple(plan.survivors) != self._live:
                raise ValueError(
                    f"plan membership {plan.survivors} does not match "
                    f"the live set {self._live}: replan after the "
                    "membership change, not before")
            self._current = plan
            self._generation += 1
            self._attempts.clear()
            return self._generation

    # -- degraded mode -----------------------------------------------------

    def degraded_live_ranks(self) -> tuple[int, ...]:
        """The survivor set in the ORIGINAL world's rank space — the
        ``live_ranks`` argument of ``allreduce(mode="live_subset")``:
        the full-world program keeps running (dead ranks relay masked
        zeros) and the certifier proves exactly whose data is in the
        answer."""
        with self._mu:
            return self._live

    # -- the facade seam (armed deadlines on eager calls) ------------------

    def observe_call(self, op: Operation, count: int, elem_bytes: int,
                     elapsed_s: float) -> DeadlineMissed | None:
        """Post-completion deadline check for a facade call (the
        ``ACCL.arm_resilience`` seam): with a policy armed, a call
        that outlived its derived deadline produces the structured
        verdict (flight-recorder post-mortem attached) and is
        recorded; the call itself already completed — nothing is
        raised on this path.

        The FIRST observation of each (op, count, elem_bytes) shape is
        a warm-up, never checked: the facade's wall time includes the
        one-time XLA compile of a fresh program shape (orders of
        magnitude over any wire deadline), and flagging it would
        freeze a spurious post-mortem and burn retry budget on a
        perfectly healthy world.  Deadlines are a steady-state claim."""
        if self.policy is None:
            return None
        if op in (Operation.config, Operation.nop, Operation.copy,
                  Operation.combine):
            return None  # no wire, no deadline
        shape = (op, int(count), int(elem_bytes))
        with self._mu:
            if shape not in self._warmed_shapes:
                self._warmed_shapes.add(shape)
                return None
        miss = self.policy.check(op, count, elem_bytes, elapsed_s)
        if miss is not None:
            self.record_miss(miss)
        return miss


def _f32():
    from ..constants import DataType

    return DataType.float32
