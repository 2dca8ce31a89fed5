"""SequenceLinter: the static gate in front of ScheduleCompiler.

Orchestrates the analysis passes over a recorded descriptor batch —
structural validation (validate.py), dataflow hazards over the
canonical renaming (hazards.py), overlap-slot liveness (slots.py), the
semantic certifier (semantics.py, when per-step Plans are available),
and optionally the deep per-rank protocol interpretation (protocol.py)
— and returns the combined diagnostic list, most severe first.

The shallow passes are pure Python over the descriptors
(microseconds). The semantic pass (ACCL501-504) is per-batch
LINEAR — one contribution-set abstract evaluation per step, verdicts
cached by static signature — so it rides the DEFAULT tier; only
pathologically segmented shapes defer to the CLI/CI sweep (see
semantics._within_inband_budget). The deep tier abstractly evaluates
every step's schedule body under jax tracing (about the cost of a
second trace) and then model-checks the batch's per-rank hop programs
over EVERY legal match order (modelcheck.py — ACCL205/206/207,
budgeted): it is OFF in the in-band default (`lint="error"`), opted
into per batch with `lint="deep"`, and ON in the corpus CLI
(tools/accl_lint.py) and the schedule-conformance tests, where its job
— proving the shipping schedules deadlock-free under all interleavings
— earns the cost.
"""

from __future__ import annotations

from ..constants import Operation
from .diagnostics import Diagnostic, enforce
from .hazards import analyze_dataflow
from .slots import check_slots, ring_slot_timeline
from .validate import validate_steps

__all__ = ["SequenceLinter", "lint_sequence"]

_SEV_ORDER = {"error": 0, "warning": 1}


class SequenceLinter:
    """One linter per (world, lowering flags) configuration.

    `use_pallas_ring`/`pallas_ring_overlap` mirror the ScheduleCompiler
    flags of the communicator context the batch will compile under, so
    the slot model matches what the lowering would actually launch.
    """

    def __init__(
        self,
        world: int,
        *,
        use_pallas_ring: bool = False,
        pallas_ring_overlap: bool = True,
        deep: bool = False,
        axis_name: str = "ccl",
        arith_table: dict | None = None,
        budget=None,
    ):
        self.world = world
        self.use_pallas_ring = use_pallas_ring
        self.pallas_ring_overlap = pallas_ring_overlap
        self.deep = deep
        self.axis_name = axis_name
        # the ACTIVE arithmetic configuration (compression-lane pairing,
        # ACCL406): None = the shipping default table
        self.arith_table = arith_table
        # exploration caps for the deep tier's interleaving checker
        # (modelcheck.Budget); None = the shipping default
        self.budget = budget

    def ring_steps(self, steps) -> frozenset[int]:
        """Indices that lower to the slot-keyed pallas ring — the same
        predicate sequence.py uses to insert cross-step ordering."""
        if not self.use_pallas_ring:
            return frozenset()
        return frozenset(
            k for k, o in enumerate(steps)
            if o.scenario == Operation.allreduce)

    def lint(
        self,
        steps,
        plans=None,
        *,
        buffer_widths: dict[int, int] | None = None,
        persistent_addrs: frozenset[int] | set[int] = frozenset(),
        operand_reads=None,
    ) -> list[Diagnostic]:
        """Run the configured passes over a batch of CallOptions.
        `plans` (one Plan per step, from plan.select_algorithm) enables
        the deep protocol pass; `buffer_widths` (address -> registered
        element width) enables the static underflow check;
        `persistent_addrs` declares device-resident state buffers whose
        partial-width refresh pattern waives ACCL101; `operand_reads`
        (per step, (address, width) of its consumer's operand buffers)
        adds those whole-buffer reads (see hazards.analyze_dataflow)."""
        steps = list(steps)
        diags = validate_steps(steps, self.world)
        if any(d.code in ("ACCL404", "ACCL403") for d in diags):
            # structurally not a sequence: downstream passes would
            # misread the batch
            return self._sorted(diags)
        diags += analyze_dataflow(
            steps, self.world,
            ring_steps=self.ring_steps(steps),
            buffer_widths=buffer_widths,
            arith_table=self.arith_table,
            persistent_addrs=persistent_addrs,
            operand_reads=operand_reads,
        )
        if self.use_pallas_ring:
            timeline = ring_slot_timeline(
                steps, self.world, overlap=self.pallas_ring_overlap)
            diags += check_slots(timeline)
        if plans is not None and not any(
                d.severity == "error" for d in diags):
            # semantic certification (ACCL501-504): per-batch LINEAR —
            # one contribution-set abstract evaluation per step, cached
            # by static signature — so it rides the DEFAULT tier, not
            # just the deep one. Pathologically segmented shapes defer
            # to the CLI/CI conformance sweep (semantics budget).
            # Warning-severity findings (WAR/WAW advisories) do NOT
            # skip it: under lint="error" those batches still dispatch,
            # so they still need their answer certified.
            from .semantics import check_batch_semantics

            diags += check_batch_semantics(
                steps, plans, self.world, self.axis_name,
                arith_table=self.arith_table)
        if self.deep and plans is not None and not diags:
            from .protocol import (
                batch_programs_from_hops,
                check_hops,
                rank_programs_from_hops,
                simulate,
                trace_schedule_hops,
            )

            # per-step interpretation (interpret_schedule's passes,
            # inlined so each schedule body is abstractly traced ONCE —
            # the trace is the deep tier's dominant cost, and the batch
            # checker below reuses the same hops)
            hops_per_step = []
            for k, (opts, plan) in enumerate(zip(steps, plans)):
                hops = trace_schedule_hops(opts, plan, self.world,
                                           self.axis_name)
                hops_per_step.append(hops)
                step_diags = check_hops(hops, self.world)
                if not step_diags:  # malformed perms confuse the matcher
                    step_diags = simulate(
                        rank_programs_from_hops(hops, self.world),
                        blocking_sends=False)
                for d in step_diags:
                    diags.append(Diagnostic(d.code, d.message, step=k,
                                            rank=d.rank))
            if not diags:
                # exhaustive-interleaving tier: certify the BATCH's
                # per-rank hop programs over every legal match order
                # (per-step interpretation above saw one step and one
                # schedule at a time). The checker's static router skips
                # exploration when the matching is provably unique.
                programs = batch_programs_from_hops(hops_per_step,
                                                    self.world)
                diags += self.check_interleavings(programs)
        return self._sorted(diags)

    def check_interleavings(self, programs) -> list[Diagnostic]:
        """Model-check per-rank event programs over every legal match
        order (the deep tier's last pass; also the entry point
        tools/accl_lint.py uses for `rank_programs` fixtures). The
        static pin analysis routes: a batch where every endpoint has a
        provably unique partner (which subsumes the no-MatchNote case —
        a multi-eligible recv is never uniquely pinned) admits exactly
        one matching and skips exploration outright."""
        from .modelcheck import (
            Budget,
            diagnose_programs,
            statically_deterministic,
        )

        if statically_deterministic(programs):
            return []
        return diagnose_programs(programs,
                                 budget=self.budget or Budget())

    @staticmethod
    def _sorted(diags: list[Diagnostic]) -> list[Diagnostic]:
        return sorted(diags,
                      key=lambda d: (_SEV_ORDER[d.severity], d.code,
                                     d.step if d.step is not None else -1))


def lint_sequence(steps, world: int, *, mode: str = "error",
                  plans=None, buffer_widths=None, **kw) -> list[Diagnostic]:
    """One-shot convenience: lint a batch and apply `mode`
    (`"error"` raises LintError on error-severity findings, `"warn"`
    logs, `"off"` skips, `"deep"` adds the exhaustive-interleaving
    tier and enforces like `"error"`). Returns the diagnostics either
    way."""
    if mode == "off":
        return []
    if mode == "deep":
        kw.setdefault("deep", True)
    diags = SequenceLinter(world, **kw).lint(
        steps, plans, buffer_widths=buffer_widths)
    enforce(diags, mode)
    return diags
