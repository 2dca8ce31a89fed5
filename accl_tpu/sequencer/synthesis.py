"""Schedule synthesis: search the hop-DAG space, certify winners, ship
them as first-class algorithms.

The prove side already exists: `analysis.semantics` certifies that a
hop-DAG computes its declared collective (ACCL501-504) and
`analysis.modelcheck` certifies its hop programs race/deadlock-free over
every legal match order (ACCL205-207). This module is the inversion of
those checkers into a GENERATOR (ROADMAP item 1; SCCL's k-step hop
formulation, arxiv 2008.08708): given (operation, world size, payload,
link parameters), enumerate candidate schedules as hop-DAGs, prune by
latency/bandwidth dominance, certify every survivor with the existing
stack — an uncertified candidate is discarded loudly, never shipped —
score the rest with `timing`-style alpha-beta prediction, and cache the
winners as JSON hop-DAGs in the committed `synthesized/` library, where
`plan.select_algorithm` can pick them behind measured crossover
registers and `lowering.ScheduleCompiler` compiles them like any other
algorithm.

Search space
------------
Candidates are ROTATIONALLY SYMMETRIC k-step schedules over the
fully-connected per-step topology one `lax.ppermute` expresses: a
candidate is a sequence of rotation distances (d_1 .. d_k), each step a
full-ring permutation `rank -> rank + d_i`. Rank symmetry is the
symmetry pruning rule: the whole orbit of rank-relabelings collapses to
one candidate, and the compiled program is one rank-relative chain (no
per-rank branching). Families:

  exchange   allreduce: every rank sends its running PARTIAL to
             rank+d_i and folds the arrival from rank-d_i; valid iff
             the 2^k subset sums of the distances are pairwise distinct
             mod world (each input contributes exactly once — the
             double-count/partial classes are pruned here, and the
             certifier re-proves it). k = log2(world) steps: the
             latency-optimal end of the frontier (recursive doubling is
             the (1, 2, 4, ...) member).
  doubling   allgather: every rank relays ALL chunks held so far;
             same validity condition; k steps moving (P-1) chunks.
  halving    reduce_scatter: the time-reversal dual of `doubling` —
             responsibility sets halve each step, partials fold at the
             receiver.
  rs_ag      allreduce as halving reduce_scatter + doubling allgather
             over the same distance set: 2k steps, 2(P-1)/P payload
             bytes — the bandwidth-optimal point at log latency
             (recursive halving-doubling is a member).

Each family also admits an int8 blockwise-quantized wire variant
(`wire="int8"` currently generated for `exchange`): hops carry
(codes, scales) through encode/decode nodes backed by the real
`ops.compression` reference, so certification and numeric execution see
exactly what the compiled program runs.

These families cover the latency-bandwidth frontier the hand-written
zoo lacks (the zoo's eager ring is the bandwidth end; nothing
hand-written occupies the log-step region on the XLA tier). Non-power-
of-two worlds admit no valid candidate in these families and simply
yield an empty library — the search never ships a schedule it cannot
prove.

Factored topologies (pod scale)
-------------------------------
TPU pod slices are not uniform rings: a world factors as
inner x outer (L devices per slice on the fast tier, P slices across
the slow tier), and a hop moves along exactly ONE axis of that 2-D
torus. The tiered families (`t_<inner>_<outer>`, allreduce) search
this factored space with TIER-ANNOTATED hops: every hop carries which
tier it crosses (`hop_layout`), is charged against that tier's
`timing.TierLinks` entry (`tiered_phase_costs` /
`predict_spec_tiered` — the `timing.hier_phase_costs` accounting,
generalized to arbitrary hop sequences), and compiles to that tier's
ring permutation (the `ring=(pos, perm)` embedding `hierarchical.
RankMap` provides; outer-major global ranks, g = outer*L + inner).
Members compose one inner reduce-scatter, one outer shard-allreduce,
and one inner allgather — HiCCL's multiply/factor shape — from
per-tier family choices:

  inner  `lg`    log-step halving/doubling over the inner distance
                 tuple (power-of-two L)
         `ring`  the bandwidth-optimal one-chunk-per-hop ring
                 (any L; distance d with gcd(d, L) = 1)
  outer  `exchange` / `rs_ag`  the flat families over the 1/L shard
         `ring`  ring RS + ring AG over the shard

The hand-written striped `HIER_RS_AR_AG` composition is exactly the
`t_ring_ring` member at one stripe — a POINT in this space the search
rediscovers (it scores identical to the composition's serial form) and
then beats with the log-step members wherever per-message latency
matters. Tiered entries arbitrate against the striped composition by
predicted time inside the HIER_ALLREDUCE_MIN_COUNT window
(plan.select_algorithm), never through a separate register.

Scaling the enumeration (w16-w256)
----------------------------------
Distance tuples are enumerated by a branch-and-bound DFS
(`_valid_distance_tuples`): a prefix is pruned the moment its subset
sums collide, so the first valid tuple at w256 costs ~k*world set ops
instead of the lexicographic-combinations scan's millions. Candidates
are scored with the alpha-beta model BEFORE any certification is paid
(`search` scores, beam-prunes to the `beam` best predicted advantages,
then certifies only the survivors): the score is the model's EXACT
serial cost of the emitted DAG — phases never overlap, each hop is
charged to precisely the link it crosses — so pruning by it is
admissible: certification only rejects candidates, never improves
their score, and the kept set always contains the model's best
certifiable candidate. Every survivor still pays the FULL existing
stack (semantics ACCL501-504 + modelcheck ACCL205-207); an uncertified
winner is a loud discard, never shipped.

Everything here is deterministic: no RNG, candidates enumerated in
lexicographic order, so the same inputs always produce the same winner
DAG (pinned by tests/test_synthesis.py).
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Any, Callable, Iterator

from ..constants import (
    QUANT_BLOCK_ELEMS,
    QUANT_SCALE_BYTES,
    STREAM_SEG_BYTES,
    Operation,
    ReduceFunction,
)
from ..analysis.diagnostics import Diagnostic
from ..analysis.hopdag import (
    CONST,
    DATA,
    SCALES,
    HopDag,
    Node,
    Piece,
    Value,
    concat_values,
    from_json,
    slice_value,
    to_json,
)

__all__ = [
    "SynthSpec",
    "SynthesisError",
    "instantiate",
    "certify_spec",
    "enumerate_candidates",
    "enumerate_tiered_candidates",
    "search",
    "cost_shape",
    "predict_spec",
    "tiered_phase_costs",
    "predict_spec_tiered",
    "hop_layout",
    "lower_dag",
    "lower_plan",
    "library",
    "library_dir",
    "select_entry",
    "clear_library_cache",
    "hand_written_best",
    "hand_written_tiered_best",
    "SIZE_GRID",
    "SIZE_GRID_LAT",
    "grid_for",
]

# the ops a synthesized schedule can implement today
SYNTH_OPS = (Operation.allreduce, Operation.allgather,
             Operation.reduce_scatter)

# predicted-score grid: payload bytes per (world, size) cell
SIZE_GRID = tuple(1 << k for k in range(10, 25, 2))  # 1 KB .. 16 MB

# the latency grid: every power of two across the 1-64 KiB decode
# regime, where the alpha term — not bytes — is the product. Entries
# searched on this grid carry grid="lat" and a "_lat" key suffix; they
# live behind SYNTH_LATENCY_MAX_COUNT, never the std synth registers,
# so a minimum-step schedule that only wins the small-payload floor
# cannot widen the bandwidth-calibrated windows.
SIZE_GRID_LAT = tuple(1 << k for k in range(10, 17))  # 1 KB .. 64 KB


def grid_for(spec: "SynthSpec") -> tuple[int, ...]:
    """The scoring grid a spec's window is defined over — the ONE
    resolution rule shared by search/--export, verify_library, and
    timing.tuning_crossovers."""
    return SIZE_GRID_LAT if spec.grid == "lat" else SIZE_GRID


class SynthesisError(Exception):
    """A candidate the generator/lowering cannot handle (never converted
    into a silent pass: callers fail loudly or discard the candidate)."""


class _NotRankSymmetric(SynthesisError):
    """The DAG is well-formed but its per-rank programs are not a strict
    rotation of rank 0's — the one condition under which `lower_dag` may
    fall back to the generic masked lowering. Structural malformation
    (cross-rank dataflow, out-of-range ranks) stays a plain
    SynthesisError: the generic lowering would compile it to a WRONG
    program, so it must never be caught as a fallback signal."""


@dataclasses.dataclass(frozen=True)
class SynthSpec:
    """One synthesized schedule family member: enough to regenerate its
    hop-DAG deterministically at any payload size. `key` names the
    library entry (and rides Plan.synth_key into the XLA cache key).

    `tiers=(inner_world, outer_world)` marks a FACTORED-topology member
    (family `t_<inner>_<outer>`): `distances` are then the inner-axis
    tuple and `outer_distances` the outer-axis one, and every hop is
    tier-annotated (`hop_layout`) — charged to its `TierLinks` entry
    and compiled to its RankMap ring permutation. `tiers=()` is the
    flat single-ring space."""

    key: str
    op: str  # "allreduce" | "allgather" | "reduce_scatter"
    world: int
    family: str  # exchange | doubling | halving | rs_ag | t_<ik>_<ok>
    distances: tuple[int, ...]
    wire: str = ""  # "" = payload dtype on the wire, "int8" = quantized
    tiers: tuple[int, ...] = ()  # (inner_world, outer_world) | () flat
    outer_distances: tuple[int, ...] = ()
    grid: str = "std"  # "std" = SIZE_GRID window, "lat" = SIZE_GRID_LAT

    @property
    def scenario(self) -> Operation:
        return Operation[self.op]

    def to_json(self) -> dict:
        d: dict[str, Any] = {
            "key": self.key, "op": self.op, "world": self.world,
            "family": self.family, "distances": list(self.distances),
        }
        if self.wire:
            d["wire"] = self.wire
        if self.tiers:
            d["tiers"] = list(self.tiers)
            d["outer_distances"] = list(self.outer_distances)
        if self.grid != "std":
            d["grid"] = self.grid
        return d

    @classmethod
    def from_json(cls, d: dict) -> "SynthSpec":
        return cls(key=str(d["key"]), op=str(d["op"]),
                   world=int(d["world"]), family=str(d["family"]),
                   distances=tuple(int(x) for x in d["distances"]),
                   wire=str(d.get("wire", "")),
                   tiers=tuple(int(x) for x in d.get("tiers", ())),
                   outer_distances=tuple(
                       int(x) for x in d.get("outer_distances", ())),
                   grid=str(d.get("grid", "std")))


def _spec_key(op: str, world: int, family: str,
              distances: tuple[int, ...], wire: str) -> str:
    d = "_".join(str(x) for x in distances)
    w = f"_{wire}" if wire else ""
    return f"{op}_w{world}_{family}_d{d}{w}"


def _tiered_key(world: int, tiers: tuple[int, int], family: str,
                di: tuple[int, ...], do: tuple[int, ...]) -> str:
    L, P = tiers
    return (f"allreduce_w{world}_t{L}x{P}_{family[2:]}"
            f"_d{'_'.join(map(str, di))}_o{'_'.join(map(str, do))}")


def _tier_kinds(family: str) -> tuple[str, str]:
    """('lg'|'ring', 'exchange'|'rs_ag'|'ring') of a tiered family."""
    if not family.startswith("t_"):
        raise SynthesisError(f"not a tiered family: {family!r}")
    ik, ok = family[2:].split("_", 1)
    if ik not in ("lg", "ring") or ok not in ("exchange", "rs_ag",
                                              "ring"):
        raise SynthesisError(f"unknown tiered family {family!r}")
    return ik, ok


# ---------------------------------------------------------------------------
# Validity: the exact-cover condition shared by every family
# ---------------------------------------------------------------------------


def _subset_sums_distinct(world: int, distances: tuple[int, ...]) -> bool:
    """True iff the 2^k subset sums of `distances` are pairwise distinct
    mod `world` (and therefore, with 2^k == world, cover Z_world exactly
    once). This is the generator-side pruning of the wrong-result
    classes: a collision is a double-count (ACCL503) and a shortfall a
    missing contribution (ACCL502) — the certifier re-proves the same
    property on the emitted DAG, so the pruning can never silently
    diverge from the proof."""
    sums = {0}
    for d in distances:
        shifted = {(s + d) % world for s in sums}
        if sums & shifted:
            return False
        sums |= shifted
    return len(sums) == world


def coverage_sets(world: int,
                  distances: tuple[int, ...]) -> list[set[int]]:
    """S_0 .. S_k with S_i the relative offsets reachable after step i
    (S_0 = {0}, S_i = S_{i-1} u (S_{i-1} + d_i))."""
    sets = [{0}]
    for d in distances:
        cur = sets[-1]
        sets.append(cur | {(s + d) % world for s in cur})
    return sets


def _valid_distance_tuples(world: int, k: int) -> Iterator[tuple[int, ...]]:
    """Strictly-increasing k-tuples whose 2^k subset sums are pairwise
    distinct mod `world`, in lexicographic order — enumerated by
    branch-and-bound DFS: a prefix dies the moment its sums collide, so
    the first valid tuple at w256 costs ~k*world set extensions instead
    of the millions of complete tuples a combinations scan would build
    and re-check (the scaling lever for w16-w256 enumeration)."""

    def rec(start: int, sums: frozenset, prefix: tuple[int, ...],
            ) -> Iterator[tuple[int, ...]]:
        if len(prefix) == k:
            yield prefix
            return
        for d in range(start, world):
            shifted = {(s + d) % world for s in sums}
            if sums & shifted:
                continue  # collision: every extension collides too
            yield from rec(d + 1, frozenset(sums | shifted),
                           prefix + (d,))

    yield from rec(1, frozenset({0}), ())


def _first_valid_tuple(world: int) -> tuple[int, ...] | None:
    """The lexicographically first valid k=log2(world) tuple (the
    dominance representative: valid tuples within a family share the
    per-step byte profile, so they are cost-identical)."""
    if world < 2 or world & (world - 1):
        return None
    k = world.bit_length() - 1
    return next(_valid_distance_tuples(world, k), None)


# ---------------------------------------------------------------------------
# DAG generation (rank-symmetric by construction)
# ---------------------------------------------------------------------------


class _Builder:
    """Emit nodes in a strict per-step, rank-major order so position
    p*world + r is rank r's p-th node — the layout `lower_dag`'s
    rotational-symmetry extraction relies on."""

    def __init__(self, world: int):
        self.world = world
        self.nodes: list[Node] = []

    def emit_round(self, make: Callable[[int, int], Node]) -> list[int]:
        """One rank-major round: `make(rank, id)` for every rank;
        returns the new node ids (index by rank)."""
        ids = []
        for r in range(self.world):
            nid = len(self.nodes)
            self.nodes.append(make(r, nid))
            ids.append(nid)
        return ids


class _FlatAxis:
    """The single-ring geometry: positions ARE global ranks, a hop at
    distance d is the full-ring rotation g -> g + d."""

    tier = ""

    def __init__(self, world: int):
        self.world = world
        self.nranks = world

    def pos(self, g: int) -> int:
        return g

    def peer(self, g: int, d: int) -> int:
        return (g + d) % self.world


class _InnerAxis:
    """The fast tier of an outer-major (g = outer*L + inner) factored
    world: a hop rotates every slice's inner ring in lockstep — the
    global pairs are exactly `hierarchical.RankMap.inner_perm(d)`."""

    tier = "inner"

    def __init__(self, L: int, P: int):
        self.world = L
        self.nranks = L * P
        self._L = L

    def pos(self, g: int) -> int:
        return g % self._L

    def peer(self, g: int, d: int) -> int:
        return g - g % self._L + (g % self._L + d) % self._L


class _OuterAxis:
    """The slow tier: a hop rotates every inner row's outer ring in
    lockstep — the global pairs of `RankMap.outer_perm(d)`."""

    tier = "outer"

    def __init__(self, L: int, P: int):
        self.world = P
        self.nranks = L * P
        self._L = L

    def pos(self, g: int) -> int:
        return g // self._L

    def peer(self, g: int, d: int) -> int:
        return ((g // self._L + d) % self.world) * self._L + g % self._L


def _scales_len(n: int) -> int:
    return max(1, math.ceil(n / QUANT_BLOCK_ELEMS))


def _exchange_core(b: _Builder, axis, distances: tuple[int, ...],
                   count: int, func: str, acc: list[Value],
                   hop_base: int, wire: str) -> tuple[list[Value], int]:
    """allreduce exchange along one axis: every rank sends its running
    partial `acc[g]` distance d down the axis and folds the arrival
    from distance -d. Returns (final partials, next free hop). The flat
    family and the tiered outer-`exchange` phase share this emitter —
    only the axis geometry differs."""
    w = axis.world
    hop = hop_base
    for d in distances:
        if wire == "int8":
            enc = b.emit_round(lambda g, i: Node(
                id=i, kind="encode", rank=g, length=count,
                value=acc[g],
                scales_len=_scales_len(count), dtype="int8"))
            b.emit_round(lambda g, i: Node(
                id=i, kind="send", rank=g, length=count,
                value=(Piece(count, enc[g]),), hop=hop,
                peer=axis.peer(g, d)))
            b.emit_round(lambda g, i: Node(
                id=i, kind="send", rank=g, length=_scales_len(count),
                value=(Piece(_scales_len(count), enc[g], 0, SCALES),),
                hop=hop + 1, peer=axis.peer(g, d)))
            rq = b.emit_round(lambda g, i: Node(
                id=i, kind="recv", rank=g, length=count, hop=hop,
                peer=axis.peer(g, -d)))
            rs = b.emit_round(lambda g, i: Node(
                id=i, kind="recv", rank=g, length=_scales_len(count),
                hop=hop + 1, peer=axis.peer(g, -d)))
            dec = b.emit_round(lambda g, i: Node(
                id=i, kind="decode", rank=g, length=count,
                value=(Piece(count, rq[g]),),
                value2=(Piece(_scales_len(count), rs[g]),)))
            ids = b.emit_round(lambda g, i: Node(
                id=i, kind="combine", rank=g, length=count,
                value=acc[g],
                value2=(Piece(count, dec[g]),), func=func))
            acc = [(Piece(count, ids[g]),) for g in range(axis.nranks)]
            hop += 2
        else:
            b.emit_round(lambda g, i: Node(
                id=i, kind="send", rank=g, length=count,
                value=acc[g], hop=hop, peer=axis.peer(g, d)))
            rv = b.emit_round(lambda g, i: Node(
                id=i, kind="recv", rank=g, length=count, hop=hop,
                peer=axis.peer(g, -d)))
            ids = b.emit_round(lambda g, i: Node(
                id=i, kind="combine", rank=g, length=count,
                value=acc[g],
                value2=(Piece(count, rv[g]),), func=func))
            acc = [(Piece(count, ids[g]),) for g in range(axis.nranks)]
            hop += 1
    return acc, hop


def _exchange_dag(spec: SynthSpec, count: int, func: str) -> HopDag:
    """allreduce: acc[r] folds the arrival from r - d_i each step."""
    w = spec.world
    b = _Builder(w)
    args = b.emit_round(lambda r, i: Node(
        id=i, kind="arg", rank=r, length=count, arg=0, dtype="float32"))
    acc: list[Value] = [(Piece(count, args[r]),) for r in range(w)]
    acc, _hop = _exchange_core(b, _FlatAxis(w), spec.distances, count,
                               func, acc, 0, spec.wire)
    outputs: tuple[Value, ...] = tuple(acc[r] for r in range(w))
    return HopDag(world=w, n_in=1, in_elems=count, out_elems=count,
                  nodes=tuple(b.nodes), outputs=outputs)


def _doubling_core(b: _Builder, axis, distances: tuple[int, ...],
                   count: int, held: list[dict[int, Value]],
                   hop_base: int) -> tuple[list[dict[int, Value]], int]:
    """allgather doubling along one axis: each rank relays EVERY chunk
    held so far; `held[g]` maps origin axis POSITION -> that origin's
    chunk Value on rank g. Returns (full held maps, next free hop)."""
    w = axis.world
    sets = coverage_sets(w, distances)
    for step, d in enumerate(distances):
        rel = sorted(sets[step])  # canonical message layout
        msg_len = len(rel) * count

        def payload(g: int) -> Value:
            out: tuple[Piece, ...] = ()
            for s in rel:
                out = out + held[g][(axis.pos(g) - s) % w]
            return out

        b.emit_round(lambda g, i: Node(
            id=i, kind="send", rank=g, length=msg_len,
            value=payload(g), hop=hop_base + step, peer=axis.peer(g, d)))
        rv = b.emit_round(lambda g, i: Node(
            id=i, kind="recv", rank=g, length=msg_len,
            hop=hop_base + step, peer=axis.peer(g, -d)))
        for g in range(axis.nranks):
            for j, s in enumerate(rel):
                origin = (axis.pos(g) - d - s) % w
                held[g][origin] = (
                    Piece(count, rv[g], j * count),)
    return held, hop_base + len(distances)


def _doubling_dag(spec: SynthSpec, count: int) -> HopDag:
    """allgather: each rank relays every chunk held so far; held sets
    are `coverage_sets` in relative offsets (held chunk = rank - s)."""
    w = spec.world
    b = _Builder(w)
    args = b.emit_round(lambda r, i: Node(
        id=i, kind="arg", rank=r, length=count, arg=0, dtype="float32"))
    # held[r][origin] = Value holding origin's chunk on rank r
    held: list[dict[int, Value]] = [
        {r: (Piece(count, args[r]),)} for r in range(w)]
    held, _hop = _doubling_core(b, _FlatAxis(w), spec.distances, count,
                                held, 0)
    outputs = []
    for r in range(w):
        v: tuple[Piece, ...] = ()
        for origin in range(w):
            v = v + held[r][origin]
        outputs.append(v)
    return HopDag(world=w, n_in=1, in_elems=count,
                  out_elems=w * count, nodes=tuple(b.nodes),
                  outputs=tuple(outputs))


def _halving_core(b: _Builder, axis, distances: tuple[int, ...],
                  count: int, func: str,
                  part: list[dict[int, Value]],
                  hop_base: int) -> tuple[list[dict[int, Value]], int]:
    """reduce_scatter halving along one axis: position p hands off
    partials for chunks p + d + A_i to position p + d each step;
    responsibility sets A_i halve (A_i = S_{k-i} of the reversed
    distance sequence). `part[g]` maps ABSOLUTE axis chunk -> partial
    Value; on return only position g's kept chunks remain. Returns
    (part, next free hop)."""
    w = axis.world
    k = len(distances)
    # A_i chain: A_k = {0}; A_{i-1} = A_i u (A_i + d_i)
    A: list[set[int]] = [set() for _ in range(k + 1)]
    A[k] = {0}
    for i in range(k, 0, -1):
        d = distances[i - 1]
        A[i - 1] = A[i] | {(a + d) % w for a in A[i]}
    for i in range(1, k + 1):
        d = distances[i - 1]
        send_rel = sorted((a + d) % w for a in A[i])
        msg_len = len(send_rel) * count

        def payload(g: int) -> Value:
            out: tuple[Piece, ...] = ()
            for a in send_rel:
                out = out + part[g][(axis.pos(g) + a) % w]
            return out

        b.emit_round(lambda g, i_: Node(
            id=i_, kind="send", rank=g, length=msg_len,
            value=payload(g), hop=hop_base + i - 1,
            peer=axis.peer(g, d)))
        rv = b.emit_round(lambda g, i_: Node(
            id=i_, kind="recv", rank=g, length=msg_len,
            hop=hop_base + i - 1, peer=axis.peer(g, -d)))
        # arrival from pos-d carries chunks (pos-d) + send_rel, i.e.
        # pos + a for a = send_rel - d (mod w) — all kept chunks; fold
        # each slice into the kept partial, rank-major per arrival slot
        # so symmetry holds
        arr_rel = [(a - d) % w for a in send_rel]
        for j, a in enumerate(arr_rel):
            ids = b.emit_round(lambda g, i_: Node(
                id=i_, kind="combine", rank=g, length=count,
                value=part[g][(axis.pos(g) + a) % w],
                value2=(Piece(count, rv[g], j * count),), func=func))
            for g in range(axis.nranks):
                part[g][(axis.pos(g) + a) % w] = (Piece(count, ids[g]),)
        # drop handed-off chunks (no longer this position's duty)
        for g in range(axis.nranks):
            part[g] = {c: v for c, v in part[g].items()
                       if (c - axis.pos(g)) % w in A[i]}
    return part, hop_base + k


def _ring_rs_core(b: _Builder, axis, d: int, count: int, func: str,
                  part: list[dict[int, Value]],
                  hop_base: int) -> tuple[list[dict[int, Value]], int]:
    """Bandwidth-optimal ring reduce-scatter along one axis — the
    hand-written ring's structure as a searchable point: w-1 steps each
    moving exactly ONE chunk partial distance d down the axis. At step
    s position p sends its partial of chunk p - s*d and folds the
    arrival into chunk p - (s+1)*d; after w-1 steps position p owns
    chunk p fully reduced (gcd(d, w) = 1 walks the whole ring)."""
    w = axis.world
    hop = hop_base
    for s in range(1, w):
        b.emit_round(lambda g, i: Node(
            id=i, kind="send", rank=g, length=count,
            value=part[g][(axis.pos(g) - s * d) % w], hop=hop,
            peer=axis.peer(g, d)))
        rv = b.emit_round(lambda g, i: Node(
            id=i, kind="recv", rank=g, length=count, hop=hop,
            peer=axis.peer(g, -d)))
        ids = b.emit_round(lambda g, i: Node(
            id=i, kind="combine", rank=g, length=count,
            value=part[g][(axis.pos(g) - (s + 1) * d) % w],
            value2=(Piece(count, rv[g]),), func=func))
        for g in range(axis.nranks):
            part[g][(axis.pos(g) - (s + 1) * d) % w] = (
                Piece(count, ids[g]),)
        hop += 1
    return part, hop


def _ring_ag_core(b: _Builder, axis, d: int, count: int,
                  held: list[dict[int, Value]],
                  hop_base: int) -> tuple[list[dict[int, Value]], int]:
    """Ring allgather along one axis: w-1 steps each relaying the chunk
    received the previous step (at step 1 the own chunk), so every
    position holds every origin after the walk."""
    w = axis.world
    hop = hop_base
    for s in range(1, w):
        b.emit_round(lambda g, i: Node(
            id=i, kind="send", rank=g, length=count,
            value=held[g][(axis.pos(g) - (s - 1) * d) % w], hop=hop,
            peer=axis.peer(g, d)))
        rv = b.emit_round(lambda g, i: Node(
            id=i, kind="recv", rank=g, length=count, hop=hop,
            peer=axis.peer(g, -d)))
        for g in range(axis.nranks):
            held[g][(axis.pos(g) - s * d) % w] = (Piece(count, rv[g]),)
        hop += 1
    return held, hop


def _halving_dag(spec: SynthSpec, count: int, func: str,
                 b: _Builder | None = None,
                 part_in: list[dict[int, Value]] | None = None,
                 hop_base: int = 0) -> tuple[
                     "_Builder", list[dict[int, Value]]]:
    """reduce_scatter wrapper over `_halving_core` on the flat axis;
    returns the builder and per-rank {abs_chunk: partial Value} so
    `rs_ag` can continue the same DAG."""
    w = spec.world
    if b is None:
        b = _Builder(w)
        args = b.emit_round(lambda r, i: Node(
            id=i, kind="arg", rank=r, length=w * count, arg=0,
            dtype="float32"))
        part_in = [
            {c: (Piece(count, args[r], c * count),) for c in range(w)}
            for r in range(w)]
    assert b is not None and part_in is not None
    part, _hop = _halving_core(b, _FlatAxis(w), spec.distances, count,
                               func, part_in, hop_base)
    return b, part


def _reduce_scatter_dag(spec: SynthSpec, count: int, func: str) -> HopDag:
    b, part = _halving_dag(spec, count, func)
    w = spec.world
    outputs = tuple(part[r][r] for r in range(w))
    return HopDag(world=w, n_in=1, in_elems=w * count, out_elems=count,
                  nodes=tuple(b.nodes), outputs=outputs)


def _rs_ag_dag(spec: SynthSpec, count: int, func: str) -> HopDag:
    """allreduce = halving reduce_scatter + doubling allgather over the
    same distance set (payload padded to a world multiple upstream by
    the chunking rule in `instantiate`)."""
    w = spec.world
    if count % w:
        raise SynthesisError(
            f"rs_ag payload must chunk by world ({count} % {w})")
    chunk = count // w
    k = len(spec.distances)
    b, part = _halving_dag(spec, chunk, func, hop_base=0)
    # allgather phase: start from the reduced chunk, doubling relays
    held: list[dict[int, Value]] = [
        {r: part[r][r]} for r in range(w)]
    held, _hop = _doubling_core(b, _FlatAxis(w), spec.distances, chunk,
                                held, k)
    outputs = []
    for r in range(w):
        v: tuple[Piece, ...] = ()
        for origin in range(w):
            v = v + held[r][origin]
        outputs.append(v)
    return HopDag(world=w, n_in=1, in_elems=count, out_elems=count,
                  nodes=tuple(b.nodes), outputs=tuple(outputs))


def _tiered_dag(spec: SynthSpec, count: int, func: str) -> HopDag:
    """Factored-topology allreduce over outer-major global ranks
    (g = outer*L + inner): inner reduce-scatter -> outer allreduce of
    the 1/L shard (the ONLY bytes that ever cross the slow tier) ->
    inner allgather, each phase built from the per-tier family the spec
    names. Every hop moves along exactly one axis of the (L, P) torus —
    the tier annotation `hop_layout` records and the per-tier cost
    accounting charges."""
    L, P = spec.tiers
    w = L * P
    if count % (L * P):
        raise SynthesisError(
            f"{spec.key}: tiered payload must chunk by inner*outer "
            f"({count} % {L * P})")
    cpk = count // L  # one inner chunk == the outer shard
    ik, ok = _tier_kinds(spec.family)
    inner = _InnerAxis(L, P)
    outer = _OuterAxis(L, P)
    b = _Builder(w)
    args = b.emit_round(lambda g, i: Node(
        id=i, kind="arg", rank=g, length=count, arg=0, dtype="float32"))
    part: list[dict[int, Value]] = [
        {c: (Piece(cpk, args[g], c * cpk),) for c in range(L)}
        for g in range(w)]
    hop = 0
    if ik == "ring":
        part, hop = _ring_rs_core(b, inner, spec.distances[0], cpk,
                                  func, part, hop)
    else:
        part, hop = _halving_core(b, inner, spec.distances, cpk, func,
                                  part, hop)
    shard: list[Value] = [part[g][inner.pos(g)] for g in range(w)]
    if ok == "exchange":
        shard, hop = _exchange_core(b, outer, spec.outer_distances,
                                    cpk, func, shard, hop, "")
    else:
        ocpk = cpk // P
        opart: list[dict[int, Value]] = [
            {c: slice_value(shard[g], c * ocpk, ocpk) for c in range(P)}
            for g in range(w)]
        if ok == "ring":
            od = spec.outer_distances[0]
            opart, hop = _ring_rs_core(b, outer, od, ocpk, func,
                                       opart, hop)
            held_o: list[dict[int, Value]] = [
                {outer.pos(g): opart[g][outer.pos(g)]} for g in range(w)]
            held_o, hop = _ring_ag_core(b, outer, od, ocpk, held_o, hop)
        else:  # rs_ag
            opart, hop = _halving_core(b, outer, spec.outer_distances,
                                       ocpk, func, opart, hop)
            held_o = [
                {outer.pos(g): opart[g][outer.pos(g)]} for g in range(w)]
            held_o, hop = _doubling_core(b, outer, spec.outer_distances,
                                         ocpk, held_o, hop)
        shard = [concat_values(*(held_o[g][c] for c in range(P)))
                 for g in range(w)]
    held: list[dict[int, Value]] = [
        {inner.pos(g): shard[g]} for g in range(w)]
    if ik == "ring":
        held, hop = _ring_ag_core(b, inner, spec.distances[0], cpk,
                                  held, hop)
    else:
        held, hop = _doubling_core(b, inner, spec.distances, cpk,
                                   held, hop)
    outputs = tuple(concat_values(*(held[g][c] for c in range(L)))
                    for g in range(w))
    return HopDag(world=w, n_in=1, in_elems=count, out_elems=count,
                  nodes=tuple(b.nodes), outputs=outputs)


def _check_axis_family(spec: SynthSpec, kind: str, axis_world: int,
                       distances: tuple[int, ...], what: str) -> None:
    """Per-tier validity: the log-step families need the exact-cover
    subset-sum condition over THEIR axis; a ring needs one distance
    coprime to the axis extent (the walk must visit every position)."""
    if kind in ("lg", "exchange", "rs_ag"):
        if not _subset_sums_distinct(axis_world, distances):
            raise SynthesisError(
                f"{spec.key}: {what} distances {distances} do not "
                f"cover Z_{axis_world} exactly once — not a valid "
                "schedule")
    else:  # ring
        if len(distances) != 1 or math.gcd(distances[0],
                                           axis_world) != 1:
            raise SynthesisError(
                f"{spec.key}: {what} ring distance {distances} must be "
                f"a single generator of Z_{axis_world}")


def instantiate(spec: SynthSpec, count: int,
                func: str = "sum") -> HopDag:
    """Deterministically regenerate `spec`'s hop-DAG for a concrete
    per-rank element count. The same generator builds the committed
    canonical instance, the fuzz instances and the lowered program's
    source DAG — there is exactly one structure to certify."""
    if count <= 0:
        raise SynthesisError(f"count must be positive, got {count}")
    if spec.tiers:
        L, P = spec.tiers
        if L * P != spec.world or L < 2 or P < 2:
            raise SynthesisError(
                f"{spec.key}: tiers {spec.tiers} do not factor world "
                f"{spec.world}")
        ik, ok = _tier_kinds(spec.family)
        _check_axis_family(spec, ik, L, spec.distances, "inner")
        _check_axis_family(spec, ok, P, spec.outer_distances, "outer")
        return _tiered_dag(spec, count, func)
    if not _subset_sums_distinct(spec.world, spec.distances):
        raise SynthesisError(
            f"{spec.key}: distances {spec.distances} do not cover "
            f"Z_{spec.world} exactly once — not a valid schedule")
    if spec.family == "exchange":
        return _exchange_dag(spec, count, func)
    if spec.family == "doubling":
        return _doubling_dag(spec, count)
    if spec.family == "halving":
        return _reduce_scatter_dag(spec, count, func)
    if spec.family == "rs_ag":
        return _rs_ag_dag(spec, count, func)
    raise SynthesisError(f"unknown family {spec.family!r}")


# canonical counts for the committed/certified instances: big enough to
# exercise multi-chunk layouts, small enough to keep fixtures readable
CANONICAL_COUNT = {"exchange": 64, "doubling": 16, "halving": 16,
                   "rs_ag": 64}


def canonical_count(spec: SynthSpec) -> int:
    if spec.tiers:
        # must chunk by inner*outer (the 2-D torus chunking rule)
        L, P = spec.tiers
        return 8 * L * P
    base = CANONICAL_COUNT[spec.family]
    if spec.family == "rs_ag":
        return max(base, spec.world)  # must chunk by world
    return base


# ---------------------------------------------------------------------------
# Certification: the existing prove stack, candidate by candidate
# ---------------------------------------------------------------------------


def _call_options(spec: SynthSpec, count: int,
                  func: ReduceFunction = ReduceFunction.SUM) -> Any:
    from ..constants import DataType
    from ..descriptor import CallOptions

    return CallOptions(scenario=spec.scenario, count=count,
                       function=int(func), data_type=DataType.float32)


def certify_dag(dag: HopDag, spec: SynthSpec, count: int,
                func: ReduceFunction = ReduceFunction.SUM,
                ) -> list[Diagnostic]:
    """Run one candidate instance through the full prove stack:
    semantic certification (ACCL501-504) against the declared
    collective, the canonical protocol simulation, and the exhaustive-
    interleaving model checker (ACCL205-207). Returns every diagnostic;
    an empty list is the only shippable verdict."""
    from ..analysis import semantics
    from ..analysis.hopdag import rank_programs, validate_order
    from ..analysis.linter import SequenceLinter
    from ..analysis.protocol import simulate

    opts = _call_options(spec, count, func)
    spec_map = semantics.collective_spec(opts, dag.world)
    diags = list(validate_order(dag))
    diags += semantics.certify(dag, spec_map, spec.op)
    programs = rank_programs(dag)
    diags += simulate(programs, blocking_sends=False)
    if not diags:
        diags += SequenceLinter(dag.world).check_interleavings(programs)
    return diags


def certify_spec(spec: SynthSpec,
                 counts: tuple[int, ...] = (),
                 ) -> tuple[bool, list[Diagnostic]]:
    """Certify a spec at its canonical count (and any extra counts).
    False means DISCARD: the caller must not ship the candidate."""
    all_diags: list[Diagnostic] = []
    for count in (canonical_count(spec),) + tuple(counts):
        try:
            dag = instantiate(spec, count)
        except SynthesisError:
            return False, all_diags
        all_diags += certify_dag(dag, spec, count)
        if spec.op == "allreduce" and spec.wire != "int8":
            # MAX folds certify too (idempotent reduction class)
            dag_max = instantiate(spec, count, func="max")
            all_diags += certify_dag(dag_max, spec, count,
                                     ReduceFunction.MAX)
    return not all_diags, all_diags


# ---------------------------------------------------------------------------
# Scoring: alpha-beta prediction of a spec, same posture as timing.py
# ---------------------------------------------------------------------------


def _wire_bytes_per_elem(spec: SynthSpec, elem_bytes: int) -> float:
    if spec.wire == "int8":
        return 1.0 + QUANT_SCALE_BYTES / QUANT_BLOCK_ELEMS
    return float(elem_bytes)


def hop_layout(spec: SynthSpec) -> list[tuple[str, int]]:
    """(tier, axis_distance) per hop channel of a tiered spec, in hop
    order — THE tier annotation of the factored search space: each hop
    is charged against its `TierLinks` entry (`tiered_phase_costs`) and
    compiles to its tier's ring permutation (`lower_plan` cross-checks
    the emitted DAG's send pairs against `RankMap.inner_perm` /
    `outer_perm` at exactly these distances)."""
    if not spec.tiers:
        raise SynthesisError(f"{spec.key} is not a tiered spec")
    L, P = spec.tiers
    ik, ok = _tier_kinds(spec.family)
    inner_hops = ([("inner", spec.distances[0])] * (L - 1)
                  if ik == "ring"
                  else [("inner", d) for d in spec.distances])
    if ok == "exchange":
        outer_hops = [("outer", d) for d in spec.outer_distances]
    elif ok == "rs_ag":
        outer_hops = [("outer", d) for d in spec.outer_distances] * 2
    else:  # ring RS + ring AG
        outer_hops = [("outer", spec.outer_distances[0])] * (2 * (P - 1))
    # the inner allgather mirrors the inner reduce-scatter's hop count
    return inner_hops + outer_hops + inner_hops


def _tiered_step_elems(spec: SynthSpec,
                       count: int) -> list[tuple[str, int]]:
    """(tier, elements-sent-per-rank) per hop of a tiered spec, in hop
    order (count padded up to the inner*outer chunking the DAG
    requires — the same rule `lower_plan` applies)."""
    L, P = spec.tiers
    padded = count + (-count) % (L * P)
    cpk = padded // L
    ik, ok = _tier_kinds(spec.family)
    k_i = len(spec.distances)
    if ik == "ring":
        inner_rs = [cpk] * (L - 1)
        inner_ag = [cpk] * (L - 1)
    else:
        inner_rs = [cpk * (1 << (k_i - i)) // 2 for i in range(k_i)]
        inner_ag = [cpk * (1 << i) for i in range(k_i)]
    if ok == "exchange":
        outer = [cpk] * len(spec.outer_distances)
    else:
        ocpk = cpk // P
        if ok == "ring":
            outer = [ocpk] * (2 * (P - 1))
        else:
            k_o = len(spec.outer_distances)
            outer = ([ocpk * (1 << (k_o - i)) // 2 for i in range(k_o)]
                     + [ocpk * (1 << i) for i in range(k_o)])
    return ([("inner", e) for e in inner_rs]
            + [("outer", e) for e in outer]
            + [("inner", e) for e in inner_ag])


def _step_elems(spec: SynthSpec, count: int) -> list[int]:
    """Per-step elements each rank sends (every rank sends the same —
    rank symmetry). `count` follows the descriptor convention of the
    op: allgather = chunk elems, reduce_scatter = output chunk elems,
    allreduce = payload elems. Tiered specs flatten their per-tier hop
    profile (the single-link fallback `cost_shape` documents)."""
    if spec.tiers:
        return [e for _t, e in _tiered_step_elems(spec, count)]
    w = spec.world
    k = len(spec.distances)
    if spec.family == "exchange":
        return [count] * k
    if spec.family == "doubling":
        return [count * (1 << i) for i in range(k)]
    if spec.family == "halving":
        return [count * (1 << (k - i)) // 2 for i in range(k)]
    if spec.family == "rs_ag":
        chunk = max(count // w, 1)
        rs = [chunk * (1 << (k - i)) // 2 for i in range(k)]
        ag = [chunk * (1 << i) for i in range(k)]
        return rs + ag
    raise SynthesisError(f"unknown family {spec.family!r}")


def cost_shape(spec: SynthSpec, count: int, elem_bytes: int,
               *, aggregate: bool = False) -> tuple[float, float]:
    """(messages, bytes) for one call of the synthesized schedule —
    critical path by default (every step is one full-ring permutation:
    all ranks move concurrently, so the critical path is the per-rank
    chain), aggregate = summed over ranks (the serialized-host shape
    timing.coefficients_aggregate documents). Bytes are WIRE bytes;
    jumbo-segment streaming charges one message per STREAM_SEG_BYTES
    like the hand-written eager shapes."""
    wb = _wire_bytes_per_elem(spec, elem_bytes)
    msgs = 0.0
    nbytes = 0.0
    for elems in _step_elems(spec, count):
        step_bytes = elems * wb
        msgs += max(1, math.ceil(step_bytes / STREAM_SEG_BYTES))
        nbytes += step_bytes
    if aggregate:
        return msgs * spec.world, nbytes * spec.world
    return msgs, nbytes


def predict_spec(link: Any, spec: SynthSpec, count: int,
                 elem_bytes: int, *, aggregate: bool = False) -> float:
    """Expected seconds under LinkParams `link` (timing.predict's synth
    counterpart; timing.coefficients routes SYNTHESIZED plans here).
    For a tiered spec this is the single-link FALLBACK (both tiers
    charged to one link); the calibrated per-tier prediction is
    `predict_spec_tiered`."""
    m, b = cost_shape(spec, count, elem_bytes, aggregate=aggregate)
    return float(link.seconds(m, b))


def tiered_phase_costs(spec: SynthSpec, count: int, elem_bytes: int,
                       *, aggregate: bool = False,
                       ) -> list[tuple[str, float, float]]:
    """(tier, messages, bytes) of a tiered spec's hops, summed per tier
    — the `timing.hier_phase_costs` accounting generalized to arbitrary
    tier-annotated hop sequences: every hop's wire bytes are charged to
    exactly the link it crosses. aggregate=True sums over all ranks
    (the serialized-host regime); default is the per-link critical
    path (every hop is a full-torus permutation — all ranks move
    concurrently)."""
    wb = _wire_bytes_per_elem(spec, elem_bytes)
    per: dict[str, list[float]] = {"inner": [0.0, 0.0],
                                   "outer": [0.0, 0.0]}
    for tier, elems in _tiered_step_elems(spec, count):
        step_bytes = elems * wb
        per[tier][0] += max(1, math.ceil(step_bytes / STREAM_SEG_BYTES))
        per[tier][1] += step_bytes
    scale = spec.world if aggregate else 1
    return [("inner", per["inner"][0] * scale, per["inner"][1] * scale),
            ("outer", per["outer"][0] * scale, per["outer"][1] * scale)]


def predict_spec_tiered(links: Any, spec: SynthSpec, count: int,
                        elem_bytes: int, *,
                        aggregate: bool = False) -> float:
    """Expected seconds for a tiered spec under a `timing.TierLinks`
    calibration: the phases serialize (the emitted DAG never overlaps
    tiers), so the prediction is the exact per-tier alpha-beta sum —
    which is also why it is an ADMISSIBLE pruning bound for the search:
    it is the model's exact cost of the candidate, not a relaxation,
    and certification can only reject candidates, never improve this
    score."""
    return float(sum(
        links.of(tier).seconds(m, b)
        for tier, m, b in tiered_phase_costs(spec, count, elem_bytes,
                                             aggregate=aggregate)))


def hand_written_best(link: Any, op: Operation, count: int,
                      elem_bytes: int, world: int, *,
                      rx_buf_bytes: int = 4096,
                      aggregate: bool = False,
                      wire: str = "") -> float:
    """The best PREDICTED hand-written time for this cell: the default
    selection plus every tuning-reachable alternative (the rendezvous
    compositions/trees the registers can force), so 'beats every
    hand-written algorithm' is checked against the whole zoo, not just
    the default pick. `wire="int8"` scores against the hand-written
    quantized ring (the baseline an int8 synthesized entry must
    beat)."""
    from ..constants import (
        DEFAULT_EAGER_RX_BUF_SIZE,
        DEFAULT_MAX_EAGER_SIZE,
        DEFAULT_MAX_RENDEZVOUS_SIZE,
        CompressionFlags,
        DataType,
        TuningParams,
    )
    from .plan import select_algorithm
    from .timing import predict

    comp = (CompressionFlags.ETH_COMPRESSED if wire
            else CompressionFlags.NO_COMPRESSION)
    cdt = DataType.int8 if wire == "int8" else DataType.none
    tunings = (
        TuningParams.default(DEFAULT_MAX_RENDEZVOUS_SIZE),
        # force the composition / tree branches so they compete
        TuningParams(allreduce_composition_max_count=1 << 62),
        TuningParams(bcast_flat_tree_max_ranks=2,
                     reduce_flat_tree_max_ranks=2,
                     reduce_flat_tree_max_count=64),
    )
    best = math.inf
    for tuning in tunings:
        plan = select_algorithm(
            op, count, elem_bytes, world, comp,
            max_eager_size=DEFAULT_MAX_EAGER_SIZE,
            eager_rx_buf_size=DEFAULT_EAGER_RX_BUF_SIZE,
            tuning=tuning, compress_dtype=cdt)
        t = predict(link, op, plan, count, elem_bytes, world,
                    rx_buf_bytes=rx_buf_bytes, aggregate=aggregate)
        best = min(best, t)
    return best


def hand_written_tiered_best(tier_links: Any, count: int,
                             elem_bytes: int,
                             tiers: tuple[int, int], *,
                             rx_buf_bytes: int = 4096,
                             aggregate: bool = False) -> float:
    """The best PREDICTED two-tier-aware hand-written time for this
    cell: the striped hierarchical composition at the cost model's own
    stripe count (timing.best_stripes' argmin — the strongest
    hand-written two-tier opponent, pipelining included) and the flat
    zoo charged to the OUTER link (every flat ring step crosses the
    slow tier — the same accounting the hier crossover scan uses). A
    tiered synthesized entry ships only when it beats BOTH."""
    from .plan import Algorithm, Plan, Protocol
    from .timing import best_stripes, predict_tiered

    L, P = tiers
    s = best_stripes(tier_links, count, elem_bytes, L, P,
                     aggregate=aggregate)
    hplan = Plan(Protocol.EAGER, Algorithm.HIER_RS_AR_AG, count, 1,
                 inner_world=L, outer_world=P, stripes=s)
    t_hier = predict_tiered(tier_links, hplan, count, elem_bytes,
                            aggregate=aggregate)
    t_flat = hand_written_best(tier_links.outer, Operation.allreduce,
                               count, elem_bytes, L * P,
                               rx_buf_bytes=rx_buf_bytes,
                               aggregate=aggregate)
    return min(t_hier, t_flat)


# ---------------------------------------------------------------------------
# Search: enumerate -> prune -> certify -> score
# ---------------------------------------------------------------------------


def enumerate_candidates(op: Operation, world: int,
                         include_wire: bool = True,
                         ) -> Iterator[SynthSpec]:
    """All valid FLAT candidates for (op, world) in deterministic
    lexicographic order. Distances are strictly increasing (two equal
    distances always collide in the subset-sum check) and k is pinned
    to log2(world) by the exact-cover condition; candidates with the
    same per-step byte profile are cost-equivalent, so dominance
    pruning keeps only the lexicographically first of each family —
    found by the branch-and-bound DFS (`_valid_distance_tuples`), which
    is what keeps enumeration O(k*world) at w64-w256 instead of the
    combinations scan's millions of dead tuples."""
    if world < 2 or world & (world - 1):
        return  # the symmetric families need 2^k == world
    op_name = op.name
    families = {"allreduce": ("exchange", "rs_ag"),
                "allgather": ("doubling",),
                "reduce_scatter": ("halving",)}[op_name]
    distances = _first_valid_tuple(world)
    if distances is None:
        return
    for family in families:
        yield SynthSpec(
            key=_spec_key(op_name, world, family, distances, ""),
            op=op_name, world=world, family=family,
            distances=distances)
        if include_wire and family == "exchange":
            yield SynthSpec(
                key=_spec_key(op_name, world, family, distances,
                              "int8"),
                op=op_name, world=world, family=family,
                distances=distances, wire="int8")


def enumerate_tiered_candidates(world: int, tiers: tuple[int, int],
                                ) -> Iterator[SynthSpec]:
    """All tiered allreduce candidates for one (inner, outer) factoring
    of `world`, deterministic order: the per-tier family product
    {lg, ring} x {exchange, rs_ag, ring}, each at its dominance-
    representative distance tuple. The log-step kinds need a
    power-of-two axis; the ring kinds serve ANY axis extent (d = 1),
    which is what keeps non-power-of-two pod slices searchable.
    Degenerate duplicates are skipped (at an axis extent of 2 the ring
    and the log-step member emit the same hops; ring == rs_ag on the
    outer shard at P = 2)."""
    L, P = tiers
    if L < 2 or P < 2 or L * P != world:
        return
    inner_kinds: list[tuple[str, tuple[int, ...]]] = []
    i_tuple = _first_valid_tuple(L)
    if i_tuple is not None:
        inner_kinds.append(("lg", i_tuple))
    if L > 2 or i_tuple is None:
        inner_kinds.append(("ring", (1,)))
    outer_kinds: list[tuple[str, tuple[int, ...]]] = []
    o_tuple = _first_valid_tuple(P)
    if o_tuple is not None:
        outer_kinds.append(("exchange", o_tuple))
        outer_kinds.append(("rs_ag", o_tuple))
    if P > 2 or o_tuple is None:
        outer_kinds.append(("ring", (1,)))
    for ik, di in inner_kinds:
        for ok, do in outer_kinds:
            family = f"t_{ik}_{ok}"
            yield SynthSpec(
                key=_tiered_key(world, (L, P), family, di, do),
                op="allreduce", world=world, family=family,
                distances=di, tiers=(L, P), outer_distances=do)


@dataclasses.dataclass
class SearchResult:
    """One library-ready winner: its spec, certified canonical DAG, and
    the predicted winning byte window under the scoring link."""

    spec: SynthSpec
    dag: HopDag
    win_bytes: tuple[int, int]
    predicted: dict[int, tuple[float, float]]  # bytes -> (synth, hand)


def _narrow_contiguous(wins: list[int], size_grid: tuple[int, ...],
                       key: str, say: Callable[[str], None],
                       ) -> tuple[int, int]:
    """Longest contiguous grid run of a win set: select_entry treats
    every payload inside [lo, hi] as a predicted win, so a win set with
    a losing cell in the middle must not overclaim the whole span."""
    runs: list[list[int]] = [[wins[0]]]
    for prev, nbytes in zip(wins, wins[1:]):
        if size_grid.index(nbytes) - size_grid.index(prev) == 1:
            runs[-1].append(nbytes)
        else:
            runs.append([nbytes])
    run = max(runs, key=len)
    if len(run) < len(wins):
        say(f"narrow {key}: win cells non-contiguous across "
            f"the grid; keeping [{run[0]}, {run[-1]}]")
    return run[0], run[-1]


def score_window(link: Any, spec: SynthSpec, *,
                 elem_bytes: int = 4,
                 size_grid: tuple[int, ...] | None = None,
                 aggregate: bool = False,
                 log: Callable[[str], None] | None = None,
                 ) -> tuple[tuple[int, int] | None,
                            dict[int, tuple[float, float]]]:
    """Score one FLAT spec per size-grid cell against the best
    hand-written prediction (strict inequality wins) and narrow the win
    set to its longest CONTIGUOUS grid run. The ONE window rule shared
    by search/--export and verify_library — a scoring change lands here
    or nowhere. `size_grid` defaults to the spec's OWN grid
    (`grid_for`: SIZE_GRID_LAT for grid="lat" entries), so a lat
    entry's window re-scores on the cells it was searched over.
    Returns (window or None, per-cell predictions)."""
    say = log or (lambda m: None)
    if size_grid is None:
        size_grid = grid_for(spec)
    wins: list[int] = []
    predicted: dict[int, tuple[float, float]] = {}
    op = Operation[spec.op]
    for nbytes in size_grid:
        count = max(nbytes // elem_bytes, 1)
        t_synth = predict_spec(link, spec, count, elem_bytes,
                               aggregate=aggregate)
        # an int8 candidate competes against the hand-written
        # QUANTIZED ring — never against the exact fp32 zoo (a
        # lossy schedule must not displace an exact one)
        t_hand = hand_written_best(link, op, count, elem_bytes,
                                   spec.world, aggregate=aggregate,
                                   wire=spec.wire)
        predicted[nbytes] = (t_synth, t_hand)
        if t_synth < t_hand:
            wins.append(nbytes)
    if not wins:
        return None, predicted
    return _narrow_contiguous(wins, size_grid, spec.key, say), predicted


def score_window_tiered(tier_links: Any, spec: SynthSpec, *,
                        elem_bytes: int = 4,
                        size_grid: tuple[int, ...] = SIZE_GRID,
                        aggregate: bool = False,
                        log: Callable[[str], None] | None = None,
                        ) -> tuple[tuple[int, int] | None,
                                   dict[int, tuple[float, float]]]:
    """The tiered-entry window rule: per size-grid cell, the spec's
    per-tier prediction (every hop charged to ITS link) must strictly
    beat `hand_written_tiered_best` — the striped hierarchical
    composition at the model's own stripe count AND the flat zoo on the
    outer link. Shared by search/--export and verify_library's tiered
    leg exactly like `score_window` is for flat entries.

    A win needs a (tiny) relative MARGIN, not one ULP: the composition
    re-discovered (the ring x ring member) predicts EXACTLY the striped
    composition's serial form, differing only in summation order — a
    tie is a keep-out, never a shippable entry, and a summation-order
    artifact must not flip windows between hosts."""
    say = log or (lambda m: None)
    wins: list[int] = []
    predicted: dict[int, tuple[float, float]] = {}
    L, P = spec.tiers
    for nbytes in size_grid:
        count = max(nbytes // elem_bytes, 1)
        t_synth = predict_spec_tiered(tier_links, spec, count,
                                      elem_bytes, aggregate=aggregate)
        t_hand = hand_written_tiered_best(tier_links, count, elem_bytes,
                                          (L, P), aggregate=aggregate)
        predicted[nbytes] = (t_synth, t_hand)
        if t_synth < t_hand * (1.0 - 1e-9):
            wins.append(nbytes)
    if not wins:
        return None, predicted
    return _narrow_contiguous(wins, size_grid, spec.key, say), predicted


def search(op: Operation, world: int, link: Any, *,
           elem_bytes: int = 4,
           size_grid: tuple[int, ...] | None = None,
           aggregate: bool = False,
           log: Callable[[str], None] | None = None,
           beam: int | None = None,
           tiers: tuple[int, int] | None = None,
           tier_links: Any = None,
           grid: str = "std",
           ) -> list[SearchResult]:
    """The full synthesize -> score -> prune -> certify loop for one
    (op, world) — flat by default, or the factored space for one
    (inner, outer) factoring when `tiers` is given (then `tier_links`
    supplies the per-tier scoring calibration).

    Candidates are SCORED FIRST with the alpha-beta model (per-tier
    charged for tiered candidates) — the model's exact serial cost of
    the emitted DAG, so pruning on it is admissible (see module
    docstring) — and only the survivors pay certification: losers are
    reported as keep-outs without ever instantiating a DAG, and
    `beam` keeps only the beam best predicted advantages (ranked by
    best hand/synth ratio over the window; ties break to key order so
    the prune is deterministic). Every survivor is then CERTIFIED with
    the existing stack; a candidate with any diagnostic is discarded
    LOUDLY (reported through `log`) and can never reach the library.
    Winners are returned in enumeration order with their contiguous
    winning windows."""
    say = log or (lambda m: None)
    if grid not in ("std", "lat"):
        raise SynthesisError(f"unknown scoring grid {grid!r}")
    if grid == "lat" and tiers is not None:
        raise SynthesisError(
            "the latency grid scores FLAT candidates only: tiered "
            "windows are per-tier predictions selected through the "
            "hier register, not the latency window")
    if size_grid is None:
        size_grid = SIZE_GRID_LAT if grid == "lat" else SIZE_GRID
    if tiers is not None and op != Operation.allreduce:
        raise SynthesisError(
            f"the tiered families implement allreduce only; a tiered "
            f"{op.name} search has no candidates to return (and must "
            "not silently hand back allreduce schedules)")
    if tiers is not None and tier_links is None:
        raise SynthesisError(
            "tiered search needs tier_links (per-tier scoring "
            "calibration): pass timing.TierLinks or commit a "
            "link_tiers section to accl_log/timing_model.json")
    scored: list[tuple[SynthSpec, tuple[int, int],
                       dict[int, tuple[float, float]], float]] = []
    cands = (enumerate_tiered_candidates(world, tiers)
             if tiers is not None else enumerate_candidates(op, world))
    if grid == "lat":
        # the same candidate space re-scored on the latency grid: keys
        # get a "_lat" suffix so a member can ship BOTH a bandwidth
        # window and a latency window without colliding in the library
        cands = (dataclasses.replace(s, key=s.key + "_lat", grid="lat")
                 for s in cands)
    for spec in cands:
        if spec.tiers:
            window, predicted = score_window_tiered(
                tier_links, spec, elem_bytes=elem_bytes,
                size_grid=size_grid, aggregate=aggregate, log=say)
        else:
            window, predicted = score_window(
                link, spec, elem_bytes=elem_bytes, size_grid=size_grid,
                aggregate=aggregate, log=say)
        if window is None:
            say(f"keep-out {spec.key}: never beats the hand-written "
                "baselines on this link (pruned before certification)")
            continue
        advantage = max(
            hand / synth
            for nb, (synth, hand) in predicted.items()
            if window[0] <= nb <= window[1] and synth > 0)
        scored.append((spec, window, predicted, advantage))
    if beam is not None and len(scored) > beam:
        ranked = sorted(scored, key=lambda s: (-s[3], s[0].key))
        kept = {id(s) for s in ranked[:beam]}
        for spec, _w, _p, adv in ranked[beam:]:
            say(f"PRUNE {spec.key}: outside the beam of {beam} "
                f"(predicted advantage {adv:.2f}x) — never certified")
        scored = [s for s in scored if id(s) in kept]
    results: list[SearchResult] = []
    for spec, window, predicted, _adv in scored:
        ok, diags = certify_spec(spec)
        if not ok:
            say(f"DISCARD {spec.key}: candidate failed certification: "
                + "; ".join(str(d) for d in diags[:4]))
            continue
        dag = instantiate(spec, canonical_count(spec))
        results.append(SearchResult(
            spec=spec, dag=dag, win_bytes=window, predicted=predicted))
        n_cells = (size_grid.index(window[1])
                   - size_grid.index(window[0]) + 1)
        say(f"WINNER {spec.key}: beats hand-written on "
            f"[{window[0]}, {window[1]}] bytes "
            f"({n_cells}/{len(size_grid)} cells)")
    return results


# ---------------------------------------------------------------------------
# Library: the committed synthesized/ directory
# ---------------------------------------------------------------------------


def library_dir() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent / "synthesized"


@dataclasses.dataclass(frozen=True)
class LibraryEntry:
    spec: SynthSpec
    win_bytes: tuple[int, int]
    canonical_count: int
    path: pathlib.Path

    def load_dag(self) -> HopDag:
        return from_json(json.loads(self.path.read_text())["dag"])


_LIBRARY: dict[str, LibraryEntry] | None = None


def clear_library_cache() -> None:
    global _LIBRARY
    _LIBRARY = None


def library() -> dict[str, LibraryEntry]:
    """key -> entry for every committed synthesized schedule. Cached;
    `clear_library_cache()` rescans (tests, regeneration)."""
    global _LIBRARY
    if _LIBRARY is None:
        entries: dict[str, LibraryEntry] = {}
        d = library_dir()
        if d.is_dir():
            for p in sorted(d.glob("*.json")):
                try:
                    doc = json.loads(p.read_text())
                    spec = SynthSpec.from_json(doc)
                    lo, hi = doc.get("win_bytes", [0, 0])
                    entries[spec.key] = LibraryEntry(
                        spec=spec, win_bytes=(int(lo), int(hi)),
                        canonical_count=int(doc.get(
                            "canonical_count", canonical_count(spec))),
                        path=p)
                except (OSError, ValueError, KeyError) as e:
                    raise SynthesisError(
                        f"unreadable synthesized library entry {p}: "
                        f"{e!r}") from e
        _LIBRARY = entries
    return _LIBRARY


def select_entry(op: Operation, world: int, payload_bytes: int,
                 wire: str = "",
                 tiers: tuple[int, ...] = (),
                 grid: str = "std") -> str | None:
    """The library entry `plan.select_algorithm` should use for this
    cell, or None. `tiers=()` (the default) matches only FLAT entries —
    the synth registers' uniform-link windows; `tiers=(inner, outer)`
    matches only the tiered entries of that exact factoring (the
    HIER_ALLREDUCE_MIN_COUNT window's predicted-time arbitration).
    `grid="std"` (the default) matches only SIZE_GRID entries;
    `grid="lat"` matches only the latency-grid entries behind
    SYNTH_LATENCY_MAX_COUNT — the two windows never cross-select.
    Among matching entries the one whose predicted winning window
    contains the payload wins; ties break to the narrower window (the
    more specialized schedule), then key order — all deterministic."""
    best: LibraryEntry | None = None
    for entry in library().values():
        s = entry.spec
        if (s.op != op.name or s.world != world or s.wire != wire
                or s.tiers != tuple(tiers) or s.grid != grid):
            continue
        lo, hi = entry.win_bytes
        if not (lo <= payload_bytes <= hi):
            continue
        if best is None:
            best = entry
            continue
        bw = best.win_bytes[1] - best.win_bytes[0]
        ew = hi - lo
        if ew < bw or (ew == bw and entry.spec.key < best.spec.key):
            best = entry
    return best.spec.key if best else None


def entry_for_key(key: str) -> LibraryEntry:
    entry = library().get(key)
    if entry is None:
        raise SynthesisError(
            f"no synthesized library entry {key!r} "
            f"(library at {library_dir()})")
    return entry


def export_entry(result: SearchResult,
                 out_dir: pathlib.Path | None = None) -> pathlib.Path:
    """Write one winner to the library (the committed JSON form)."""
    out = out_dir or library_dir()
    out.mkdir(parents=True, exist_ok=True)
    doc = result.spec.to_json()
    doc["schema"] = 1
    doc["canonical_count"] = canonical_count(result.spec)
    doc["win_bytes"] = list(result.win_bytes)
    doc["cert"] = {"semantic": "clean", "modelcheck": "clean"}
    doc["dag"] = to_json(result.dag)
    path = out / f"{result.spec.key}.json"
    path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    return path


def shipped_link() -> Any:
    """LinkParams from the committed calibrated timing model — the same
    link `ACCL.autotune` and `tools/accl_synth`
    resolve (timing.emulator_link, the one resolution rule)."""
    from .timing import emulator_link

    model_path = (pathlib.Path(__file__).resolve().parent.parent.parent
                  / "accl_log" / "timing_model.json")
    try:
        model = json.loads(model_path.read_text())
        return emulator_link(model)
    except (OSError, ValueError) as e:
        raise SynthesisError(
            f"cannot load the shipped timing model {model_path} "
            f"(needed to re-validate library win_bytes): {e!r}") from e


def shipped_tier_links() -> Any:
    """TierLinks from the committed calibrated timing model's
    `link_tiers` section (fitted on the CPU tier's emulated two-tier
    world; its writer is retired) — the
    scoring calibration tiered library entries are verified under.
    Raises loudly when absent: a library with tiered entries and no
    shipped per-tier calibration cannot be re-validated."""
    from ..telemetry.feedback import default_tier_links

    tiers = default_tier_links()
    if tiers is None:
        raise SynthesisError(
            "the shipped timing model carries no link_tiers (needed to "
            "re-validate tiered library windows) — commit a "
            "link_tiers section to accl_log/timing_model.json")
    return tiers


def verify_library(log: Callable[[str], None] | None = None,
                   link: Any = None, tier_links: Any = None) -> bool:
    """Re-certify every committed entry from scratch: the spec must
    regenerate the committed DAG byte-for-byte (generator drift check),
    the DAG must pass semantics + deep modelcheck clean, and the
    committed win_bytes window must equal a fresh `score_window` under
    `link` (default: the shipped calibrated model) — a timing-model or
    cost-model change that leaves stale selection windows fails here
    instead of silently steering `select_entry`. TIERED entries
    re-score under `tier_links` (default: the shipped `link_tiers`
    calibration, never the flat link — their windows are per-tier
    predictions against the striped composition). The CI step that
    keeps a stale library or a checker change from silently shipping
    an uncertified schedule."""
    say = log or print
    ok = True
    entries = library()
    if not entries:
        say("synthesized library is EMPTY")
        return False
    if link is None:
        link = shipped_link()
    for key, entry in sorted(entries.items()):
        committed = entry.load_dag()
        regen = instantiate(entry.spec, entry.canonical_count)
        if to_json(regen) != to_json(committed):
            say(f" FAIL {key}: committed DAG != regenerated DAG "
                "(generator drift — re-export the library)")
            ok = False
            continue
        diags = certify_dag(committed, entry.spec,
                            entry.canonical_count)
        if diags:
            say(f" FAIL {key}: committed DAG no longer certifies: "
                + "; ".join(str(d) for d in diags[:4]))
            ok = False
            continue
        if entry.spec.tiers:
            if tier_links is None:
                tier_links = shipped_tier_links()
            window, _ = score_window_tiered(tier_links, entry.spec)
        else:
            window, _ = score_window(link, entry.spec)
        if window != entry.win_bytes:
            say(f" FAIL {key}: committed win_bytes "
                f"{list(entry.win_bytes)} != fresh scoring "
                f"{list(window) if window else None} under the scoring "
                "link (stale selection window — re-export the library)")
            ok = False
            continue
        tier_note = (f", tiers {entry.spec.tiers[0]}x"
                     f"{entry.spec.tiers[1]}" if entry.spec.tiers else "")
        say(f"  ok  {key}: regenerates + certifies clean, win window "
            f"current ({len(committed.nodes)} nodes, "
            f"world {entry.spec.world}{tier_note})")
    return ok


# ---------------------------------------------------------------------------
# Lowering: certified hop-DAG -> schedule body (the compiler seam)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _SymView:
    """The rank-relative view `lower_dag` extracts: rank 0's node slice
    with, per position, the rotation distance of its hop (if any)."""

    positions: tuple[Node, ...]  # rank-0 nodes in per-rank order
    send_pos_of_hop: dict[int, int]
    send_distance: dict[int, int]
    output: Value  # rank-0 output, refs rewritten to positions


def _extract_symmetric(dag: HopDag) -> _SymView:
    """Validate rotational symmetry and extract the rank-relative
    program. Every rank must hold the same per-rank node sequence with
    peers rotated by a constant per hop and piece references mapping to
    the same positions — the structural form of the search's symmetry
    pruning. Raises SynthesisError otherwise (the lowering never guesses
    at an asymmetric DAG)."""
    w = dag.world
    per_rank: list[list[Node]] = [[] for _ in range(w)]
    for n in dag.nodes:
        if not 0 <= n.rank < w:
            raise SynthesisError(f"node {n.id} rank {n.rank} out of range")
        per_rank[n.rank].append(n)
    n_pos = len(per_rank[0])
    if any(len(p) != n_pos for p in per_rank):
        raise _NotRankSymmetric("per-rank node counts differ")
    pos_of: list[dict[int, int]] = [
        {n.id: p for p, n in enumerate(per_rank[r])} for r in range(w)]

    def rel_value(value: Value, r: int) -> tuple:
        out = []
        for pc in value:
            if pc.node == CONST:
                out.append(("const", pc.length, pc.fill))
            else:
                ref = pos_of[r].get(pc.node)
                if ref is None:
                    raise SynthesisError(
                        "cross-rank piece reference (data must flow "
                        "through send/recv hops)")
                out.append((ref, pc.offset, pc.length, pc.part))
        return tuple(out)

    send_distance: dict[int, int] = {}
    send_pos_of_hop: dict[int, int] = {}
    for p in range(n_pos):
        base = per_rank[0][p]
        for r in range(w):
            n = per_rank[r][p]
            same = (n.kind == base.kind and n.length == base.length
                    and n.func == base.func and n.dtype == base.dtype
                    and n.hop == base.hop and n.arg == base.arg
                    and n.scales_len == base.scales_len
                    and rel_value(n.value, r) == rel_value(base.value, 0)
                    and rel_value(n.value2, r) == rel_value(base.value2,
                                                           0))
            if not same:
                raise _NotRankSymmetric(
                    f"DAG is not rank-symmetric at position {p} "
                    f"(rank {r} differs from rank 0)")
            if n.kind == "send":
                d = (n.peer - n.rank) % w
                prev = send_distance.setdefault(n.hop, d)
                if prev != d:
                    raise _NotRankSymmetric(
                        f"hop {n.hop} mixes rotation distances")
            if n.kind == "recv":
                d = (n.rank - n.peer) % w
                prev = send_distance.setdefault(n.hop, d)
                if prev != d:
                    raise _NotRankSymmetric(
                        f"hop {n.hop} recv distance mismatch")
        if base.kind == "send":
            if base.hop in send_pos_of_hop:
                raise SynthesisError(
                    f"hop {base.hop} has multiple sends per rank")
            send_pos_of_hop[base.hop] = p
    out0 = dag.outputs[0]
    for r in range(w):
        if rel_value(dag.outputs[r], r) != rel_value(out0, 0):
            raise _NotRankSymmetric("DAG outputs are not rank-symmetric")
    return _SymView(positions=tuple(per_rank[0]),
                    send_pos_of_hop=send_pos_of_hop,
                    send_distance=send_distance,
                    output=out0)


def _check_same_rank_dataflow(dag: HopDag) -> None:
    """Structural precondition BOTH lowerings require: ranks in range,
    every piece reference resolves to a node of the SAME rank (cross-rank
    data flows only through send/recv hops — the generic lowering's
    `env` is per-rank-correct only under this contract), at most one
    send per (hop, rank), and every recv hop has a send. Raises a plain
    SynthesisError: a violation means NO lowering can compile this DAG
    correctly, so it must never be demoted to a fallback."""
    rank_of: dict[int, int] = {}
    for n in dag.nodes:
        if not 0 <= n.rank < dag.world:
            raise SynthesisError(f"node {n.id} rank {n.rank} out of range")
        rank_of[n.id] = n.rank

    def check_refs(value: Value, rank: int, what: str) -> None:
        for pc in value:
            if pc.node == CONST:
                continue
            src = rank_of.get(pc.node)
            if src is None:
                raise SynthesisError(
                    f"{what} references unknown node {pc.node}")
            if src != rank:
                raise SynthesisError(
                    f"{what} is a cross-rank piece reference (data must "
                    f"flow through send/recv hops)")

    send_ranks: dict[int, set[int]] = {}
    for n in dag.nodes:
        check_refs(n.value, n.rank, f"node {n.id}")
        check_refs(n.value2, n.rank, f"node {n.id}")
        if n.kind == "send":
            ranks = send_ranks.setdefault(n.hop, set())
            if n.rank in ranks:
                raise SynthesisError(
                    f"hop {n.hop} has multiple sends from rank {n.rank}")
            ranks.add(n.rank)
    for n in dag.nodes:
        if n.kind == "recv" and n.hop not in send_ranks:
            raise SynthesisError(
                f"recv node {n.id} has no matching send on hop {n.hop}")
    for r, out in enumerate(dag.outputs):
        check_refs(out, r, f"rank {r} output")


def lower_dag(dag: HopDag, axis_name: str) -> Callable[[Any], Any]:
    """Compile a certified hop-DAG into a schedule body (flat per-rank
    buffer -> flat per-rank result) over the mesh axis, built from the
    SAME wire primitives schedules.py uses: lax.ppermute for every hop,
    ops.compression's blockwise quantize/dequantize for encode/decode
    nodes, and the reduce lane's elementwise folds for combines. The
    body is what ScheduleCompiler shard_maps + jits — a synthesized
    schedule is a first-class algorithm, not an interpreter.

    Two lowerings share this entry: DAGs whose per-rank programs are a
    strict rotation of rank 0's (the exchange family: every offset
    static) compile to ONE rank-relative chain; DAGs whose chunk
    indexing is rank-absolute (the chunked doubling/halving families)
    take the generic masked lowering, where every rank's chain is
    evaluated and each hop payload / final output is selected by
    `axis_index` — the schedules.py `jnp.where(me == ...)` idiom,
    generalized."""
    _check_same_rank_dataflow(dag)
    try:
        view = _extract_symmetric(dag)
    except _NotRankSymmetric:
        return _lower_generic(dag, axis_name)
    w = dag.world

    def body(x: Any) -> Any:
        import jax.numpy as jnp
        from jax import lax

        from ..ops.compression import (
            dequantize_blockwise,
            quantize_blockwise,
        )
        from ..ops.reduce_ops import combine_op

        env: dict[tuple[int, str], Any] = {}

        def materialize(value: Value, pos_map: dict[int, int]) -> Any:
            parts = []
            for pc in value:
                if pc.node == CONST:
                    parts.append(jnp.full((pc.length,), pc.fill,
                                          dtype=x.dtype))
                else:
                    src = env[(pos_map[pc.node], pc.part)]
                    parts.append(src[pc.offset:pc.offset + pc.length])
            if not parts:
                return jnp.zeros((0,), dtype=x.dtype)
            if len(parts) == 1:
                return parts[0]
            return jnp.concatenate(parts)

        # position map for rank-0 ids (materialize resolves refs by
        # node id -> position)
        pos_map = {n.id: p for p, n in enumerate(view.positions)}

        for p, n in enumerate(view.positions):
            if n.kind == "arg":
                out = x[: n.length]
            elif n.kind == "send":
                out = materialize(n.value, pos_map)
            elif n.kind == "recv":
                d = view.send_distance[n.hop]
                payload = env[(view.send_pos_of_hop[n.hop], DATA)]
                perm = [(i, (i + d) % w) for i in range(w)]
                out = lax.ppermute(payload, axis_name, perm)
            elif n.kind == "combine":
                func = (ReduceFunction.MAX if n.func == "max"
                        else ReduceFunction.SUM)
                out = combine_op(func, materialize(n.value, pos_map),
                                 materialize(n.value2, pos_map))
            elif n.kind == "encode":
                q, s = quantize_blockwise(materialize(n.value, pos_map))
                env[(p, SCALES)] = s
                out = q
            elif n.kind == "decode":
                q = materialize(n.value, pos_map)
                s = materialize(n.value2, pos_map)
                out = dequantize_blockwise(q, s, n.length, x.dtype)
            elif n.kind == "cast":
                v = materialize(n.value, pos_map)
                out = v.astype(jnp.dtype(n.dtype)) if n.dtype else v
            else:
                raise SynthesisError(f"cannot lower node kind {n.kind!r}")
            env[(p, DATA)] = out
        return materialize(view.output, pos_map)

    return body


def _lower_generic(dag: HopDag, axis_name: str) -> Callable[[Any], Any]:
    """Masked SPMD lowering for any same-rank-dataflow hop-DAG: every
    rank's node chain is evaluated (correct on its own rank, defined
    everywhere), hop payloads select the local rank's send by
    `axis_index`, and the output selects the local rank's composition —
    exactly the masking contract the hand-written schedules use for
    rank-dependent moves. Cross-rank data still flows ONLY through the
    ppermute hops."""
    w = dag.world
    sends_by_hop: dict[int, list[Node]] = {}
    for n in dag.nodes:
        if n.kind == "send":
            sends_by_hop.setdefault(n.hop, []).append(n)

    def body(x: Any) -> Any:
        import jax.numpy as jnp
        from jax import lax

        from ..ops.compression import (
            dequantize_blockwise,
            quantize_blockwise,
        )
        from ..ops.reduce_ops import combine_op

        me = lax.axis_index(axis_name)
        env: dict[tuple[int, str], Any] = {}

        def materialize(value: Value) -> Any:
            parts = []
            for pc in value:
                if pc.node == CONST:
                    parts.append(jnp.full((pc.length,), pc.fill,
                                          dtype=x.dtype))
                else:
                    src = env[(pc.node, pc.part)]
                    parts.append(src[pc.offset:pc.offset + pc.length])
            if not parts:
                return jnp.zeros((0,), dtype=x.dtype)
            if len(parts) == 1:
                return parts[0]
            return jnp.concatenate(parts)

        permuted: dict[int, Any] = {}
        for n in dag.nodes:
            if n.kind == "arg":
                out = x[: n.length]
            elif n.kind == "send":
                out = materialize(n.value)
            elif n.kind == "recv":
                if n.hop not in permuted:
                    sends = sends_by_hop.get(n.hop, [])
                    if not sends:
                        raise SynthesisError(
                            f"recv node {n.id} has no matching send on "
                            f"hop {n.hop}")
                    payload = env[(sends[0].id, DATA)]
                    for s in sends[1:]:
                        payload = jnp.where(me == s.rank,
                                            env[(s.id, DATA)], payload)
                    perm = [(s.rank, s.peer) for s in sends]
                    permuted[n.hop] = lax.ppermute(payload, axis_name,
                                                   perm)
                out = permuted[n.hop][: n.length]
            elif n.kind == "combine":
                func = (ReduceFunction.MAX if n.func == "max"
                        else ReduceFunction.SUM)
                out = combine_op(func, materialize(n.value),
                                 materialize(n.value2))
            elif n.kind == "encode":
                q, s = quantize_blockwise(materialize(n.value))
                env[(n.id, SCALES)] = s
                out = q
            elif n.kind == "decode":
                out = dequantize_blockwise(materialize(n.value),
                                           materialize(n.value2),
                                           n.length, x.dtype)
            elif n.kind == "cast":
                v = materialize(n.value)
                out = v.astype(jnp.dtype(n.dtype)) if n.dtype else v
            else:
                raise SynthesisError(f"cannot lower node kind {n.kind!r}")
            env[(n.id, DATA)] = out
        result = materialize(dag.outputs[0])
        for r in range(1, w):
            result = jnp.where(me == r, materialize(dag.outputs[r]),
                               result)
        return result

    return body


def _check_tier_layout(dag: HopDag, spec: SynthSpec) -> None:
    """Cross-check the spec's tier annotation against the emitted DAG:
    every hop's (rank -> peer) send pairs must be EXACTLY the RankMap
    ring permutation of its annotated (tier, distance) — the
    `ring=(pos, perm)` embedding the compiled ppermute uses and the
    per-tier cost accounting charges. A mismatch means the annotation
    would charge (or compile) the hop on the wrong tier: FATAL, never
    a fallback — a mis-annotated hop would silently bill DCN traffic
    to ICI."""
    from .hierarchical import RankMap

    L, P = spec.tiers
    rm = RankMap(L, P, "outer_major")
    layout = hop_layout(spec)
    pairs: dict[int, set[tuple[int, int]]] = {}
    for n in dag.nodes:
        if n.kind == "send":
            pairs.setdefault(n.hop, set()).add((n.rank, n.peer))
    if sorted(pairs) != list(range(len(layout))):
        raise SynthesisError(
            f"{spec.key}: DAG hops {sorted(pairs)} do not match the "
            f"tier annotation's {len(layout)} channels")
    for h, (tier, d) in enumerate(layout):
        want = set(rm.inner_perm(d) if tier == "inner"
                   else rm.outer_perm(d))
        if pairs[h] != want:
            raise SynthesisError(
                f"{spec.key}: hop {h} send pairs are not the {tier} "
                f"ring permutation at distance {d} — the tier "
                "annotation disagrees with the emitted DAG")


def lower_plan(plan: Any, options: Any, world: int,
               axis_name: str) -> tuple[Callable[[Any], Any], int]:
    """The ScheduleCompiler._body seam for Algorithm.SYNTHESIZED plans:
    resolve the plan's library entry, regenerate the DAG at the call's
    count, and lower it. Raises loudly when the key is missing or the
    entry's world disagrees — a synthesized plan must never silently
    fall back to a different schedule.

    Tiered entries validate their hop annotation against the RankMap
    ring permutations first (`_check_tier_layout`) and then compile
    through the generic same-rank-dataflow lowering, whose per-hop
    `ppermute` perm is built from the DAG's sends — i.e. exactly the
    validated `inner_perm`/`outer_perm` global pairs of the PR 8
    `ring=(pos, perm)` embedding: inner hops stay within a slice,
    outer hops cross, one compiled program either way."""
    entry = entry_for_key(plan.synth_key)
    spec = entry.spec
    if spec.world != world:
        raise SynthesisError(
            f"synthesized entry {spec.key} is for world {spec.world}, "
            f"called with world {world}")
    if spec.scenario != options.scenario:
        raise SynthesisError(
            f"synthesized entry {spec.key} implements {spec.op}, "
            f"called as {options.scenario.name}")
    func = ("max" if ReduceFunction(options.function)
            == ReduceFunction.MAX else "sum")
    count = int(options.count)
    chunk_by = 0
    if spec.tiers:
        chunk_by = spec.tiers[0] * spec.tiers[1]
    elif spec.family == "rs_ag":
        chunk_by = world
    if chunk_by and count % chunk_by:
        # chunked families pad to a chunking multiple and trim, the
        # same rule allreduce_ring_schedule applies per segment
        padded = count + (-count) % chunk_by
        dag = instantiate(spec, padded, func)
        if spec.tiers:
            _check_tier_layout(dag, spec)
        inner = lower_dag(dag, axis_name)

        def body(x: Any) -> Any:
            import jax.numpy as jnp

            y = jnp.pad(x, (0, padded - count))
            return inner(y)[:count]

        return body, 1
    dag = instantiate(spec, count, func)
    if spec.tiers:
        _check_tier_layout(dag, spec)
    return lower_dag(dag, axis_name), 1
