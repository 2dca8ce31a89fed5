"""Semantic certifier: prove a descriptor batch computes its collective.

The linter and model checker prove SAFETY — no hazards, no protocol
mismatches, no races or deadlocks — but a schedule can pass all of that
and still leave rank 3 without rank 5's addend: a device-resident
sequencer then ships a wrong ANSWER, the failure class ACCL+ (arxiv
2312.11742) reports as silent numeric corruption debugged post-hoc.
This pass closes that gap with contribution-set abstract interpretation:

  1. `lift_call` abstractly evaluates the REAL schedule body's jaxpr
     (the same `protocol.trace_schedule_jaxpr` seam the protocol pass
     reads ppermute perms from — one model, nothing to drift) into a
     hop-DAG IR (`hopdag.HopDag`): every cross-rank move, reduction
     fold, and quantized encode/decode as data, with exact region
     intervals.
  2. `certify` interprets the DAG over the contribution-set domain:
     each element of each buffer region carries the multiset of source
     atoms it holds — atom (r, slot, j) is rank r's element j of
     operand `slot` — plus the reduction the atoms were folded under
     (SUM / MAX / pure data). Slices, concatenations and hops move
     contribution intervals around; combines merge them; the quantized
     lanes' named boundaries (codes carry their payload's provenance,
     scales are block metadata) keep the nonlinear encode math from
     dissolving provenance.
  3. The final per-rank contribution map is compared against the
     declared collective spec (`collective_spec`): allreduce means
     EVERY rank's element j holds {SUM over all ranks of atom j}, and
     so on for each family, quantized variants included.

Verdicts get stable codes:

  ACCL501  wrong-result: the final contribution set differs from the
           spec in a way that is neither purely missing nor purely
           duplicated (foreign atoms, wrong reduction, misrouted
           regions)
  ACCL502  partial-contribution: some rank's input never reaches an
           output region that the spec says must include it
  ACCL503  double-count: a contribution folded into the same
           non-idempotent reduction twice
  ACCL504  stale-read: a hop forwards a region before its producer
           wrote it (program-order violation in the DAG). This is the
           IR-level complement of the hazard pass's batch-level ACCL101
           — cross-checked against it by the corpus, never duplicated.

The pass is per-batch LINEAR (one abstract evaluation per step, no
interleaving exploration), so it rides the DEFAULT lint tier; verdicts
are cached by static signature alongside the compile cache they front.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import numpy as np

from ..constants import Operation, ReduceFunction
from .diagnostics import Diagnostic, make
from .hopdag import (
    CONST,
    DATA,
    SCALES,
    HopDag,
    Node,
    Piece,
    Value,
    concat_values,
    const_value,
    slice_value,
    splice_value,
    validate_order,
    value_length,
)

__all__ = [
    "UnsupportedSchedule",
    "lift_call",
    "collective_spec",
    "certify",
    "certify_call",
    "check_batch_semantics",
    "clear_cache",
]


class UnsupportedSchedule(Exception):
    """The lifter met a jaxpr construct outside the schedule
    vocabulary: the certifier can make NO claim about this body (it
    never guesses). Strict callers (the CLI conformance gate) fail
    loudly; the in-band tier skips the step."""


# ---------------------------------------------------------------------------
# Lifter: schedule jaxpr -> HopDag
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Sym:
    """One rank's abstract (payload-carrying) array during lifting:
    flat row-major piece list + logical shape."""

    shape: tuple[int, ...]
    pieces: Value
    dtype: Any

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _is_sym(v: Any) -> bool:
    return isinstance(v, _Sym)


def _uniform_fill(a: np.ndarray) -> float | None:
    """The single fill value of a constant-uniform concrete array, or
    None when the array is not uniform."""
    flat = np.asarray(a).ravel()
    if flat.size == 0:
        return 0.0
    v = flat[0]
    if flat.size == 1 or bool(np.all(flat == v)):
        return float(v)
    return None


class _Lifter:
    def __init__(self, world: int):
        self.world = world
        self.nodes: list[Node] = []
        self.hops = 0
        from jax.extend import core as jex_core

        self._literal = jex_core.Literal
        # Evaluation memos, keyed by object identity and kept alive for
        # the lift's duration (holding the keyed objects in the values
        # prevents id reuse). A scan body re-evaluates its jaxpr once
        # per trip, but its CONCRETE index math (rank offsets, masks) is
        # trip-invariant — memoizing per (eqn, operand identities) makes
        # later trips pay only for the abstract piece bookkeeping.
        self._lit_memo: dict[int, tuple[Any, list[Any]]] = {}
        self._const_memo: dict[int, tuple[Any, list[list[Any]]]] = {}
        self._eqn_memo: dict[tuple, tuple[list[Any], list[Any]]] = {}
        self._runs_memo: dict[int, tuple[Any, list[tuple[int, int, int]]]] = {}
        # one stable object per rank: downstream concrete memo keys are
        # identity-based, so axis_index must not mint fresh scalars
        self._axis_vals = [np.int32(r) for r in range(world)]

    # -- node construction -------------------------------------------------

    def emit(self, **kw: Any) -> int:
        nid = len(self.nodes)
        self.nodes.append(Node(id=nid, **kw))
        return nid

    def pieces_of(self, v: Any, size: int | None = None) -> Value:
        """A per-rank value as a piece list: syms directly, concrete
        uniform arrays as constant fill (zeros masks, pad values)."""
        if _is_sym(v):
            return v.pieces
        a = np.asarray(v)
        fill = _uniform_fill(a)
        if fill is None:
            raise UnsupportedSchedule(
                "non-uniform concrete data flows into the payload path")
        return const_value(size if size is not None else a.size, fill)

    # -- jaxpr evaluation --------------------------------------------------

    def eval_closed(self, closed: Any, args: list[list[Any]]) -> list[list[Any]]:
        memo = self._const_memo.get(id(closed))
        if memo is None:
            consts = [[np.asarray(c)] * self.world for c in closed.consts]
            self._const_memo[id(closed)] = (closed, consts)
        else:
            consts = memo[1]
        return self.eval_jaxpr(closed.jaxpr, consts, args)

    def eval_jaxpr(self, jaxpr: Any, consts: list[list[Any]],
                   args: list[list[Any]]) -> list[list[Any]]:
        env: dict[Any, list[Any]] = {}

        def read(x: Any) -> list[Any]:
            if isinstance(x, self._literal):
                memo = self._lit_memo.get(id(x))
                if memo is None:
                    memo = (x, [np.asarray(x.val)] * self.world)
                    self._lit_memo[id(x)] = memo
                return memo[1]
            return env[x]

        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for var, val in zip(jaxpr.invars, args):
            env[var] = val
        for eqn in jaxpr.eqns:
            invals = [read(x) for x in eqn.invars]
            outs = self.eval_eqn(eqn, invals)
            if len(outs) != len(eqn.outvars):
                raise UnsupportedSchedule(
                    f"{eqn.primitive.name}: arity mismatch in lifter")
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return [read(v) for v in jaxpr.outvars]

    def eval_eqn(self, eqn: Any, invals: list[list[Any]]) -> list[list[Any]]:
        name = eqn.primitive.name
        if name == "ppermute":
            return [self._ppermute(eqn, invals[0])]
        if name == "axis_index":
            return [list(self._axis_vals)]
        if name in ("jit", "closed_call", "core_call"):
            return self._call(eqn, invals)
        if name == "scan":
            return self._scan(eqn, invals)
        if name == "optimization_barrier":
            return list(invals)
        has_sym = any(_is_sym(v) for val in invals for v in val)
        if not has_sym:
            return self._concrete(eqn, invals)
        if name == "select_n":
            return [self._select(invals)]
        if name == "convert_element_type":
            return [self._convert(eqn, invals[0])]
        if name in ("add", "sub", "mul", "div", "max", "min"):
            return [self._binop(name, invals[0], invals[1])]
        if name == "dynamic_slice":
            return [self._dynamic_slice(eqn, invals)]
        if name == "dynamic_update_slice":
            return [self._dynamic_update_slice(invals)]
        if name == "slice":
            return [self._static_slice(eqn, invals[0])]
        if name == "concatenate":
            return [self._concat(eqn, invals)]
        if name in ("reshape", "squeeze"):
            return [self._reshape(eqn, invals[0])]
        if name == "broadcast_in_dim":
            return [self._reshape(eqn, invals[0])]
        if name == "pad":
            return [self._pad(eqn, invals)]
        raise UnsupportedSchedule(
            f"primitive {name!r} over abstract payload")

    # -- handlers ----------------------------------------------------------

    def _sym(self, shape: Sequence[int], pieces: Value, dtype: Any) -> _Sym:
        return _Sym(tuple(int(s) for s in shape), pieces, np.dtype(dtype))

    def _out_aval(self, eqn: Any, i: int = 0) -> Any:
        return eqn.outvars[i].aval

    def _ppermute(self, eqn: Any, val: list[Any]) -> list[Any]:
        perm = eqn.params["perm"]
        aval = self._out_aval(eqn)
        n = int(np.prod(aval.shape)) if aval.shape else 1
        hop = self.hops
        self.hops += 1
        if not any(_is_sym(v) for v in val):
            out: list[Any] = [np.zeros(aval.shape, np.asarray(val[0]).dtype)
                              for _ in range(self.world)]
            for s, d in perm:
                out[d] = np.asarray(val[s])
            return out
        dtype = next(v.dtype for v in val if _is_sym(v))
        recvs: dict[int, int] = {}
        for s, d in perm:
            self.emit(kind="send", rank=int(s), length=n,
                      value=self.pieces_of(val[s], n), hop=hop,
                      peer=int(d))
        for s, d in perm:
            recvs[int(d)] = self.emit(kind="recv", rank=int(d), length=n,
                                      hop=hop, peer=int(s))
        outs = []
        for r in range(self.world):
            if r in recvs:
                pieces: Value = (Piece(n, recvs[r]),)
            else:
                pieces = const_value(n, 0.0)
            outs.append(self._sym(aval.shape, pieces, dtype))
        return outs

    def _call(self, eqn: Any, invals: list[list[Any]]) -> list[list[Any]]:
        name = str(eqn.params.get("name", ""))
        if name.startswith("accl_sem_"):
            return self._marker(name, eqn, invals)
        closed = eqn.params["jaxpr"] if "jaxpr" in eqn.params \
            else eqn.params.get("call_jaxpr")
        if closed is None:
            raise UnsupportedSchedule(f"call primitive without jaxpr: {name}")
        if hasattr(closed, "consts"):
            return self.eval_closed(closed, invals)
        return self.eval_jaxpr(closed, [], invals)

    def _marker(self, name: str, eqn: Any,
                invals: list[list[Any]]) -> list[list[Any]]:
        """The compression lanes' named boundaries: apply each lane's
        SEMANTIC rule instead of descending into the blockwise math."""
        if name == "accl_sem_encode":
            x = invals[0]
            n = int(self._out_aval(eqn, 0).shape[-1])
            nb = int(self._out_aval(eqn, 1).shape[-1])
            codes, scales = [], []
            for r in range(self.world):
                nid = self.emit(kind="encode", rank=r, length=n,
                                scales_len=nb,
                                value=self.pieces_of(x[r], n),
                                dtype="int8")
                codes.append(self._sym((n,), (Piece(n, nid),), np.int8))
                scales.append(self._sym(
                    (nb,), (Piece(nb, nid, 0, SCALES),), np.float32))
            return [codes, scales]
        if name == "accl_sem_decode":
            q, s = invals[0], invals[1]
            aval = self._out_aval(eqn)
            n = int(aval.shape[-1])
            outs = []
            for r in range(self.world):
                nid = self.emit(kind="decode", rank=r, length=n,
                                value=self.pieces_of(q[r]),
                                value2=self.pieces_of(s[r]))
                outs.append(self._sym(aval.shape, (Piece(n, nid),),
                                      aval.dtype))
            return [outs]
        if name == "accl_sem_pack":
            # ONE-message quantized hop (ops.compression.pack_wire):
            # codes + bitcast scales concatenated into a single int8
            # wire payload. Abstract convention: the packed value's
            # pieces are (codes pieces, scales pieces) back to back in
            # ELEMENT space (n + nb), while the wire aval is the byte
            # form (n + 4*nb) — only the matching accl_sem_unpack ever
            # slices a packed value, and it slices by the same element
            # convention, so provenance flows exactly and the 3*nb
            # bitcast-padding tail reads as empty.
            q, s = invals[0], invals[1]
            aval = self._out_aval(eqn)
            outs = []
            for r in range(self.world):
                pieces = concat_values(self.pieces_of(q[r]),
                                       self.pieces_of(s[r]))
                outs.append(self._sym(aval.shape, pieces, np.int8))
            return [outs]
        if name == "accl_sem_unpack":
            p = invals[0]
            n = int(self._out_aval(eqn, 0).shape[-1])
            nb = int(self._out_aval(eqn, 1).shape[-1])
            codes, scales = [], []
            for r in range(self.world):
                pieces = self.pieces_of(p[r])
                codes.append(self._sym((n,), slice_value(pieces, 0, n),
                                       np.int8))
                scales.append(self._sym((nb,),
                                        slice_value(pieces, n, nb),
                                        np.float32))
            return [codes, scales]
        if name.startswith("accl_sem_dequant_combine_") \
                or name.startswith("accl_sem_dequant_requant_"):
            func = name.rsplit("_", 1)[-1]
            requant = "_requant_" in name
            q, s, local = invals[0], invals[1], invals[2]
            aval = self._out_aval(eqn, 0)
            n = int(aval.shape[-1])
            outs, scales_out = [], []
            for r in range(self.world):
                dec = self.emit(kind="decode", rank=r, length=n,
                                value=self.pieces_of(q[r]),
                                value2=self.pieces_of(s[r]))
                cmb = self.emit(kind="combine", rank=r, length=n,
                                func=func, value=(Piece(n, dec),),
                                value2=self.pieces_of(local[r], n))
                if requant:
                    nb = int(self._out_aval(eqn, 1).shape[-1])
                    enc = self.emit(kind="encode", rank=r, length=n,
                                    scales_len=nb,
                                    value=(Piece(n, cmb),), dtype="int8")
                    outs.append(self._sym((n,), (Piece(n, enc),), np.int8))
                    scales_out.append(self._sym(
                        (nb,), (Piece(nb, enc, 0, SCALES),), np.float32))
                else:
                    outs.append(self._sym(aval.shape, (Piece(n, cmb),),
                                          aval.dtype))
            return [outs, scales_out] if requant else [outs]
        raise UnsupportedSchedule(f"unknown semantic marker {name!r}")

    def _scan(self, eqn: Any, invals: list[list[Any]]) -> list[list[Any]]:
        p = eqn.params
        if p.get("_split_transpose"):
            raise UnsupportedSchedule("split-transpose scan")
        nc, ncar = int(p["num_consts"]), int(p["num_carry"])
        length = int(p["length"])
        closed = p["jaxpr"]
        consts = invals[:nc]
        carry = list(invals[nc:nc + ncar])
        xs = invals[nc + ncar:]
        order = range(length - 1, -1, -1) if p.get("reverse") \
            else range(length)
        ys_acc: list[list[list[Any]]] = []
        for i in order:
            xi = [self._index_leading(x, i) for x in xs]
            outs = self.eval_closed(closed, consts + carry + xi)
            carry = outs[:ncar]
            ys = outs[ncar:]
            if p.get("reverse"):
                ys_acc.insert(0, ys)
            else:
                ys_acc.append(ys)
        stacked = []
        n_ys = len(ys_acc[0]) if ys_acc else 0
        for j in range(n_ys):
            stacked.append(self._stack([ys[j] for ys in ys_acc]))
        return carry + stacked

    def _index_leading(self, x: list[Any], i: int) -> list[Any]:
        out = []
        for v in x:
            if _is_sym(v):
                if len(v.shape) < 1:
                    raise UnsupportedSchedule("scan over scalar payload")
                m = int(np.prod(v.shape[1:])) if len(v.shape) > 1 else 1
                sub = slice_value(v.pieces, i * m, m)
                out.append(self._sym(v.shape[1:] or (), sub, v.dtype))
            else:
                out.append(np.asarray(v)[i])
        return out

    def _stack(self, rows: list[list[Any]]) -> list[Any]:
        out = []
        for r in range(self.world):
            vals = [row[r] for row in rows]
            if any(_is_sym(v) for v in vals):
                pieces = concat_values(*[self.pieces_of(v) for v in vals])
                first = next(v for v in vals if _is_sym(v))
                out.append(self._sym((len(vals),) + first.shape, pieces,
                                     first.dtype))
            else:
                out.append(np.stack([np.asarray(v) for v in vals]))
        return out

    def _select(self, invals: list[list[Any]]) -> list[Any]:
        pred, cases = invals[0], invals[1:]
        outs = []
        for r in range(self.world):
            p = pred[r]
            if _is_sym(p):
                raise UnsupportedSchedule("data-dependent select predicate")
            pi = np.asarray(p).astype(np.int64).ravel()
            rcases = [c[r] for c in cases]
            if not any(_is_sym(c) for c in rcases):
                idx = np.asarray(p).astype(np.int64)
                stackable = [np.broadcast_to(np.asarray(c), idx.shape)
                             for c in rcases]
                outs.append(np.choose(idx, stackable))
                continue
            template = next(c for c in rcases if _is_sym(c))
            n = template.size
            if pi.size <= 1:
                choice = rcases[int(pi[0]) if pi.size else 0]
                pieces = self.pieces_of(choice, n)
            else:
                if pi.size != n:
                    raise UnsupportedSchedule("select mask/payload mismatch")
                memo = self._runs_memo.get(id(p))
                if memo is None:
                    bounds = list(np.flatnonzero(np.diff(pi)) + 1)
                    starts = [0, *bounds]
                    ends = [*bounds, n]
                    memo = (p, [(lo, hi, int(pi[lo]))
                                for lo, hi in zip(starts, ends)])
                    self._runs_memo[id(p)] = memo
                runs = []
                for lo, hi, which in memo[1]:
                    src = self.pieces_of(rcases[which], n)
                    runs.append(slice_value(src, lo, hi - lo))
                pieces = concat_values(*runs)
            outs.append(self._sym(template.shape, pieces, template.dtype))
        return outs

    def _convert(self, eqn: Any, val: list[Any]) -> list[Any]:
        new = np.dtype(eqn.params["new_dtype"])
        outs = []
        for r in range(self.world):
            v = val[r]
            if not _is_sym(v):
                outs.append(np.asarray(v).astype(new))
            elif v.dtype == new:
                outs.append(v)
            else:
                nid = self.emit(kind="cast", rank=r, length=v.size,
                                value=v.pieces, dtype=new.name)
                outs.append(self._sym(v.shape, (Piece(v.size, nid),), new))
        return outs

    def _binop(self, name: str, a: list[Any], b: list[Any]) -> list[Any]:
        np_ops: dict[str, Callable] = {
            "add": np.add, "sub": np.subtract, "mul": np.multiply,
            "div": np.divide, "max": np.maximum, "min": np.minimum}
        outs = []
        for r in range(self.world):
            x, y = a[r], b[r]
            if not _is_sym(x) and not _is_sym(y):
                outs.append(np_ops[name](np.asarray(x), np.asarray(y)))
                continue
            outs.append(self._abstract_binop(name, r, x, y))
        return outs

    def _abstract_binop(self, name: str, rank: int, x: Any, y: Any) -> _Sym:
        sym = x if _is_sym(x) else y
        other = y if _is_sym(x) else x
        if not _is_sym(other):
            fill = _uniform_fill(np.asarray(other))
            if fill is None:
                raise UnsupportedSchedule(
                    f"{name} of payload with non-uniform concrete data")
            neutral = {"add": 0.0, "sub": 0.0, "mul": 1.0, "div": 1.0}
            if name in neutral and fill == neutral[name]:
                if name in ("sub", "div") and _is_sym(y):
                    raise UnsupportedSchedule(f"payload on {name} rhs only")
                return sym
            if name == "mul" and fill == 0.0:
                return self._sym(sym.shape, const_value(sym.size, 0.0),
                                 sym.dtype)
            if name == "max":
                # max with a constant floor keeps provenance
                other = self._sym(sym.shape, const_value(sym.size, fill),
                                  sym.dtype)
            else:
                raise UnsupportedSchedule(
                    f"{name} of payload with constant {fill}")
        if name not in ("add", "max"):
            raise UnsupportedSchedule(f"{name} folds payload values")
        lhs = x if _is_sym(x) else other
        rhs = y if _is_sym(y) else other
        assert _is_sym(lhs) and _is_sym(rhs)
        if lhs.size != rhs.size:
            raise UnsupportedSchedule("combine of mismatched extents")
        func = "sum" if name == "add" else "max"
        nid = self.emit(kind="combine", rank=rank, length=lhs.size,
                        func=func, value=lhs.pieces, value2=rhs.pieces)
        return self._sym(lhs.shape, (Piece(lhs.size, nid),), lhs.dtype)

    def _int_of(self, v: Any) -> int:
        if _is_sym(v):
            raise UnsupportedSchedule("data-dependent index")
        return int(np.asarray(v).reshape(()))

    def _dynamic_slice(self, eqn: Any, invals: list[list[Any]]) -> list[Any]:
        sizes = eqn.params["slice_sizes"]
        outs = []
        for r in range(self.world):
            op = invals[0][r]
            starts = [self._int_of(s[r]) for s in invals[1:]]
            if not _is_sym(op):
                idx = tuple(slice(st, st + sz)
                            for st, sz in zip(starts, sizes))
                outs.append(np.asarray(op)[idx])
                continue
            if (len(op.shape) > 1
                    and (any(s for s in starts[1:])
                         or tuple(sizes[1:]) != op.shape[1:])):
                raise UnsupportedSchedule(
                    "non-contiguous dynamic_slice of payload")
            m = int(np.prod(op.shape[1:])) if len(op.shape) > 1 else 1
            n = int(sizes[0]) * m
            start = max(0, min(starts[0] * m, op.size - n))  # lax clamping
            outs.append(self._sym(tuple(sizes),
                                  slice_value(op.pieces, start, n),
                                  op.dtype))
        return outs

    def _dynamic_update_slice(self, invals: list[list[Any]]) -> list[Any]:
        outs = []
        for r in range(self.world):
            base, upd = invals[0][r], invals[1][r]
            starts = [self._int_of(s[r]) for s in invals[2:]]
            if not _is_sym(base) and not _is_sym(upd):
                a = np.array(np.asarray(base), copy=True)
                idx = tuple(slice(st, st + sz) for st, sz in
                            zip(starts, np.shape(upd)))
                a[idx] = upd
                outs.append(a)
                continue
            shape = base.shape if _is_sym(base) else np.shape(base)
            if len(shape) != 1:
                raise UnsupportedSchedule(
                    "dynamic_update_slice on nd payload")
            total = int(shape[0])
            u_len = upd.size if _is_sym(upd) else int(np.asarray(upd).size)
            start = max(0, min(starts[0], total - u_len))
            dtype = base.dtype if _is_sym(base) else upd.dtype
            pieces = splice_value(self.pieces_of(base, total),
                                  self.pieces_of(upd, u_len), start)
            outs.append(self._sym((total,), pieces, dtype))
        return outs

    def _static_slice(self, eqn: Any, val: list[Any]) -> list[Any]:
        p = eqn.params
        strides = p.get("strides")
        if strides is not None and any(int(s) != 1 for s in strides):
            raise UnsupportedSchedule("strided slice of payload")
        starts, limits = p["start_indices"], p["limit_indices"]
        outs = []
        for r in range(self.world):
            v = val[r]
            if not _is_sym(v):
                idx = tuple(slice(int(a), int(b))
                            for a, b in zip(starts, limits))
                outs.append(np.asarray(v)[idx])
                continue
            if (len(v.shape) > 1
                    and (any(int(a) for a in starts[1:])
                         or tuple(int(b) for b in limits[1:])
                         != v.shape[1:])):
                raise UnsupportedSchedule("non-contiguous slice of payload")
            m = int(np.prod(v.shape[1:])) if len(v.shape) > 1 else 1
            lo, hi = int(starts[0]), int(limits[0])
            shape = (hi - lo,) + v.shape[1:]
            outs.append(self._sym(shape,
                                  slice_value(v.pieces, lo * m,
                                              (hi - lo) * m),
                                  v.dtype))
        return outs

    def _concat(self, eqn: Any, invals: list[list[Any]]) -> list[Any]:
        dim = int(eqn.params["dimension"])
        outs = []
        for r in range(self.world):
            vals = [v[r] for v in invals]
            if not any(_is_sym(v) for v in vals):
                outs.append(np.concatenate(
                    [np.asarray(v) for v in vals], axis=dim))
                continue
            if dim != 0 or any(_is_sym(v) and len(v.shape) != 1
                               for v in vals):
                raise UnsupportedSchedule("nd concatenate of payload")
            pieces = concat_values(*[self.pieces_of(v) for v in vals])
            first = next(v for v in vals if _is_sym(v))
            outs.append(self._sym((value_length(pieces),), pieces,
                                  first.dtype))
        return outs

    def _reshape(self, eqn: Any, val: list[Any]) -> list[Any]:
        aval = self._out_aval(eqn)
        outs = []
        for r in range(self.world):
            v = val[r]
            if not _is_sym(v):
                outs.append(np.broadcast_to(
                    np.asarray(v), aval.shape).reshape(aval.shape))
                continue
            if int(np.prod(aval.shape)) != v.size:
                raise UnsupportedSchedule("broadcast enlarges payload")
            outs.append(self._sym(aval.shape, v.pieces, v.dtype))
        return outs

    def _pad(self, eqn: Any, invals: list[list[Any]]) -> list[Any]:
        config = eqn.params["padding_config"]
        outs = []
        for r in range(self.world):
            v, pv = invals[0][r], invals[1][r]
            if not _is_sym(v):
                outs.append(np.asarray(
                    np.pad(np.asarray(v),
                           [(int(lo), int(hi)) for lo, hi, _ in config],
                           constant_values=float(np.asarray(pv)))))
                continue
            if len(config) != 1:
                raise UnsupportedSchedule("nd pad of payload")
            lo, hi, interior = (int(x) for x in config[0])
            if interior or lo < 0 or hi < 0:
                raise UnsupportedSchedule("interior/negative pad of payload")
            fill = float(np.asarray(pv).reshape(()))
            pieces = concat_values(const_value(lo, fill), v.pieces,
                                   const_value(hi, fill))
            outs.append(self._sym((lo + v.size + hi,), pieces, v.dtype))
        return outs

    def _concrete(self, eqn: Any, invals: list[list[Any]]) -> list[list[Any]]:
        n_out = len(eqn.outvars)
        outs: list[list[Any]] = [[None] * self.world for _ in range(n_out)]
        for r in range(self.world):
            args = [val[r] for val in invals]
            key = (id(eqn), *(id(a) for a in args))
            memo = self._eqn_memo.get(key)
            if memo is None:
                try:
                    raw = eqn.primitive.bind(*args, **eqn.params)
                except Exception as e:
                    raise UnsupportedSchedule(
                        f"concrete eval of {eqn.primitive.name} failed: "
                        f"{e!r}") from e
                res = [np.asarray(x) for x in raw] \
                    if eqn.primitive.multiple_results else [np.asarray(raw)]
                # the keyed objects ride the value so their ids stay
                # live (no reuse) for the lift's lifetime
                memo = (args, res)
                self._eqn_memo[key] = memo
            for j in range(n_out):
                outs[j][r] = memo[1][j]
        return outs


def lift_call(options: Any, plan: Any, world: int,
              axis_name: str = "ccl",
              arith_table: dict | None = None) -> HopDag:
    """Lift ONE call's schedule body into the hop-DAG IR by abstract
    evaluation of its jaxpr (shared tracing seam:
    `protocol.trace_schedule_jaxpr` with the semantic boundaries
    active)."""
    from .protocol import trace_schedule_jaxpr

    closed, n_in, in_elems = trace_schedule_jaxpr(
        options, plan, world, axis_name, arith_table=arith_table,
        semantic_marks=True)
    lifter = _Lifter(world)
    args: list[list[Any]] = []
    for slot in range(n_in):
        per_rank = []
        for r in range(world):
            nid = lifter.emit(kind="arg", rank=r, length=in_elems,
                              arg=slot, dtype="float32")
            per_rank.append(lifter._sym((in_elems,),
                                        (Piece(in_elems, nid),),
                                        np.float32))
        args.append(per_rank)
    outs = lifter.eval_closed(closed, args)
    if len(outs) != 1:
        raise UnsupportedSchedule("schedule body with multiple outputs")
    result = outs[0]
    out_values = []
    out_elems = 0
    for r in range(world):
        v = result[r]
        pieces = lifter.pieces_of(v)
        out_values.append(pieces)
        out_elems = max(out_elems, value_length(pieces))
    return HopDag(world=world, n_in=n_in, in_elems=in_elems,
                  out_elems=out_elems, nodes=tuple(lifter.nodes),
                  outputs=tuple(out_values))


# ---------------------------------------------------------------------------
# Contribution-set interpretation
# ---------------------------------------------------------------------------

# A Term names one source of data: ("a", rank, slot, base) is the affine
# atom family "operand `slot` of rank `rank`, element base+j at local
# offset j"; ("s", node) is block-scale metadata of an encode node;
# ("stale", node) marks content read before node `node` produced it.
Term = tuple
Terms = dict[Term, int]
# A segment is (length, op, terms): `op` is the reduction the terms were
# folded under — None (pure data), "sum", "max", or "mixed".
Seg = tuple[int, Any, Terms]
IMap = list[Seg]


def _shift_terms(terms: Terms, off: int) -> Terms:
    if off == 0:
        return terms
    return {(t[0], t[1], t[2], t[3] + off) if t[0] == "a" else t: c
            for t, c in terms.items()}


def _imap_slice(imap: IMap, start: int, length: int) -> IMap:
    out: IMap = []
    pos = 0
    end = start + length
    for seg_len, op, terms in imap:
        lo, hi = max(start, pos), min(end, pos + seg_len)
        if lo < hi:
            out.append((hi - lo, op, _shift_terms(terms, lo - pos)))
        pos += seg_len
        if pos >= end:
            break
    got = sum(s[0] for s in out)
    if got < length:
        out.append((length - got, None, {}))
    return out


def _join_op(func: str, a: Any, b: Any) -> Any:
    for side in (a, b):
        if side not in (None, func):
            return "mixed"
    return func


def _merge_terms(a: Terms, b: Terms) -> Terms:
    out = dict(a)
    for t, c in b.items():
        out[t] = out.get(t, 0) + c
    return out


def _imap_join(func: str, a: IMap, b: IMap) -> IMap:
    out: IMap = []
    ai = bi = 0
    a_off = b_off = 0
    while ai < len(a) and bi < len(b):
        alen, aop, at = a[ai]
        blen, bop, bt = b[bi]
        take = min(alen - a_off, blen - b_off)
        out.append((take, _join_op(func, aop, bop),
                    _merge_terms(_shift_terms(at, a_off),
                                 _shift_terms(bt, b_off))))
        a_off += take
        b_off += take
        if a_off == alen:
            ai += 1
            a_off = 0
        if b_off == blen:
            bi += 1
            b_off = 0
    return _imap_norm(out)


def _imap_norm(imap: IMap) -> IMap:
    out: IMap = []
    for seg in imap:
        if seg[0] == 0:
            continue
        if out and out[-1][1] == seg[1] and out[-1][2] == _shift_terms(
                seg[2], -out[-1][0]):
            prev = out.pop()
            out.append((prev[0] + seg[0], prev[1], prev[2]))
        else:
            out.append(seg)
    return out


class _ContribEval:
    """Evaluate every node's contribution interval map in program
    order; reads of not-yet-produced nodes yield stale terms."""

    def __init__(self, dag: HopDag):
        self.dag = dag
        self.sends = dag.sends_by_channel()
        self.memo: dict[tuple[int, str], IMap] = {}

    def value_imap(self, value: Value, consumer: int) -> IMap:
        segs: IMap = []
        for p in value:
            if p.node == CONST:
                segs.append((p.length, None, {}))
            elif p.node >= consumer:
                segs.append((p.length, None, {("stale", p.node): 1}))
            else:
                segs.extend(_imap_slice(self.memo[(p.node, p.part)],
                                        p.offset, p.length))
        return _imap_norm(segs)

    def run(self) -> None:
        for n in self.dag.nodes:
            imap: IMap
            if n.kind == "arg":
                imap = [(n.length, None, {("a", n.rank, max(n.arg, 0), 0): 1})]
            elif n.kind in ("send", "cast"):
                imap = self.value_imap(n.value, n.id)
            elif n.kind == "recv":
                s = self.sends.get((n.hop, n.rank))
                if s is None:
                    imap = [(n.length, None, {("stale", n.id): 1})]
                elif s.id >= n.id:
                    imap = [(n.length, None, {("stale", s.id): 1})]
                else:
                    imap = _imap_slice(self.memo[(s.id, DATA)], 0, n.length)
            elif n.kind == "combine":
                imap = _imap_join(n.func or "sum",
                                  self.value_imap(n.value, n.id),
                                  self.value_imap(n.value2, n.id))
            elif n.kind == "encode":
                imap = self.value_imap(n.value, n.id)
                self.memo[(n.id, SCALES)] = [
                    (n.scales_len, None, {("s", n.id): 1})]
            elif n.kind == "decode":
                imap = _imap_slice(self.value_imap(n.value, n.id),
                                   0, n.length)
            else:
                raise UnsupportedSchedule(f"unknown node kind {n.kind!r}")
            self.memo[(n.id, DATA)] = imap

    def output_imap(self, rank: int) -> IMap:
        return self.value_imap(self.dag.outputs[rank],
                               len(self.dag.nodes))


# ---------------------------------------------------------------------------
# Collective specs
# ---------------------------------------------------------------------------


def _func_name(function: int) -> str:
    return "max" if ReduceFunction(function) == ReduceFunction.MAX \
        else "sum"


def collective_spec(options: Any, world: int) -> list[IMap | None] | None:
    """The declared meaning of one call as per-rank contribution maps:
    spec[r] is the interval map rank r's output MUST equal, or None for
    ranks whose output the collective leaves unspecified (non-root
    ranks of reduce/gather). Returns None when the scenario carries no
    payload contract (barrier/config/nop)."""
    op = options.scenario
    count = int(options.count)
    func = _func_name(options.function)

    def atom(r: int, base: int = 0, slot: int = 0) -> Terms:
        return {("a", r, slot, base): 1}

    def data(terms: Terms, length: int = count) -> Seg:
        return (length, None, terms)

    def red(terms: Terms, length: int = count) -> Seg:
        o = func if sum(terms.values()) > 1 else None
        return (length, o, terms)

    if op in (Operation.barrier, Operation.config, Operation.nop):
        return None
    if op == Operation.copy:
        return [[data(atom(r))] for r in range(world)]
    if op == Operation.combine:
        return [[red(_merge_terms(atom(r, 0, 0), atom(r, 0, 1)))]
                for r in range(world)]
    if op in (Operation.send, Operation.recv):
        src = options.root_src_dst & 0xFFFF
        dst = (options.root_src_dst >> 16) & 0xFFFF
        return [[data(atom(src if r == dst else r))] for r in range(world)]
    root = int(options.root_src_dst)
    if op == Operation.bcast:
        return [[data(atom(root))] for r in range(world)]
    if op == Operation.scatter:
        return [[data(atom(root, r * count))] for r in range(world)]
    if op == Operation.gather:
        rooted = [data(atom(c)) for c in range(world)]
        return [rooted if r == root else None for r in range(world)]
    if op == Operation.allgather:
        return [[data(atom(c)) for c in range(world)]
                for _ in range(world)]
    if op == Operation.reduce:
        full = _merge_all(atom(rr) for rr in range(world))
        return [[red(full)] if r == root else None for r in range(world)]
    if op == Operation.allreduce:
        # degraded live-subset mode (allreduce(mode="live_subset")): the
        # descriptor DECLARES the surviving-contributor set, and the
        # spec demands exactly those ranks' atoms — no more (a dead
        # rank's stale partial folded in is a foreign atom, ACCL501),
        # no fewer (a dropped survivor is ACCL502). Every rank's output
        # still carries the (survivor) sum: dead ranks relay the ring
        # but contribute masked zeros. Empty live_ranks = every rank
        # contributes, the ordinary collective.
        live = tuple(getattr(options, "live_ranks", ()) or ())
        contributors = live if live else tuple(range(world))
        full = _merge_all(atom(rr) for rr in contributors)
        return [[red(full)] for _ in range(world)]
    if op == Operation.reduce_scatter:
        return [[red(_merge_all(atom(rr, r * count)
                                for rr in range(world)))]
                for r in range(world)]
    if op == Operation.alltoall:
        pc = tuple(getattr(options, "peer_counts", ()) or ())
        if pc and any(c != count for c in pc):
            # alltoallv: rank r's slot for source c holds the first
            # peer_counts[r] elements of c's slot r — the capacity
            # prefix — and the overflow tail is DROPPED: the spec
            # declares it empty (zero-fill), so a schedule leaking
            # stale or misrouted data into the dropped region fails
            # certification instead of hiding behind the drop.
            def v_slot(r: int, c: int) -> IMap:
                v = int(pc[r])
                segs: IMap = [data(atom(c, r * count), v)]
                if v < count:
                    segs.append((count - v, None, {}))
                return segs

            return [[seg for c in range(world) for seg in v_slot(r, c)]
                    for r in range(world)]
        return [[data(atom(c, r * count)) for c in range(world)]
                for r in range(world)]
    return None


def _merge_all(terms_iter: Any) -> Terms:
    out: Terms = {}
    for t in terms_iter:
        out = _merge_terms(out, t)
    return out


# ---------------------------------------------------------------------------
# Certification
# ---------------------------------------------------------------------------

_MAX_DIAGS = 8


def _render_terms(terms: Terms, limit: int = 4) -> str:
    """Compact `{SUM-ready}` rendering: atom families grouped by
    (slot, base) over their rank sets."""
    fams: dict[tuple[int, int], list[tuple[int, int]]] = {}
    other: list[str] = []
    for t, c in sorted(terms.items(), key=repr):
        if t[0] == "a":
            fams.setdefault((t[2], t[3]), []).append((t[1], c))
        elif t[0] == "s":
            other.append(f"scales(node {t[1]})")
        else:
            other.append(f"stale(node {t[1]})")
    parts = []
    for (slot, base), ranks in sorted(fams.items()):
        rs = ",".join(f"r{r}" + (f"x{c}" if c != 1 else "")
                      for r, c in ranks)
        loc = f"@{base}+j" if base else "@j"
        sl = f" arg{slot}" if slot else ""
        parts.append("{" + rs + "}" + sl + loc)
    parts.extend(other)
    if not parts:
        return "(nothing: no source data reaches this region)"
    if len(parts) > limit:
        parts = parts[:limit] + [f"...+{len(parts) - limit} more"]
    return " + ".join(parts)


def _classify(got_op: Any, got: Terms, want_op: Any,
              want: Terms) -> tuple[str, str] | None:
    """Compare one aligned region's contribution set against the spec;
    returns (code, detail) or None when it matches."""
    idem = want_op == "max"
    g = {t: (1 if idem else c) for t, c in got.items()}
    w = {t: (1 if idem else c) for t, c in want.items()}
    stale = [t for t in g if t[0] == "stale"]
    if stale:
        return ("ACCL501",
                "region holds stale data (read before written)")
    op_ok = (sum(g.values()) <= 1 or got_op == want_op
             or (got_op is None and sum(g.values()) <= 1))
    if g == w and op_ok:
        return None
    foreign = {t: c for t, c in g.items() if t not in w}
    missing = {t: w[t] - g.get(t, 0) for t in w if g.get(t, 0) < w[t]}
    excess = {t: g[t] - w[t] for t in w if g.get(t, 0) > w[t]}
    if not foreign and not excess and missing:
        return ("ACCL502",
                f"missing contribution {_render_terms(missing)}")
    if not foreign and not missing and excess and not idem:
        return ("ACCL503",
                f"contribution {_render_terms(excess)} folded into the "
                f"same {want_op or 'sum'} twice")
    if g == w and not op_ok:
        return ("ACCL501",
                f"region reduced with {got_op or 'no fold'} where the "
                f"collective declares {want_op}")
    return ("ACCL501",
            f"expected {_render_terms(want)}, got {_render_terms(got)}")


def certify(dag: HopDag, spec: list[IMap | None] | None,
            scenario_name: str = "collective") -> list[Diagnostic]:
    """Prove the DAG's outputs carry exactly the contribution sets the
    collective spec declares. Emits ACCL501-504."""
    if spec is None:
        return []
    diags = validate_order(dag)
    ev = _ContribEval(dag)
    ev.run()
    have_stale = bool(diags)
    for r in range(dag.world):
        want = spec[r] if r < len(spec) else None
        if want is None:
            continue
        got = ev.output_imap(r)
        want_total = sum(s[0] for s in want)
        got_total = sum(s[0] for s in got)
        if got_total < want_total:
            got = got + [(want_total - got_total, None, {})]
        pos = 0
        gi = wi = 0
        g_off = w_off = 0
        while wi < len(want) and len(diags) < _MAX_DIAGS:
            wl, wop, wt = want[wi]
            if gi >= len(got):
                break
            gl, gop, gt = got[gi]
            take = min(wl - w_off, gl - g_off)
            verdict = _classify(gop, _shift_terms(gt, g_off),
                                wop, _shift_terms(wt, w_off))
            if verdict is not None:
                code, detail = verdict
                if not (code == "ACCL501" and "stale" in detail
                        and have_stale):
                    diags.append(make(
                        code,
                        f"{scenario_name}: rank {r} output elements "
                        f"[{pos}, {pos + take}): {detail}", rank=r))
            pos += take
            w_off += take
            g_off += take
            if w_off == wl:
                wi += 1
                w_off = 0
            if g_off == gl:
                gi += 1
                g_off = 0
    return diags[:_MAX_DIAGS]


# ---------------------------------------------------------------------------
# Cached entry points (the lint-tier surface)
# ---------------------------------------------------------------------------

# key -> (arith_table ref, verdict tuple); the table reference pins the
# id() component of the key against reuse after GC
_CERT_CACHE: dict[tuple, tuple[Any, tuple[Diagnostic, ...]]] = {}
_CERT_CACHE_CAP = 4096

# In-band budget: the abstract evaluation is linear in hop count, but a
# heavily segmented schedule (hundreds of eager segments x world ranks)
# can cost whole seconds to lift — too slow for the opt-out lint stage
# in front of every first-time compile. Batches past these bounds skip
# the in-band certification (the step still gets every other pass); the
# CLI conformance sweep (`accl_lint.py --semantic --schedules`) runs
# strict with no budget, so the same shape classes stay covered in CI.
_INBAND_MAX_SEGMENTS = 64
_INBAND_MAX_ELEMS = 1 << 19


def _within_inband_budget(options: Any, plan: Any, world: int) -> bool:
    # only the allreduce ring actually segments its own body
    # (schedules.segmented_apply); other plans' num_segments describe
    # the transport, not the traced program size
    if (options.scenario == Operation.allreduce
            and int(getattr(plan, "num_segments", 1)) > _INBAND_MAX_SEGMENTS):
        return False
    return int(options.count) * world <= _INBAND_MAX_ELEMS


def clear_cache() -> None:
    from ..ops import compression as _comp

    _CERT_CACHE.clear()
    _comp._SEM_JITS.clear()


def certify_call(options: Any, plan: Any, world: int,
                 axis_name: str = "ccl",
                 arith_table: dict | None = None) -> list[Diagnostic]:
    """Certify ONE call: lift its schedule body and check the final
    contribution sets against `collective_spec`. Verdicts are cached by
    the call's static signature (the same key class the compile cache
    uses), so re-linting a recorded shape costs a dict hit."""
    spec = collective_spec(options, world)
    if spec is None or world < 2:
        return []
    # custom tables key by identity; the table object rides the cache
    # value so its id can never be reused for a different table
    key = (options.signature(), plan, world, axis_name,
           0 if arith_table is None else id(arith_table))
    cached = _CERT_CACHE.get(key)
    if cached is not None:
        return list(cached[1])
    dag = lift_call(options, plan, world, axis_name,
                    arith_table=arith_table)
    diags = certify(dag, spec, options.scenario.name)
    if len(_CERT_CACHE) >= _CERT_CACHE_CAP:
        _CERT_CACHE.clear()
    _CERT_CACHE[key] = (arith_table, tuple(diags))
    return diags


def check_batch_semantics(steps: Sequence[Any], plans: Sequence[Any],
                          world: int, axis_name: str = "ccl",
                          arith_table: dict | None = None,
                          strict: bool = False) -> list[Diagnostic]:
    """The batch-level pass the linter's DEFAULT tier runs: certify
    each step's schedule against its declared collective. Per-batch
    linear — one abstract evaluation per step, no interleaving
    exploration. A step the lifter cannot analyze is SKIPPED unless
    `strict` (the CLI conformance gate), which re-raises
    UnsupportedSchedule: the certifier never converts inability into a
    wrong-result claim."""
    diags: list[Diagnostic] = []
    for k, (opts, plan) in enumerate(zip(steps, plans)):
        if not strict and not _within_inband_budget(opts, plan, world):
            continue
        try:
            step_diags = certify_call(opts, plan, world, axis_name,
                                      arith_table=arith_table)
        except UnsupportedSchedule:
            if strict:
                raise
            continue
        except Exception as e:  # analysis must never break dispatch
            if strict:
                raise UnsupportedSchedule(
                    f"step {k} ({opts.scenario.name}): lifter error "
                    f"{e!r}") from e
            continue
        for d in step_diags:
            diags.append(Diagnostic(d.code, d.message, step=k,
                                    rank=d.rank))
    return diags
