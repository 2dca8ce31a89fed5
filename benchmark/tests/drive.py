"""Drive run.py's whole run on the CPU, with the chip check skipped and,
per case, the timed path broken underneath. One process per cell; prints
`case <json result>` per case. Used by test_checks.py:

    python benchmark/tests/drive.py <cell> <case> [<case> ...]

Cases: `sound`, `control` (the bf16 wire), and the faults in FAULTS.
"""

from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SMALL_BUCKETS = {  # ar.bw.w4's mix at a size the CPU holds
    "generator": "closed_loop_sizes",
    "sizes_bytes": [65536, 262144], "per_block": 1,
    "compare_sample": 4, "trace_seconds": 1,
}


def _lowered(fault):
    """Break every program the facade lowers: `fault(fn, x)` stands in
    for `fn(x)`, x being the (ranks, n) operand."""
    from accl_tpu.sequencer.lowering import ScheduleCompiler

    orig = ScheduleCompiler.lower

    @contextlib.contextmanager
    def patched():
        def lower(self, options, plan):
            fn = orig(self, options, plan)
            return lambda x: fault(fn, x)

        ScheduleCompiler.lower = lower
        try:
            yield
        finally:
            ScheduleCompiler.lower = orig

    return patched


def _half_batch(fn, x):
    """Half of the contributions left out, the mean taken over the rest:
    half of the ranks where there are several, else half the elements."""
    import jax.numpy as jnp

    w, n = x.shape
    if w > 1:
        kept = x[: w // 2].sum(0) * (w / (w // 2))
        return jnp.broadcast_to(kept, x.shape)
    out = fn(x)
    return out.at[:, n // 2:].set(out[:, : n // 2].mean())


@contextlib.contextmanager
def _attr(obj, name, value):
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _not_placed():
    from accl_tpu.device import tpu_device

    return _attr(tpu_device, "_place_into", lambda dst, out: dst)


def _not_copied_back():
    from accl_tpu.buffers import TPUBuffer

    return _attr(TPUBuffer, "sync_from_device", lambda self: self)


FAULTS = {
    # the call returns its state unchanged: every rank keeps its input
    "unchanged": _lowered(lambda fn, x: x),
    "half_batch": _lowered(_half_batch),
    # the exchange between chips left out: each rank scales its own part
    "no_exchange": _lowered(lambda fn, x: x * x.shape[0]),
    # one answer altered where it is produced
    "altered": _lowered(lambda fn, x: fn(x).at[0, x.shape[1] // 2].add(1.0)),
    # the result never written into the caller's buffer
    "not_placed": _not_placed,
    # the host mirror never filled (host buffers)
    "not_copied_back": _not_copied_back,
}


def main(cell: str, cases: list[str]) -> None:
    override = SMALL_BUCKETS if cell == "ar.bw.w4" else None
    for case in cases:
        argv = ["--workload", cell, "--seed", "4294967311", "--seconds", "0.5",
                "--trace", "0"]
        ctx = contextlib.nullcontext()
        if case == "control":
            argv.append("--control")
        elif case != "sound":
            ctx = FAULTS[case]()
        out = io.StringIO()
        with ctx, contextlib.redirect_stdout(out):
            rc = run.main(argv, require_tpu=False, traffic_override=override)
        last = out.getvalue().strip().splitlines()[-1]
        print(case, rc, last, flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
