"""The per-layer metrics that read the program's own spans: each reads
its value from a ring of spans and the window's calls, and reads nothing
where the ring dropped spans, holds too few, or is empty (a program
without them). A traced run on the CPU reads them end to end."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import run  # noqa: E402

from accl_tpu import telemetry  # noqa: E402

NEW = ["facade_us.lat", "launch_us.lat", "wait_us.lat", "staging_us.lat",
       "lower_hit_share.lat", "host_outside_wait_us.bw"]


def read(name: str, r):
    return run.load_module("metrics", name).read(r)


@pytest.fixture
def ring():
    """The process tracer, enabled and empty; restored after."""
    tr = telemetry.get_tracer()
    capacity = tr.capacity
    tr.clear()
    tr.enable()
    yield tr
    tr.clear()
    tr.disable()
    tr.capacity = capacity


def emit_call(tr, call_id: int, t0: int, phases: dict, hit: bool = True,
              dur: int = 1000) -> None:
    """A call span at t0 of `dur` ns and its children laid end to end
    from t0 + 10: `phases` maps a child's name to its duration."""
    at = t0 + 10
    for name, d in phases.items():
        args = {"call_id": call_id}
        if name == "lower":
            args["hit"] = hit
        tr.emit(name, "phase", "facade", ts_ns=at, dur_ns=d, args=args)
        at += d
    tr.emit("allreduce", "call", "facade", ts_ns=t0, dur_ns=dur,
            args={"call_id": call_id})


DEVICE = {"plan": 50, "lower": 20, "launch": 150, "wait": 400, "place": 30}
HOST = {"stage_in": 100, **DEVICE, "stage_out": 200}


def window(tr, n: int, phases: dict, misses: int = 0):
    """n calls 2000 ns apart, each inside a window call of 1500 ns."""
    calls = []
    for i in range(n):
        t0 = 10_000 + 2000 * i
        emit_call(tr, i + 1, t0 + 5, phases, hit=i >= misses)
        calls.append((4096, t0, t0 + 1500))
    return run.Run(calls=calls)


def test_each_metric_reads_its_value(ring):
    r = window(ring, 10, HOST, misses=1)
    assert read("facade_us.lat", r) == pytest.approx(
        (1000 - 100 - 150 - 400 - 200) / 1e3)
    assert read("launch_us.lat", r) == pytest.approx(0.15)
    assert read("wait_us.lat", r) == pytest.approx(0.4)
    assert read("staging_us.lat", r) == pytest.approx(0.3)
    assert read("lower_hit_share.lat", r) == pytest.approx(90.0)
    assert read("host_outside_wait_us.bw", r) == pytest.approx(0.6)


def test_device_buffers_stage_nothing(ring):
    r = window(ring, 10, DEVICE)
    assert read("staging_us.lat", r) is None
    assert read("facade_us.lat", r) == pytest.approx(0.45)


def test_spans_outside_the_window_are_not_read(ring):
    emit_call(ring, 99, 0, HOST, dur=900_000)  # a warm-up call, long
    r = window(ring, 10, DEVICE)
    assert read("wait_us.lat", r) == pytest.approx(0.4)
    assert read("facade_us.lat", r) == pytest.approx(0.45)


@pytest.mark.parametrize("name", NEW)
def test_no_reading_after_drops(ring, name):
    ring.capacity = 40  # 10 calls of 7 spans overflow it
    r = window(ring, 10, HOST)
    assert ring.drops > 0
    assert read(name, r) is None


@pytest.mark.parametrize("name", NEW)
def test_no_reading_when_too_few_calls_have_a_span(ring, name):
    r = window(ring, 10, HOST)
    r.calls += [(4096, 10**9 + 2000 * i, 10**9 + 2000 * i + 10)
                for i in range(2)]  # 10 of 12 paired: under 90%
    assert read(name, r) is None


@pytest.mark.parametrize("name", NEW)
def test_no_reading_from_a_program_without_spans(name):
    tr = telemetry.get_tracer()
    tr.clear()
    r = run.Run(calls=[(4096, 0, 100), (4096, 200, 300)])
    assert read(name, r) is None


def test_traced_cpu_run_reads_the_span_metrics(tmp_path):
    """run.py --trace 1 on the CPU (chip check skipped): the ring fills
    while the profiler collects, and the cell's span metrics read; the
    span medians add up to about the median call span."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    code = (
        "import sys; sys.path.insert(0, 'benchmark'); import run; "
        "sys.exit(run.main(['--workload', 'ar.hostbuf.lat.w1', '--seed', "
        "'4294967311', '--seconds', '0.5', '--trace', '1'], "
        "require_tpu=False))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600, cwd=BENCH.parent)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW[:5]) <= set(m), m
    assert m["lower_hit_share.lat"] == 100.0
    assert m["staging_us.lat"] > 0 and m["wait_us.lat"] > 0
