"""The yardstick's arithmetic: interval reduction, bytes and least times,
the peaks table, and run.py's refusal of a machine without a TPU."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import costs  # noqa: E402
import trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 7), (0, 2), (1, 3), (10, 12), (3, 4)]) == [
        (0, 4), (5, 7), (10, 12)]


def test_covered_and_gaps_partition_a_window():
    merged = tr.union([(0, 3), (5, 7), (10, 12)])
    assert tr.covered(merged, 1, 11) == 2 + 2 + 1
    assert tr.gaps(merged, 1, 11) == [(3, 5), (7, 10)]
    for lo, hi in [(0, 13), (4, 6), (7.5, 9), (-5, 1)]:
        g = sum(e - s for s, e in tr.gaps(merged, lo, hi))
        assert tr.covered(merged, lo, hi) + g == hi - lo


def synthetic_trace() -> tr.Trace:
    """Two chips, a 100 ns window, two calls; device 1 lags device 0."""
    k = "ring_allreduce"
    ops = {
        0: [tr.Op("copy", 12, 15), tr.Op(k, 15, 25), tr.Op(k, 60, 70),
            tr.Op("fusion", 68, 80)],
        1: [tr.Op(k, 18, 30), tr.Op(k, 62, 74)],
    }
    modules = {0: [tr.Op("jit_a", 12, 26), tr.Op("jit_b", 60, 80)],
               1: [tr.Op("jit_a", 18, 30), tr.Op("jit_b", 62, 74)]}
    spans = [tr.Span("bench.window", 0, 100, {}),
             tr.Span("bench.call", 10, 40, {"nbytes": 4096}),
             tr.Span("bench.call", 55, 85, {"nbytes": 1024})]
    return tr.Trace(ops, spans, modules)


def test_clock_shift_fits_every_execution_between_launch_and_flag():
    # device reads 100 ns ahead; launches 50 ns before, flags 30 ns
    # after each true execution
    execs = [(1100, 1120), (1300, 1350)]
    launches = [(950, 960), (1150, 1160)]
    flags = [(1000, 1050), (1200, 1280)]
    # the offsets that fit lie in [max(1120-1050, 1350-1280), 1300-1150]
    assert tr.clock_shift(execs, launches, flags) == (70 + 150) / 2
    # the trace lost the first launch and flag: pairing moves by one
    assert tr.clock_shift(execs[1:], launches[1:], flags[1:]) == (70 + 150) / 2
    assert tr.clock_shift(execs, launches, []) is None


def test_calls_pair_with_executions_by_order_or_by_time():
    t = synthetic_trace()
    assert [m.name for _, m in tr.call_executions(t, 0)] == ["jit_a", "jit_b"]
    t.modules[0].insert(0, tr.Op("warm", 0, 5))  # one more: match by time
    assert [m.name for _, m in tr.call_executions(t, 0)] == ["jit_a", "jit_b"]


def test_busy_idle_and_kernel_matching_on_a_synthetic_trace():
    t = synthetic_trace()
    assert t.window() == (0, 100)
    assert tr.busy(t, 0) == [(12, 25), (60, 80)]
    assert tr.busy_in_window(t, 0) == 33
    assert tr.busy_in_window(t, 1) == 24
    assert tr.idle_share_pct(t, [0, 1]) == pytest.approx(100 - 28.5)
    got = [o.name for o in tr.ops_in(t, 0, 10, 40, r"^ring_allreduce$")]
    assert got == ["ring_allreduce"]
    assert [o.start for o in tr.ops_in(t, 0, 55, 100)] == [60, 68]
    assert tr.idle_share_pct(tr.Trace({}, t.spans), [0]) is None


def test_metric_readers_on_a_synthetic_trace(monkeypatch):
    import kernels
    import run

    monkeypatch.setattr(kernels, "KERNEL_EVENT", r"^ring_allreduce$")
    t = synthetic_trace()
    r = run.Run(trace=t, device_ids=[0, 1], world=2, peaks=costs.peaks_for(
        "TPU v5 lite"), calls=[], t_start=0, t_end=100)
    assert kernels.per_call(t, [0, 1]) == [(4096, 11.0), (1024, 11.0)]
    # host path: span less the busiest chip's busy time in its program
    host = run.load_module("metrics", "host_us_per_call.lat").read(r)
    assert host == pytest.approx(((30 - 13) + (30 - 20)) / 2 / 1e3)
    kus = run.load_module("metrics", "ring_kernel_us.lat").read(r)
    assert kus == pytest.approx(11 / 1e3)
    roof = run.load_module("metrics", "ring_kernel_roofline.bw").read(r)
    least = sum(costs.least_time_s(n, 2, r.peaks)[0] for n in (4096, 1024))
    assert roof == pytest.approx(100 * least / 22e-9)


def test_breakdown_names_idle_time_by_host_activity():
    import breakdown
    import run

    t = synthetic_trace()
    t.dispatch = [(20, 30), (56, 58)]
    r = run.Run(device_ids=[0, 1])
    assert breakdown.device_time(t, r) == {"busy_s": 28.5e-9,
                                           "window_s": 100e-9}
    b = breakdown.breakdown(t, r)
    # chip 0 is idle over [0,12) [25,60) [80,100); calls cover [10,40)
    # and [55,85), dispatch [20,30) (busy on chip 0 from 20 to 25) and
    # [56,58)
    assert dict(b["idle_gaps"]) == pytest.approx({
        "in call: jit dispatch": 7e-9,
        "in call: rest of host path": 20e-9,
        "between calls": 40e-9})
    assert b["device_ops"][0] == ["ring_allreduce", 22e-9]


def test_a_recorded_chip_trace_reduces():
    """A trace recorded on a v5e (PERF.md): the device plane, the kernel
    events and the benchmark's spans are found, and nearly every call's
    kernel events lie inside its span (the profiler drops a few device
    events, the last call's among them)."""
    import kernels

    path = BENCH / "tests" / "data" / "lat_w1.xplane.pb"
    t = tr.load(str(path), [0])
    assert t.window() is not None and t.ops.get(0)
    calls = t.spans_named("bench.call")
    per_call = kernels.per_call(t, [0])
    assert len(calls) > 10 and len(per_call) >= 0.9 * len(calls)
    share = tr.idle_share_pct(t, [0])
    assert 0 < share < 100


def test_bus_bytes_and_least_time():
    mib = 1 << 20
    assert costs.bus_bytes(64 * mib, 4) == 1.5 * 64 * mib
    assert costs.bus_bytes(64 * mib, 1) == 0
    p = costs.peaks_for("TPU v5 lite")
    t, bound = costs.least_time_s(128 * mib, 4, p)
    assert bound == "ici" and t == pytest.approx(1.5 * 128 * mib / 200e9)
    t, bound = costs.least_time_s(128 * mib, 1, p)
    assert bound == "hbm" and t == pytest.approx(2 * 128 * mib / 819e9)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        costs.peaks_for("source")


def test_reference_gap_reads_nan_as_inf_and_rounding_as_small():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import reference

    mesh = Mesh(np.array(jax.devices()[:1]), ("ccl",))
    x = np.random.default_rng(0).standard_normal((4, 1000)).astype(np.float32)
    ref = reference.SumReference(
        jax.device_put(x, NamedSharding(mesh, PartitionSpec("ccl"))))
    exact = np.tile(x[0] + x[1] + x[2] + x[3], (4, 1))
    assert ref.gap(exact) == 0.0
    assert 0 < ref.gap(np.tile(x.astype(np.float64).sum(0), (4, 1))) < 1e-6
    bad = exact.copy()
    bad[2, 5] = np.nan
    assert ref.gap(bad) == float("inf")
    assert ref.gap(exact[:1]) == float("inf")
    off = exact.copy()
    off[3, 7] += 1.0
    assert ref.gap(off) > 0.05


def _run_cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          capture_output=True, text=True, cwd=cwd, env=env,
                          timeout=300)


def test_run_refuses_a_machine_without_a_tpu(tmp_path):
    proc = _run_cli(["--workload", "ar.lat.w1", "--seed", "3000000019",
                     "--seconds", "1", "--trace", "0"], BENCH.parent,
                    {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_fails_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark, the
    run exits non-zero and prints no result."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    proc = _run_cli(["--workload", "ar.lat.w1", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], tmp_path,
                    {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((BENCH.parent / c["file"]).read_text())
        assert conf["name"] == c["name"]
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
