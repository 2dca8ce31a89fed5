#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from BENCHMARK.json, its configuration and its traffic mix
from their own files, warms every size the traffic sends, measures for
`--seconds`, compares a seeded sample of the window's answers with the
plain reference, and prints one JSON object as its last line. Refuses to
run without a TPU, or with fewer chips than the cell asks for.

Everything that belongs to one configuration, traffic mix, collective
or metric is a file of its own, found by name:
  configs/<config>.json      the configuration as it is run (BENCHMARK.json `file`)
  traffic/<traffic>.json     the mix's parameters; names its generator
  generators/<name>.py       `Traffic(params, seed).next_call()`
  drivers/<collective>.py    `Driver`: buffers, one call, answers, comparison
  metrics/<metric>.py        `read(run)`: one number, or None where there is nothing to read
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WARM_CALLS = 3  # per size, before the window
INF = 1e300  # a compared number with no finite reading (a NaN answer)


def log(*parts) -> None:
    print(*parts, flush=True)


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def for_cell(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return dict(
        cell=cell,
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        end_to_end=for_cell(bench["end_to_end"]),
        per_layer=for_cell(bench["per_layer"]),
    )


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class CompileCounter:
    """Counts compile requests and traces that JAX reports while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.armed and event.startswith("/jax/compilation_cache/"):
            self.events[event] = self.events.get(event, 0) + 1

    def _duration(self, event, duration, **_):
        if self.armed and event.startswith("/jax/core/compile/"):
            self.events[event] = self.events.get(event, 0) + 1


def measure(driver, traffic, seconds: float, seed: int, keep: int,
            annotate: bool = False) -> dict:
    """Drive the traffic for `seconds`. Each call is timed from when it
    was due (at once, in a closed loop) to its result being ready. A
    sample of the answers, drawn from the seed, is kept for the
    comparison, with the last answer of every size."""
    import jax

    rng = random.Random(seed)
    calls = []  # (size, t0_ns, t1_ns)
    kept = []  # reservoir of (size, answer)
    last = {}
    failed = 0
    errors = []
    t_start = time.perf_counter_ns()
    end = t_start + int(seconds * 1e9)
    while True:
        size, due = traffic.next_call()
        if due is not None:
            t0 = t_start + int(due * 1e9)
            if t0 >= end:
                break
            while time.perf_counter_ns() < t0:
                pass
        else:
            t0 = time.perf_counter_ns()
        try:
            if annotate:
                with jax.profiler.TraceAnnotation("bench.call", nbytes=size):
                    driver.call(size)
            else:
                driver.call(size)
        except Exception as e:  # counted, and the run is not correct
            failed += 1
            if len(errors) < 3:
                errors.append(f"{type(e).__name__}: {e}")
            if time.perf_counter_ns() >= end:
                break
            continue
        t1 = time.perf_counter_ns()
        calls.append((size, t0, t1))
        answer = driver.answer(size)
        last[size] = answer
        i = len(calls) - 1
        if i < keep:
            kept.append((size, answer))
        else:
            j = rng.randrange(i + 1)
            if j < keep:
                kept[j] = (size, answer)
        if t1 >= end:
            break
    t_end = max([t_start] + [c[2] for c in calls])
    return dict(calls=calls, failed=failed, errors=errors,
                kept=kept + list(last.items()), t_start=t_start,
                t_end=t_end)


def main(argv=None, require_tpu: bool = True, traffic_override=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the lower-precision control (PERF.md): the configuration's
    # `control_wire` in the facade. The driver's runs never pass it.
    ap.add_argument("--control", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    cell, config = spec["cell"], spec["config"]
    traffic_params = traffic_override or spec["traffic"]
    chips = int(cell["chips"])

    from compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {d0.platform!r} "
              f"({d0.device_kind}, {len(devices)} devices)", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: cell {cell['name']} needs {chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    if int(config["ranks"]) != chips:
        print(f"run.py: configuration has {config['ranks']} ranks, the cell "
              f"{chips} chips", file=sys.stderr)
        return 2

    from jax.sharding import Mesh

    import costs
    import trace_reduce
    from accl_tpu import ACCL

    counter = CompileCounter()
    counter.armed = True
    used = devices[:chips]
    peaks = costs.peaks_for(d0.device_kind) if require_tpu else None
    t = time.perf_counter()
    accl = ACCL(Mesh(np.array(used), ("ccl",)))
    t_accl = time.perf_counter() - t

    traffic = load_module("generators", traffic_params["generator"]).Traffic(
        traffic_params, args.seed)
    sizes = sorted(set(traffic.sizes))
    t = time.perf_counter()
    driver = load_module("drivers", config["collective"]).Driver(
        accl, config, sizes, args.seed, control=args.control)
    t_inputs = time.perf_counter() - t
    t = time.perf_counter()
    for size in sizes:
        for _ in range(WARM_CALLS):
            driver.call(size)
    t_warm = time.perf_counter() - t
    log(f"device: {d0.platform} {d0.device_kind} x{len(devices)}, "
        f"cell {cell['name']} on {chips}, compile cache {cache_dir}")
    log(f"setup: accl {t_accl:.3f} s, inputs {t_inputs:.3f} s, warm "
        f"{t_warm:.3f} s; jax compile events in setup {counter.events}")

    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, float(traffic_params.get("trace_seconds",
                                                        seconds)))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.events = {}
    counter.armed = True
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    if args.trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            rec = measure(driver, traffic, seconds, args.seed,
                          int(traffic_params["compare_sample"]), True)
        jax.profiler.stop_trace()
    else:
        rec = measure(driver, traffic, seconds, args.seed,
                      int(traffic_params["compare_sample"]))
    counter.armed = False
    in_window = dict(counter.events)

    trace = None
    if trace_dir:
        found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        if found:
            trace = trace_reduce.load(found[0], [d.id for d in used])
        shutil.rmtree(trace_dir, ignore_errors=True)

    per_size = {}
    for size, _, _ in rec["calls"]:
        per_size[size] = per_size.get(size, 0) + 1
    log(f"window: {len(rec['calls'])} calls in "
        f"{(rec['t_end'] - rec['t_start']) / 1e9:.3f} s, failed "
        f"{rec['failed']}; calls per size {dict(sorted(per_size.items()))}")
    log(f"jax compile events inside the window: {in_window or 'none'}")
    for err in rec["errors"]:
        log(f"call failed: {err}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    run = Run(calls=rec["calls"], t_start=rec["t_start"], t_end=rec["t_end"],
              world=chips, peaks=peaks, setup_s=setup_s, trace=trace,
              device_ids=[d.id for d in used], config=config,
              traffic=traffic_params)
    metrics = {}
    for m in spec["per_layer"] if args.trace else spec["end_to_end"]:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(rec["calls"]) + rec["failed"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        import breakdown

        device.update(breakdown.device_time(trace, run))
        result["breakdown"] = breakdown.breakdown(trace, run)

    # the comparison: after the window, with the program's state freed
    t = time.perf_counter()
    kept = rec["kept"]
    driver.free()
    readings = driver.compare(kept) if kept else {}
    limits = config["check"]
    checks = {k: {"value": readings.get(k, INF), "limit": lim}
              for k, lim in limits.items()}
    for c in checks.values():  # JSON has no inf: an answer of NaN reads 1e300
        c["value"] = min(c["value"], INF)
    result["correct"] = bool(
        kept and rec["failed"] == 0
        and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"compared {len(kept)} answers in {time.perf_counter() - t:.3f} s"
        f"{' (control: ' + config['control_wire'] + ' wire)' if args.control else ''}")
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
