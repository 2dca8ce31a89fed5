#!/usr/bin/env python3
"""Calibrate the per-hop timing model from measured sweeps and validate
the tuning-register defaults as performance crossovers.

The cclo_sim role (reference test/model/simulator/cclo_sim.cpp:25-80):
a second target answering "how long should this schedule take" — here an
alpha-beta model (sequencer/timing.py) fitted to the emulator benchmark
CSV (tools/bench_emulator.py) and, when present, the TPU profile.

Writes accl_log/timing_model.json:
  { link params, per-row predicted-vs-measured, tuning crossovers }
"""

import csv
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from accl_tpu.constants import Operation, TuningParams  # noqa: E402
from accl_tpu.sequencer.plan import select_algorithm  # noqa: E402
from accl_tpu.sequencer.timing import (  # noqa: E402
    calibrate,
    coefficients,
    coefficients_aggregate,
    predict,
    tuning_crossovers,
)

OPS = {"allreduce": Operation.allreduce, "bcast": Operation.bcast,
       "allgather": Operation.allgather, "reduce": Operation.reduce,
       "gather": Operation.gather, "scatter": Operation.scatter,
       "alltoall": Operation.alltoall,
       "reduce_scatter": Operation.reduce_scatter}

# the emulator bench's eager/rx geometry, single-sourced from the sweep
# tool so calibration can never drift from what the sweep actually ran
from tools.bench_emulator import (  # noqa: E402
    FIT_MAX_WORLD,
    MAX_EAGER,
    RX_BUF,
)


def load_rows(path: pathlib.Path, default_world: int):
    """Rows inside the calibration domain (worlds <= FIT_MAX_WORLD —
    see tools/bench_emulator.py: larger worlds are scale evidence, not
    fit input), plus the count of rows excluded by the domain."""
    rows = []
    beyond = 0
    with open(path) as f:
        for r in csv.DictReader(f):
            op = OPS.get(r["Collective"])
            if op is None:
                continue
            world = int(r.get("World") or default_world)
            if world > FIT_MAX_WORLD:
                beyond += 1
                continue
            rows.append((op, int(r["Bytes"]), float(r["Seconds"]), world))
    return rows, beyond


def tpu_tier(profile: pathlib.Path) -> dict | None:
    """Second calibration tier from an on-chip profile
    (accl_log/profile.csv: Test,Bytes,Seconds rows; absent until a chip
    run writes one): the
    reference calibrates its simulator against silicon the same way
    (cycles x 4ns, xrtdevice.cpp:248). Measured quantities only:

      - dispatch alpha: alpha-beta fit over the w1 compiled-collective
        lanes (host-observed per-dispatch cost; on a dispatch-bound
        single chip the fit clamps beta to ~inf);
      - HBM beta: the streaming-regime combine rows (payload GB/s).

    ICI beta needs a multi-chip slice and is reported as unmeasured
    rather than assumed."""
    if not profile.exists():
        return None
    disp, hbm = [], []
    with open(profile) as f:
        for r in csv.DictReader(f):
            if r.get("Regime") == "noise":
                continue  # resolution floor, not a measurement
            if "_w1_dispatch_datapath" in r["Test"]:
                disp.append((1.0, float(r["Bytes"]), float(r["Seconds"])))
            elif r["Test"] == "combine_sum_fp32" and \
                    r.get("Regime") == "stream":
                hbm.append(float(r["GBps"]))
    if not disp:
        return None
    params = calibrate(disp)
    alpha = params.alpha
    if params.beta >= 1e11:
        # pure-latency fit (beta clamped at inf): the least-squares alpha
        # can overshoot every sample when the raw slope was negative —
        # the median dispatch time is the honest constant
        times = sorted(t for _, _, t in disp)
        alpha = times[len(times) // 2]
    tier = {
        "source": str(profile.name),
        "dispatch_alpha_us": alpha * 1e6,
        "dispatch_beta_gbps": (None if params.beta >= 1e11
                               else params.beta / 1e9),
        "hbm_stream_gbps": (sorted(hbm)[len(hbm) // 2] if hbm else None),
        "ici_beta_gbps": None,
        "note": "ici unmeasured: single-chip profile; datapath beta "
                "clamps to inf when dispatch swamps the w1 lanes",
    }
    # crossovers under TPU dispatch costs: latency this high pushes the
    # flat->tree switch far right (a projection labeled as such — the
    # wire beta is the HBM bound, an upper limit on any future ICI tier)
    if tier["hbm_stream_gbps"]:
        from accl_tpu.sequencer.timing import LinkParams

        proj = LinkParams(alpha=alpha,
                          beta=tier["hbm_stream_gbps"] * 1e9)
        tier["projected_crossovers"] = tuning_crossovers(proj, world=8)
    return tier


def _fit_per_collective(meta):
    """meta: (op, plan, count, nbytes, secs, world). One LinkParams per
    collective, fitted on the AGGREGATE (serialized-host) cost shape —
    see timing.coefficients_aggregate: the emulator world timeshares one
    CI core, so wall time tracks total moved bytes/messages, and
    per-collective fits absorb each algorithm family's distinct
    per-message cost (a bcast tree hop is a light relay; an allgather
    hop is a full chunk landing)."""
    groups = {}
    for op, plan, count, nbytes, secs, world in meta:
        m, b = coefficients_aggregate(op, plan, count, 4, world,
                                      rx_buf_bytes=RX_BUF)
        groups.setdefault(op.name, []).append((m, b, secs))
    return {name: calibrate(samples) for name, samples in groups.items()}


def _predict_row(fits, op, plan, count, nbytes, world):
    params = fits[op.name]
    return predict(params, op, plan, count, 4, world, rx_buf_bytes=RX_BUF,
                   aggregate=True)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=4,
                    help="world size of the sweep, used only for CSVs "
                         "written before the World column existed")
    args = ap.parse_args()

    src = REPO / "accl_log" / "emu_bench.csv"
    if not src.exists():
        print(f"no {src}; run tools/bench_emulator.py first",
              file=sys.stderr)
        return 1
    rows, main_beyond = load_rows(src, args.world)
    if not rows:
        print(f"{src} has no usable collective rows; re-run "
              "tools/bench_emulator.py", file=sys.stderr)
        return 1
    tuning = TuningParams.default()
    meta = []
    for op, nbytes, secs, world in rows:
        count = nbytes // 4
        plan = select_algorithm(op, count, 4, world,
                                max_eager_size=MAX_EAGER,
                                eager_rx_buf_size=RX_BUF, tuning=tuning)
        meta.append((op, plan, count, nbytes, secs, world))

    # per-collective aggregate-shape fits on the full sweep (the
    # reported model), plus leave-one-world-out cross-validation: each
    # world's rows are predicted by a model fitted WITHOUT them, so the
    # reported holdout error measures generalization, not curve
    # memorization.
    fits = _fit_per_collective(meta)
    report = []
    for op, plan, count, nbytes, secs, world in meta:
        pred = _predict_row(fits, op, plan, count, nbytes, world)
        report.append({
            "collective": op.name, "bytes": nbytes, "world": world,
            "algorithm": plan.algorithm.name,
            "measured_s": secs, "predicted_s": pred,
            "ratio": pred / secs if secs else None,
        })
    ratios = sorted(r["ratio"] for r in report if r["ratio"])
    med = ratios[len(ratios) // 2]

    holdout_ratios = []
    worlds = sorted({w for *_x, w in meta})
    if len(worlds) >= 2:
        for held in worlds:
            train = [m for m in meta if m[5] != held]
            test = [m for m in meta if m[5] == held]
            try:
                hfits = _fit_per_collective(train)
            except Exception:
                continue
            for op, plan, count, nbytes, secs, world in test:
                if op.name not in hfits or not secs:
                    continue
                pred = predict(hfits[op.name], op, plan, count, 4, world,
                               rx_buf_bytes=RX_BUF, aggregate=True)
                holdout_ratios.append(pred / secs)
    holdout_ratios.sort()
    med_holdout = (holdout_ratios[len(holdout_ratios) // 2]
                   if holdout_ratios else None)

    # per-POE tiers: each transport has its own link parameters (the
    # datagram POE pays per-packet costs, the intra-process POE has no
    # sockets at all) — fit each sweep that exists separately, the
    # per-calibration-target posture of the reference's simulator/hw
    # split
    def fit_tier(csv_name: str) -> dict | None:
        src = REPO / "accl_log" / csv_name
        if not src.exists():
            return None
        # the calibration domain (worlds <= FIT_MAX_WORLD) is enforced
        # by load_rows, shared with the main fit: w32 local rows fit at
        # ~1.6x median when pooled — superlinear scheduling at 32
        # threads on one core — so they stay out of every tier
        trows, skipped = load_rows(src, args.world)
        tmeta = []
        for op, nbytes, secs, world in trows:
            count = nbytes // 4
            plan = select_algorithm(op, count, 4, world,
                                    max_eager_size=MAX_EAGER,
                                    eager_rx_buf_size=RX_BUF,
                                    tuning=tuning)
            tmeta.append((op, plan, count, nbytes, secs, world))
        if not tmeta:
            return None
        tfits = _fit_per_collective(tmeta)
        tratios = sorted(
            _predict_row(tfits, op, plan, count, nbytes, world) / secs
            for op, plan, count, nbytes, secs, world in tmeta if secs)
        return {
            "source": csv_name,
            "link_per_collective": {
                name: {"alpha_us": p.alpha * 1e6,
                       "beta_gbps": p.beta / 1e9}
                for name, p in sorted(tfits.items())
            },
            "fit": {"rows": len(tmeta),
                    "rows_beyond_domain": skipped,
                    "calibration_domain": f"worlds <= {FIT_MAX_WORLD}",
                    "median_pred_over_meas":
                        (tratios[len(tratios) // 2] if tratios else None)},
        }

    local_fits = fit_tier("emu_bench_local.csv")
    udp_fits = fit_tier("emu_bench_udp.csv")

    # Crossovers reason over CRITICAL-PATH shapes (the parallel-hardware
    # posture the registers exist for); feed them the bcast link — the
    # root-serialized collective whose aggregate and critical shapes
    # coincide, so its fitted alpha/beta are genuine per-message /
    # per-byte costs of this host rather than world-summed ones.
    cross_params = fits.get("bcast") or next(iter(fits.values()))
    cross = tuning_crossovers(cross_params, world=8)
    tpu = tpu_tier(REPO / "accl_log" / "profile.csv")
    out = {
        "source": str(src.relative_to(REPO)),
        "cost_shape": "aggregate (serialized single-core host; see "
                      "timing.coefficients_aggregate)",
        "link_per_collective": {
            name: {"alpha_us": p.alpha * 1e6, "beta_gbps": p.beta / 1e9,
                   "rows": sum(1 for r in report
                               if r["collective"] == name)}
            for name, p in sorted(fits.items())
        },
        "fit": {"rows": len(report), "median_pred_over_meas": med,
                "median_holdout_pred_over_meas": med_holdout,
                "holdout": "leave-one-world-out",
                "worlds": worlds,
                "rows_beyond_domain": main_beyond,
                "calibration_domain": f"worlds <= {FIT_MAX_WORLD}"},
        "rows": report,
        "local_poe_tier": local_fits,
        "udp_poe_tier": udp_fits,
        "tuning_crossovers": cross,
        "tpu_tier": tpu,
        "reference_defaults": {
            "bcast_flat_tree_max_ranks": 3,
            "reduce_flat_tree_max_ranks": 4,
            "reduce_flat_tree_max_count_bytes": 32 * 1024,
            "gather_flat_tree_max_count_bytes": 32 * 1024,
        },
    }
    dst = REPO / "accl_log" / "timing_model.json"
    dst.write_text(json.dumps(out, indent=1) + "\n")
    for reg, p in sorted(fits.items()):
        print(f"{reg}: alpha={p.alpha*1e6:.1f}us "
              f"beta={p.beta/1e9:.3f}GB/s")
    print(f"median pred/meas={med:.2f} holdout={med_holdout and round(med_holdout, 2)}"
          f" -> {dst.relative_to(REPO)}")
    print(f"crossovers: {cross}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
