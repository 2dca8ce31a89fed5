#!/usr/bin/env python
"""accl_synth: search the hop-DAG schedule space, certify winners, and
manage the committed synthesized-schedule library
(accl_tpu/sequencer/synthesized/, docs/synthesis.md).

Modes:

  --search            run the synthesize -> score -> prune -> certify
                      loop for every (op, world) in --ops/--worlds
                      (plus every --tiers factoring) and print the
                      winner table (no files written)
  --export            like --search, but write every winner to the
                      library directory and prune in-scope entries
                      that no longer win any cell (regenerates the
                      committed JSON hop-DAGs; diff should be empty
                      unless the generator or the scoring link
                      changed)
  --score             print the predicted synth-vs-hand-written time
                      per (world, size) cell for every committed entry
  --verify-library    re-certify every committed entry: the spec must
                      regenerate the committed DAG byte-for-byte, the
                      DAG must pass the semantic certifier + deep
                      model checker clean, and the committed win_bytes
                      window must match fresh scoring under the link
                      — TIERED entries re-score under the shipped
                      link_tiers per-tier calibration, never the flat
                      link (the CI gate that keeps a stale library,
                      stale selection window, or a checker change from
                      silently shipping an uncertified schedule)

  --tiers LxP [...]   factored topologies to search (e.g. 2x4 4x4):
                      each searches the tier-annotated hop-DAG space
                      over inner=L x outer=P, scored per tier against
                      the striped hand-written composition under the
                      shipped link_tiers calibration
  --beam N            certify only the N best predicted advantages per
                      (op, world) cell (branch-and-bound: losers are
                      pruned on the admissible alpha-beta bound BEFORE
                      any certification is paid; default: certify
                      every candidate with a non-empty window)

The scoring link defaults to the committed calibrated timing model
(accl_log/timing_model.json, bcast row — the same link ACCL.autotune
reads); --alpha-us/--beta-gbps override it. Tiered scoring reads the
same model's link_tiers section.

Exit status is 0 only when every requested gate holds.
"""

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from accl_tpu.constants import Operation  # noqa: E402
from accl_tpu.sequencer import synthesis  # noqa: E402
from accl_tpu.sequencer.timing import LinkParams, emulator_link  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_MODEL = REPO / "accl_log" / "timing_model.json"


def _rel(path: pathlib.Path) -> pathlib.Path:
    """Repo-relative for display when possible (the library dir can be
    redirected outside the repo in tests)."""
    try:
        return path.relative_to(REPO)
    except ValueError:
        return path


OPS = {
    "allreduce": Operation.allreduce,
    "allgather": Operation.allgather,
    "reduce_scatter": Operation.reduce_scatter,
}


def load_link(args) -> LinkParams:
    if args.alpha_us is not None or args.beta_gbps is not None:
        if args.alpha_us is None or args.beta_gbps is None:
            raise SystemExit("pass both --alpha-us and --beta-gbps")
        return LinkParams(alpha=args.alpha_us * 1e-6,
                          beta=args.beta_gbps * 1e9)
    model = json.loads(pathlib.Path(args.timing_model).read_text())
    try:
        return emulator_link(model)
    except ValueError as e:
        raise SystemExit(f"{args.timing_model}: {e}") from e


def parse_tiers(specs: list[str]) -> list[tuple[int, int]]:
    out = []
    for s in specs:
        try:
            L, P = (int(x) for x in s.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--tiers wants LxP (e.g. 2x4), got {s!r}")
        out.append((L, P))
    return out


def load_tier_links(args):
    from accl_tpu.telemetry.feedback import default_tier_links

    tiers = default_tier_links(args.timing_model)
    if tiers is None:
        raise SystemExit(
            f"{args.timing_model} carries no link_tiers (needed to "
            "score tiered candidates)")
    return tiers


def run_search(args, export: bool) -> bool:
    link = load_link(args)
    print(f"scoring link: alpha {link.alpha * 1e6:.2f} us, "
          f"beta {link.beta / 1e9:.3f} GB/s")
    n_winners = 0
    written: set[str] = set()
    grid = getattr(args, "grid", "std")
    if grid == "lat" and args.tiers:
        raise SystemExit("--grid lat scores flat candidates only "
                         "(tiered windows live behind the hier "
                         "register, not the latency window)")
    for world in args.worlds:
        for op_name in args.ops:
            results = synthesis.search(OPS[op_name], world, link,
                                       beam=args.beam, grid=grid,
                                       log=lambda m: print("  " + m))
            for res in results:
                n_winners += 1
                if export:
                    path = synthesis.export_entry(res)
                    written.add(path.name)
                    print(f"  wrote {_rel(path)}")
    tier_specs = parse_tiers(args.tiers or [])
    if tier_specs:
        tl = load_tier_links(args)
        print(f"tier links: inner alpha {tl.inner.alpha * 1e6:.1f} us "
              f"beta {tl.inner.beta / 1e9:.2f} GB/s / outer alpha "
              f"{tl.outer.alpha * 1e6:.1f} us beta "
              f"{tl.outer.beta / 1e9:.3f} GB/s")
        for L, P in tier_specs:
            results = synthesis.search(
                synthesis.Operation.allreduce, L * P, link,
                beam=args.beam, tiers=(L, P), tier_links=tl,
                log=lambda m: print("  " + m))
            for res in results:
                n_winners += 1
                if export:
                    path = synthesis.export_entry(res)
                    written.add(path.name)
                    print(f"  wrote {_rel(path)}")
    print(f"{n_winners} winner(s) across worlds {args.worlds} "
          f"x ops {args.ops} + tiers {args.tiers or []}")
    if export:
        # prune in-scope entries that stopped winning: after a timing-
        # or cost-model change an entry whose fresh window is None is
        # never rewritten by the loop above, and verify_library would
        # fail it forever with advice (--export) that otherwise could
        # not resolve the failure. Out-of-scope entries (ops/worlds/
        # factorings not searched this run) are kept untouched — a
        # flat search never prunes tiered entries and vice versa, and
        # a std-grid search never prunes latency-grid entries (nor the
        # reverse) — the two windows are scored on different grids.
        op_names = {OPS[o].name for o in args.ops}
        searched_tiers = set(tier_specs)
        for p in sorted(synthesis.library_dir().glob("*.json")):
            if p.name in written:
                continue
            spec = synthesis.SynthSpec.from_json(
                json.loads(p.read_text()))
            in_scope = (
                (spec.tiers and tuple(spec.tiers) in searched_tiers)
                or (not spec.tiers and spec.op in op_names
                    and spec.world in args.worlds
                    and spec.grid == grid))
            if in_scope:
                p.unlink()
                print(f"  pruned {_rel(p)} "
                      "(no longer wins any cell under this link)")
        synthesis.clear_library_cache()
    return n_winners > 0


def run_score(args) -> bool:
    link = load_link(args)
    entries = synthesis.library()
    if not entries:
        print("synthesized library is empty", file=sys.stderr)
        return False
    tl = None
    if any(e.spec.tiers for e in entries.values()):
        tl = load_tier_links(args)
    print(f"{'entry':44s} {'bytes':>10s} {'synth_us':>10s} "
          f"{'hand_us':>10s}  verdict")
    for key, entry in sorted(entries.items()):
        s = entry.spec
        for nbytes in synthesis.grid_for(s):
            count = max(nbytes // 4, 1)
            if s.tiers:
                # per-tier scoring against the striped composition —
                # the baseline a tiered entry actually displaces
                t_s = synthesis.predict_spec_tiered(tl, s, count, 4)
                t_h = synthesis.hand_written_tiered_best(
                    tl, count, 4, (s.tiers[0], s.tiers[1]))
            else:
                t_s = synthesis.predict_spec(link, s, count, 4)
                t_h = synthesis.hand_written_best(
                    link, s.scenario, count, 4, s.world, wire=s.wire)
            verdict = "WINS" if t_s < t_h else ("tie" if t_s == t_h
                                                else "loses")
            print(f"{key:44s} {nbytes:>10d} {t_s * 1e6:>10.1f} "
                  f"{t_h * 1e6:>10.1f}  {verdict}")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--search", action="store_true",
                    help="run the search and print winners")
    ap.add_argument("--export", action="store_true",
                    help="run the search and (re)write the library")
    ap.add_argument("--score", action="store_true",
                    help="predicted synth-vs-hand-written per cell for "
                         "the committed library")
    ap.add_argument("--verify-library", action="store_true",
                    help="re-certify every committed entry (CI gate)")
    ap.add_argument("--worlds", type=int, nargs="+",
                    default=[2, 4, 8, 16])
    ap.add_argument("--ops", nargs="+", default=sorted(OPS),
                    choices=sorted(OPS))
    ap.add_argument("--tiers", nargs="+", default=None, metavar="LxP",
                    help="factored topologies to search, e.g. 2x4 4x4")
    ap.add_argument("--grid", default="std", choices=["std", "lat"],
                    help="scoring grid for flat searches: std = the "
                         "1 KiB-16 MiB bandwidth grid, lat = the "
                         "1-64 KiB latency grid behind "
                         "SYNTH_LATENCY_MAX_COUNT")
    ap.add_argument("--beam", type=int, default=None,
                    help="certify only the N best predicted advantages")
    ap.add_argument("--timing-model", default=str(DEFAULT_MODEL))
    ap.add_argument("--alpha-us", type=float, default=None)
    ap.add_argument("--beta-gbps", type=float, default=None)
    args = ap.parse_args(argv)
    if not (args.search or args.export or args.score
            or args.verify_library):
        ap.error("nothing to do: pass --search, --export, --score, or "
                 "--verify-library")
    ok = True
    if args.search or args.export:
        ok &= run_search(args, export=args.export)
    if args.score:
        ok &= run_score(args)
    if args.verify_library:
        from accl_tpu.telemetry.feedback import default_tier_links

        # tiered entries re-score under the SHIPPED per-tier
        # calibration of --timing-model (verify_library falls back to
        # the committed model's link_tiers when this resolves None)
        ok &= synthesis.verify_library(
            log=print, link=load_link(args),
            tier_links=default_tier_links(args.timing_model))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
