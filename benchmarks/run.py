"""Benchmark orchestrator: one entry per paper table/figure.

``python -m benchmarks.run [--force] [--only fig5,...]``
prints a ``name,us_per_call,derived`` CSV summary at the end.  Results are
cached under results/bench_*.json (delete or --force to recompute).

``python -m benchmarks.run --json [PATH] [--quick]`` instead measures the
DSE perf trajectory — evaluator / SA / screening throughput, before and
after the batched evaluation engine (the "before" legs are the preserved
per-candidate / serial-loop code paths plus the committed
``benchmarks/pr4_baseline.json`` cross-tree measurement) — and writes it
as machine-readable JSON (default ``BENCH_dse.json`` at the repo root).
The document is ``bench_dse/v2``: the top-level snapshot is overwritten
each run, while the ``trajectory`` array is append-only — one headline
row (commit, date, CPU count, iters/s figures) per measurement, with v1
documents migrated in place on the first v2 write.  ``--check-floor``
asserts ``lockstep_sa.speedup`` against the committed regression floor.
CI uploads the file as an artifact on every bench-smoke run.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from .common import csv_line

BENCH_JSON_DEFAULT = Path(__file__).resolve().parent.parent / "BENCH_dse.json"

# Committed regression floor for the lockstep-vs-serial stepping speedup
# (``lockstep_sa.speedup`` in BENCH_dse.json).  Full-rounds measurement on
# this 1-CPU container is ~1.15x; the quick-rounds CI leg is noisier, so
# the floor only asserts lockstep never regresses below the serial loop.
LOCKSTEP_SPEEDUP_FLOOR = 1.0


def make_trajectory_entry(data: dict, commit: str, date: str) -> dict:
    """Pure projection of one dse_bench() snapshot onto a trajectory row.

    Only headline figures — the full snapshot lives at the document's top
    level and is overwritten each run; the trajectory rows are append-only
    so the perf history across commits survives regeneration.
    """
    ls = data.get("lockstep_sa", {})
    return {
        "commit": commit,
        "date": date,
        "cpus": data.get("provenance", {}).get("cpu_count"),
        "screening_cands_per_s":
            data.get("screening", {}).get("batched_cands_per_s"),
        "serial_iters_per_s": ls.get("serial_iters_per_s"),
        "lockstep_iters_per_s": ls.get("lockstep_iters_per_s"),
        "fused_iters_per_s": ls.get("fused_iters_per_s"),
        "lockstep_speedup": ls.get("speedup"),
        "sa_chain_n4_speedup_vs_pr4":
            data.get("vs_pr4", {}).get("sa_chain_n4_speedup"),
        "sweep_n4_wall_s": data.get("sweep_n4", {}).get("wall_s"),
        "serve_replay_req_per_s":
            data.get("serving", {}).get("continuous", {}).get(
                "req_per_wall_s"),
    }


def migrate_bench_doc(doc: dict) -> dict:
    """Migrate a bench_dse/v1 document to v2 (pure; v2 passes through).

    v1 had no ``trajectory``: its single snapshot becomes the first
    trajectory row, tagged ``pre-v2`` since v1 recorded no commit.
    """
    if doc.get("schema") == "bench_dse/v2":
        return doc
    out = dict(doc)
    out["schema"] = "bench_dse/v2"
    out["trajectory"] = [make_trajectory_entry(doc, commit="pre-v2",
                                               date="unknown")]
    return out


def _git_head(repo: Path) -> str:
    try:
        from repro.obs.manifest import git_head
        return git_head(repo)
    except ImportError:
        import subprocess
        try:
            return subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or "unknown"
        except Exception:
            return "unknown"


def write_bench_json(path: Path, quick: bool = False) -> None:
    from datetime import datetime, timezone

    from . import misc_bench

    trajectory = []
    if path.exists():
        try:
            old = migrate_bench_doc(json.loads(path.read_text()))
            trajectory = list(old.get("trajectory", []))
        except (ValueError, OSError):
            pass                     # corrupt/unreadable: start fresh
    t0 = time.time()
    data = misc_bench.dse_bench(quick=quick)
    data["schema"] = "bench_dse/v2"
    data["quick_rounds"] = quick
    data["_wall_s"] = time.time() - t0
    entry = make_trajectory_entry(
        data, commit=_git_head(path.resolve().parent),
        date=datetime.now(timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"))
    data["trajectory"] = trajectory + [entry]
    path.write_text(json.dumps(data, indent=1, default=float) + "\n")
    print(f"[bench] DSE perf trajectory -> {path} "
          f"({len(data['trajectory'])} trajectory rows)")


def check_floor(path: Path) -> None:
    """CI regression guard: fail if the freshly measured lockstep stepping
    speedup fell below the committed floor."""
    doc = migrate_bench_doc(json.loads(path.read_text()))
    speedup = doc["lockstep_sa"]["speedup"]
    if speedup < LOCKSTEP_SPEEDUP_FLOOR:
        raise SystemExit(
            f"[bench] FAIL: lockstep_sa.speedup {speedup:.3f} < committed "
            f"floor {LOCKSTEP_SPEEDUP_FLOOR} ({path})")
    print(f"[bench] lockstep_sa.speedup {speedup:.3f} >= floor "
          f"{LOCKSTEP_SPEEDUP_FLOOR}: OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", nargs="?", const=str(BENCH_JSON_DEFAULT),
                    default=None, metavar="PATH",
                    help="measure the DSE perf trajectory and write "
                    "BENCH_dse.json instead of running the figure suite")
    ap.add_argument("--quick", action="store_true",
                    help="with --json: fewer timing rounds (CI bench-smoke)")
    ap.add_argument("--check-floor", nargs="?", const=str(BENCH_JSON_DEFAULT),
                    default=None, metavar="PATH",
                    help="assert lockstep_sa.speedup in an existing "
                    "BENCH_dse.json meets the committed floor "
                    f"({LOCKSTEP_SPEEDUP_FLOOR}); exits nonzero otherwise")
    args = ap.parse_args()
    if args.check_floor is not None:
        check_floor(Path(args.check_floor))
        return
    if args.json is not None:
        write_bench_json(Path(args.json), quick=args.quick)
        return
    only = set(args.only.split(",")) if args.only else None

    lines = []

    def run(name, fn, derived_fn):
        if only and name not in only:
            return
        t0 = time.time()
        data = fn(force=args.force)
        us = data.get("_wall_s", time.time() - t0) * 1e6
        lines.append(csv_line(name, us, derived_fn(data)))

    from . import (fig5_overall, fig6_fig7_granularity, fig8_reuse,
                   fig9_heatmap, misc_bench, table1_dse)

    run("fig5_overall", fig5_overall.main,
        lambda d: (f"perf_x={d['summary']['perf_x']:.2f};"
                   f"eff_x={d['summary']['eff_x']:.2f};"
                   f"mc_pct={d['summary']['mc_increase_pct']:.1f}"))
    run("table1_dse", table1_dse.main,
        lambda d: f"best={d['best_arch'].replace(',', ';')}")
    run("fig6_fig7", fig6_fig7_granularity.main,
        lambda d: f"chiplet_rows={len(d['chiplet_sweep'])};"
                  f"objectives={len(d['objectives'])}")
    run("fig8_reuse", fig8_reuse.main,
        lambda d: "schemes=" + ";".join(sorted(d["schemes"])))
    run("fig9_heatmap", fig9_heatmap.main,
        lambda d: (f"hops_pct={d['hops_reduction_pct']:.1f};"
                   f"d2d_pct={d['d2d_reduction_pct']:.1f}"))
    run("misc", misc_bench.main,
        lambda d: f"sa_iters_per_s={d['sa']['iters_per_s']:.0f}")

    print("\nname,us_per_call,derived")
    for ln in lines:
        print(ln)


if __name__ == "__main__":
    from repro.launch.cli import enable_compile_cache
    enable_compile_cache()
    main()
