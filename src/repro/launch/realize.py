import os
import sys


def _preparse_host_devices(default: int = 16) -> int:
    """--host-devices must take effect BEFORE the first jax import (jax
    locks the device count on first init), so it is pre-parsed from argv."""
    for i, a in enumerate(sys.argv):
        if a == "--host-devices" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--host-devices="):
            return int(a.split("=", 1)[1])
    return default


if __name__ == "__main__" and "jax" not in sys.modules:
    _n = _preparse_host_devices()
    if _n > 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n}").strip()

"""Realization driver: DSE checkpoint -> compiled sharded programs ->
measured-vs-predicted report -> Tech overlay (paper loop closure).

Usage (CPU, interpret-mode Pallas):

  PYTHONPATH=src python -m repro.launch.realize \
      --ckpt results/table1_quick.ckpt.jsonl --workload TF=tf-quick \
      --top 2 --calibrate --out results/realize.jsonl

The sweep is resumable like every other driver: one JSONL record per
realized candidate, keyed by the checkpoint's task key; re-runs skip
records already measured (--force re-measures).  --calibrate fits the
Tech overlay from every record in the sweep (resumed ones included) and
writes it next to the report; feed it back with
``realize.calibrate.load_overlay`` + ``calibrated_candidates`` for the
measured-calibrated second DSE pass.
"""

import argparse
import time
from pathlib import Path
from typing import List


def _device_pool(mesh_spec: str):
    import jax
    from .mesh import DRYRUN_ENV_FIX, make_production_mesh
    if mesh_spec == "host":
        return list(jax.devices())
    if mesh_spec in ("production", "production2"):
        mesh = make_production_mesh(multi_pod=(mesh_spec == "production2"))
        return list(mesh.devices.flat)
    n = int(mesh_spec)
    devs = list(jax.devices())
    if len(devs) < n:
        raise SystemExit(
            f"--mesh {n} asks for {n} devices, host has {len(devs)} "
            f"(pass --host-devices >= {n}; {DRYRUN_ENV_FIX})")
    return devs[:n]


def _print_report(rep) -> None:
    print(f"[realize] {rep.arch_label} x {rep.workload} "
          f"(batch_unit={rep.batch_unit}, {len(rep.stages)} stages)")
    hdr = (f"  {'stage':5s} {'devs':>4s} {'route':14s} "
           f"{'GFLOP m/p':>16s} {'HBM m/p MB':>16s} "
           f"{'ICI/NoC m/p MB':>16s} {'DCI/D2D m/p MB':>16s}")
    print(hdr)
    for st in rep.stages:
        # flash-scores is the fused half of a flash pair — not a kernel
        kernels = sorted({r.split(":")[0] for r in st.routes.values()}
                         - {"add", "jnp", "flash-scores"})
        route = "+".join(kernels) if kernels else "add"
        print(f"  {st.index:5d} {st.n_devices:4d} {route:14s} "
              f"{st.flops/1e9:7.2f}/{st.pred_flops/1e9:<8.2f} "
              f"{st.hbm_bytes/1e6:7.2f}/{st.pred_dram_bytes/1e6:<8.2f} "
              f"{st.ici_bytes/1e6:7.2f}/{st.pred_noc_bytes/1e6:<8.2f} "
              f"{st.dci_bytes/1e6:7.2f}/{st.pred_d2d_bytes/1e6:<8.2f}")
    rs = rep.ratio_summary()
    if rs:
        print("  measured/predicted geomean: "
              + "  ".join(f"{k}={v:.3g}" for k, v in sorted(rs.items())))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="realize DSE checkpoint mappings as sharded JAX "
                    "programs and calibrate the cost model")
    ap.add_argument("--ckpt", required=True,
                    help="schema-v2 keep_mappings sweep checkpoint")
    ap.add_argument("--workload", action="append", default=[],
                    metavar="NAME=SPEC",
                    help="workload graph binding (preset name, "
                    "'transformer:k=v,...' or 'lm:<config>'); bare SPEC ok "
                    "for single-workload checkpoints")
    ap.add_argument("--top", type=int, default=2,
                    help="realize the K best-EDP mapped records (0 = all)")
    ap.add_argument("--mesh", default="host",
                    help="device pool: 'host' (all devices), 'production' "
                    "(256-chip pod), 'production2' (512), or a count")
    ap.add_argument("--host-devices", type=int, default=16,
                    help="virtual host devices to force before jax init "
                    "(0 = leave the backend alone)")
    ap.add_argument("--out", default="results/realize.jsonl",
                    help="resumable measured-vs-predicted report (JSONL)")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit + write the Tech overlay from all records")
    ap.add_argument("--overlay-out", default=None,
                    help="overlay path (default: <out>.overlay.json)")
    ap.add_argument("--no-exec", action="store_true",
                    help="compile + measure only; skip execution")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.core.explore import ResumableSweep
    from repro.dist.retrying import RetryPolicy, retry_call
    from repro.launch.cli import resolve_workloads, workload_bindings
    from repro.realize.calibrate import fit_overlay, save_overlay
    from repro.realize.measure import measure_candidate
    from repro.realize.plan import (checkpoint_workload_fingerprints,
                                    graph_from_spec,
                                    load_realize_candidates, plans_for)
    from repro.realize.program import build_program

    ckpt = Path(args.ckpt)
    if not ckpt.exists():
        raise SystemExit(f"checkpoint {ckpt} not found")
    # parse the (potentially large) mapping checkpoint exactly once; the
    # open retries briefly — on shared filesystems the sweep artifact may
    # still be settling (NFS attribute-cache lag right after a merge)
    ckpt_retry = RetryPolicy(max_attempts=3, base_s=0.2, max_s=2.0,
                             retryable=(OSError,))
    ck_sweep = retry_call(ResumableSweep.read, ckpt, policy=ckpt_retry,
                          label="realize.read_ckpt")
    wl_names = sorted({rec["workload"]
                       for rec in ck_sweep.as_dict().values()
                       if "workload" in rec})
    if not args.workload:
        raise SystemExit(
            f"checkpoint has workload(s) {wl_names}; bind each with "
            f"--workload NAME=SPEC (e.g. --workload TF=tf-quick)")
    # shared NAME=SPEC grammar (launch.cli): a bare SPEC binds to the
    # checkpoint's single workload; several workloads need explicit names
    workloads = resolve_workloads(
        workload_bindings(args.workload, names=wl_names),
        builder=graph_from_spec)
    cands = load_realize_candidates(ckpt, workloads, top=args.top,
                                    sweep=ck_sweep)
    pool = _device_pool(args.mesh)
    print(f"[realize] {len(cands)} candidate(s) from {ckpt}, "
          f"device pool: {len(pool)} x {pool[0].platform}")

    fps = checkpoint_workload_fingerprints(ckpt)
    fp = ("realize:v1:" + ",".join(f"{n}:{fps.get(n, '?')}" for n in wl_names)
          + f":pool={len(pool)}:exec={int(not args.no_exec)}")
    out = Path(args.out)
    if args.force and out.exists():
        out.unlink()
    sweep = retry_call(ResumableSweep, out, fp, policy=ckpt_retry,
                       label="realize.open_out")

    t0 = time.time()
    for cand, plan in plans_for(cands, len(pool)):
        if cand.key in sweep:
            print(f"[realize] {cand.arch.label()} x {cand.workload}: "
                  f"resumed from {out}")
            continue
        prog = build_program(cand.graph, plan, devices=pool)
        prog.compile_all()
        rep = measure_candidate(cand, prog, execute=not args.no_exec)
        _print_report(rep)
        sweep.add(cand.key, rep.to_record())
    print(f"[realize] report -> {out} ({len(sweep)} records, "
          f"{time.time() - t0:.1f}s)")

    if args.calibrate:
        overlay = fit_overlay(list(sweep.as_dict().values()),
                              source=f"{ckpt.name}|pool={len(pool)}")
        op = Path(args.overlay_out) if args.overlay_out \
            else out.with_suffix(".overlay.json")
        save_overlay(overlay, op)
        print(f"[realize] Tech overlay (from {overlay.n_stages} stages): "
              f"f_d2d={overlay.f_d2d:.3g} f_noc={overlay.f_noc:.3g} "
              f"f_dram={overlay.f_dram:.3g} -> {op}")
        print("[realize] second pass: run_dse(calibrated_candidates("
              "cands, load_overlay(...)), ...) searches with "
              "measured-calibrated costs")


if __name__ == "__main__":
    from .cli import enable_compile_cache
    enable_compile_cache()
    main()
