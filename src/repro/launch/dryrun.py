import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
init.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json

Results stream into the JSON after every cell so interrupted runs resume
(cells already present are skipped unless --force).
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs.base import SHAPES, all_archs, cells_for, get_config
from .mesh import make_production_mesh
from .roofline import (analyze_compiled, flash_kernel_adjustment,
                       model_flops_for)
from .steps import input_specs, make_cell  # noqa: F401  (input_specs is API)


# The production meshes model v5e pods on virtual host devices, so the
# roofline is taken against the v5e's peaks, not the host's.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             rules_overrides=None, cfg_overrides=None, **cell_kw) -> dict:
    """Lower + compile one cell; returns the roofline/memory record."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    from ..nn.params import default_rules
    rules = default_rules(**(rules_overrides or {}))
    t0 = time.time()
    with mesh:
        bundle = make_cell(cfg, shape, mesh, rules, **cell_kw)
        lowered = bundle.fn.lower(*bundle.args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    rl = analyze_compiled(
        f"{arch}/{shape_name}/{mesh_kind}", compiled, None,
        model_flops_for(cfg, shape), n_dev, TARGET_DEVICE_KIND,
        compile_s=t_compile)
    rec = rl.to_dict()
    from .roofline import flash_kernel_adjustment
    adj = flash_kernel_adjustment(cfg, shape,
                                  n_pod=2 if mesh_kind == "multi" else 1)
    rec["flash_adj_bytes"] = adj
    rec["t_memory_kernel"] = max(0.0, (rl.bytes_per_device - adj)) \
        / rl.chip.hbm_bw
    rec.update({"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "lower_s": t_lower, "desc": bundle.static_desc,
                "ok": True})
    # the proof-it-fits printout the dry-run spec requires
    ma = compiled.memory_analysis()
    print(f"  memory_analysis: args={ma.argument_size_in_bytes/1e9:.2f}GB "
          f"out={ma.output_size_in_bytes/1e9:.2f}GB "
          f"temp={ma.temp_size_in_bytes/1e9:.2f}GB per device")
    ca = compiled.cost_analysis()
    print(f"  cost_analysis: flops/dev={ca.get('flops', 0):.3e} "
          f"bytes/dev={ca.get('bytes accessed', 0):.3e}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--micro", type=int, default=0,
                    help="override microbatch count (0 = auto)")
    args = ap.parse_args()

    archs = list(all_archs()) if args.arch == "all" else args.arch.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    # --force re-runs the SELECTED cells only; cached results for other
    # cells are always preserved (a --force on a subset must not wipe the
    # rest of the table)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = list(cells_for(cfg)) if args.shape == "all" \
            else [s for s in args.shape.split(",") if s in cells_for(cfg)]
        for shape_name in shapes:
            for mesh_kind in meshes:
                key = f"{arch}|{shape_name}|{mesh_kind}"
                if key in results and results[key].get("ok") and not args.force:
                    print(f"[skip] {key} (cached)")
                    continue
                print(f"[cell] {key} ...", flush=True)
                t0 = time.time()
                kw = {}
                if args.micro and SHAPES[shape_name].kind == "train":
                    kw["n_micro"] = args.micro
                if args.zero1 and SHAPES[shape_name].kind == "train":
                    kw["zero1"] = True
                try:
                    rec = run_cell(arch, shape_name, mesh_kind, **kw)
                    print(f"[ok]   {key}  compute={rec['t_compute']*1e3:.2f}ms "
                          f"memory={rec['t_memory']*1e3:.2f}ms "
                          f"coll={rec['t_collective']*1e3:.2f}ms "
                          f"bneck={rec['bottleneck']} "
                          f"({time.time()-t0:.0f}s)", flush=True)
                except Exception as e:  # noqa: BLE001 - report, keep going
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "ok": False,
                           "error": f"{type(e).__name__}: {e}"}
                    n_fail += 1
                    print(f"[FAIL] {key}: {rec['error'][:200]}", flush=True)
                results[key] = rec
                out_path.write_text(json.dumps(results, indent=1))
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed, "
          f"results -> {out_path}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    from .cli import enable_compile_cache
    enable_compile_cache()
    main()
