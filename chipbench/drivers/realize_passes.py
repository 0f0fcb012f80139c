"""Driver of ``realize_passes`` mixes: back-to-back passes of a realized plan.

Set-up takes the mix's one candidate through the path a user takes: a
T-Map sweep (``run_dse(use_sa=False)``) into a ``keep_mappings``
checkpoint, ``load_realize_candidates`` -> ``plans_for`` ->
``build_program`` (Pallas kernels, compiled on the TPU) under
``jax.default_matmul_precision("highest")``, ``compile_all`` and one warm
pass.  The window runs ``RealizedProgram.execute(seed=s_k)`` passes until
``--seconds`` has passed (the pass in flight finishes); ``s_k`` are drawn
from ``--seed``.  A traced run traces the first ``trace.passes`` passes
and stops there.

Check: a few passes drawn from the seed keep their exported cubes; once
the window has closed and the memory peak is read, the program is freed
and ``reference/realized.py`` recomputes those passes from the same seeds
at ``highest``, over the layers that the configuration's reference module
gives (``realized_layers``), stage by stage as the plan runs them: the
reference is handed the plan's stages as lists of layer names in
execution order, and nothing else of the program.  ``cube_rel_err`` is the
worst relative error in norm over every cube that those passes export.
"""

from __future__ import annotations

import gc
import shutil
import time
from contextlib import nullcontext
from typing import Any, Dict, List

import numpy as np

from chipbench import trace as tr
from chipbench.reference import realized as ref

# limit of cube_rel_err; PERF.md gives the readings it was set from
CUBE_REL_ERR_LIMIT = 5e-6
# what the configuration's reference module has to give
REFERENCE_NEEDS = ("realized_layers",)


def pass_seeds(seed: int):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xBA55]))
    while True:
        yield int(rng.integers(0, 2 ** 31 - 1))


def run(r, t_start: float) -> None:
    import jax

    from repro.core.dse import DSEConfig, grid_candidates, run_dse
    from repro.core.workloads import make_workload
    from repro.realize.plan import load_realize_candidates, plans_for
    from repro.realize.program import build_program

    cfg, mix = r.cell.config, r.cell.mix
    a = mix["arch"]
    wl = cfg["workload"]["name"]
    (arch,) = grid_candidates(
        float(cfg["tops"]), mac_options=(a["macs_per_core"],),
        cut_options=(a["xcut"],), dram_per_tops=(a["dram_per_tops"],),
        noc_options=(a["noc_bw"],), d2d_ratio=(a["d2d_ratio"],),
        glb_options=(a["glb_kb"],))
    g = make_workload(r.cell.spec)
    ckpt = r.work_dir / "realize.ckpt.jsonl"
    ckpt.unlink(missing_ok=True)
    run_dse([arch], {wl: g}, DSEConfig(batch=int(cfg["batch"]),
                                       keep_mappings=True),
            use_sa=False, n_workers=1, checkpoint=ckpt)
    cands = load_realize_candidates(ckpt, {wl: g}, verbose=False)
    devices = r.devices[:int(mix["devices"])]
    (_, plan), = plans_for(cands[:1], len(devices))
    with jax.default_matmul_precision("highest"):
        prog = build_program(g, plan, devices=devices)
        prog.compile_all()
        r.obs["setup_compile_s"] = sum(sp.compile_s for sp in prog.stages)
        prog.execute(seed=0)                       # warm pass

    # the plan's stages cross to the reference as layer names alone
    stages = [list(sp.stage.layers) for sp in prog.stages]
    layers = {l.name: l for l in r.cell.reference.realized_layers(cfg)}
    unknown = sorted({n for st in stages for n in st} - set(layers))
    if unknown:
        r.fail(f"the plan has layers its reference lacks: {unknown[:5]}")
    in_order = [layers[n] for st in stages for n in st]
    bu = prog.batch_unit
    r.obs["batch_unit"] = bu
    r.obs["pass_macs"] = ref.pass_macs(in_order, bu)
    r.obs["gemm_shapes"] = ref.gemm_shapes(in_order, bu)

    chk = mix["check"]
    rng = np.random.default_rng(np.random.SeedSequence([r.seed, 0xC4EC]))
    keep = set(int(i) for i in rng.choice(int(chk["among_first"]),
                                          size=int(chk["passes"]),
                                          replace=False))
    seeds = pass_seeds(r.seed)
    trace_dir = r.work_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    n_traced = int(mix["trace"]["passes"])
    passes: List[Dict[str, Any]] = []
    kept: Dict[int, Dict[str, Any]] = {}
    with (tr.capture(str(trace_dir)) if r.trace else nullcontext()), \
            jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        r.setup_s = t0 - t_start
        deadline = t0 + r.seconds
        with tr.annotate("window", r.trace):
            while (len(passes) < max(n_traced, 1 + max(keep)) if r.trace
                   else time.perf_counter() < deadline
                   or len(passes) <= max(keep)):
                s = next(seeds)
                p0 = time.perf_counter()
                with tr.annotate("pass", r.trace):
                    res = prog.execute(seed=s)
                passes.append({"seed": s, "wall_s": time.perf_counter() - p0,
                               "stage_s": float(sum(res["wall_s"]))})
                if len(passes) - 1 in keep:
                    kept[s] = res["outputs"]
                del res
        r.window_s = time.perf_counter() - t0
    r.read_memory_peak()
    r.attempted = len(passes)
    r.obs["passes"] = passes
    if r.trace:
        ev = tr.events_from_xplane(tr.find_xplane(str(trace_dir)))
        r.trace_summary = tr.summarize(ev)
        r.obs["trace_events"] = ev
        shutil.rmtree(trace_dir, ignore_errors=True)

    got = {s: {n: np.asarray(x) for n, x in outs.items()}
           for s, outs in kept.items()}
    del prog, kept
    gc.collect()
    worst, failed = 0.0, 0
    for s, outs in got.items():
        want = ref.forward(layers, stages, bu, s,
                           precision="high" if r.control else "highest")
        if r.control:                  # the control in the program's place
            outs, want = want, ref.forward(layers, stages, bu, s)
        errs = [ref.rel_err(outs[n], want[n]) if n in outs else float("inf")
                for n in want]
        failed += max(errs) > CUBE_REL_ERR_LIMIT
        worst = max(worst, max(errs))
    r.failed = int(failed)
    r.check("cube_rel_err", worst, CUBE_REL_ERR_LIMIT)
