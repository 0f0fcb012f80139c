#!/usr/bin/env python3
"""Bring-up check of the co-exploration main path on one TPU chip.

Everything runs in this one process, through the entry points a user
calls, at the widths of the paper's Table-I deployment (``transformer()``:
6 layers, d_model 512, d_ff 2048, seq 512, batch 64, a 72-TOPS grid):

1. device check — JAX must see a TPU; there is no CPU path;
2. sweep — a handful of Table-I candidates plus one 1x1-core candidate,
   scored by ``run_dse`` with the fused scorer on the chip
   (``SAConfig(backend="jax", n_chains=4, lockstep=True)``), checkpointed
   with their mappings.  Checks: the fused pass ran on the chip; every
   reported objective equals an exact NumPy re-score bit for bit; the
   fused scores sit inside their ~1e-4 envelope of the exact ones;
3. realize — the 1x1-core candidate, loaded back from that checkpoint,
   built as a Pallas program and as its jnp-oracle twin, every stage
   compiled, both run, outputs compared; then the three Pallas kernels at
   the graph's widths against their oracles.

``--four-chips`` runs only the path that exists across chips: a 2x2-core
candidate's plan realized over four chips and compared with its jnp twin
on the same meshes.

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chips

Inputs are generated from ``--seed``; outputs (the sweep checkpoint) go to
``chiprun_out/chip_smoke/``.  Any failed check raises, so the last line —
``{"ok": true, "device": {...}}`` — is printed only after every check
passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out" / "chip_smoke"

SA_ITERS = 48                 # "a few dozen" lockstep iterations
N_TABLE1 = 5                  # Table-I candidates swept beside the 1x1 one
FUSED_REL_TOL = 1e-4          # fused pass envelope (DESIGN.md, jax caveats)

# Tolerance of a Pallas-vs-oracle comparison.  Every comparison below runs
# under jax_default_matmul_precision="highest": each f32 dot — in the
# Pallas kernels (Mosaic's fp32 contract precision) and in XLA's jnp
# oracles — is computed on the MXU to f32 grade by multi-pass bf16
# emulation rather than a single bf16 pass.  Split each f32 operand into
# three bf16 pieces, a = a1 + a2 + a3 with |a2| < 2**-8 |a| and
# |a3| < 2**-16 |a|.  The cheapest such scheme (3 passes) keeps a1*b1,
# a1*b2 and a2*b1 and drops a2*b2, a1*b3 and a3*b1: at most 3 * 2**-16 of
# each product, so the two sides differ by at most 6 * 2**-16 < 2**-13
# per product.  Rounding errors of random data carry random signs, so
# that bound holds for the dot's result in relative norm too.  The f32
# accumulation orders also differ (the kernel sums bk-wide blocks),
# adding ~log2(K) * 2**-24 — negligible beside it.  So one chained dot
# moves a result by at most 2**-13 in relative norm, and a chain of D dots
# (every realized layer rescales by 1/sqrt(C), so magnitudes stay ~1) by
# at most D * 2**-13.  A single bf16 pass (2**-8 per operand) fails it.
PER_DOT_TOL = 2.0 ** -13


def _say(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(got, want) -> float:
    """Relative error in norm, ||got - want|| / ||want||; raises unless
    both are finite, of one shape, and the oracle is not all zeros."""
    import numpy as np
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    if g.shape != w.shape:
        raise AssertionError(f"shape {g.shape} != oracle shape {w.shape}")
    if not (np.isfinite(g).all() and np.isfinite(w).all()):
        raise AssertionError("non-finite values")
    norm = np.linalg.norm(w)
    if norm == 0:
        raise AssertionError("the oracle's output is all zeros")
    return float(np.linalg.norm(g - w) / norm)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)
    _say(f"  ok: {msg}")


# ---------------------------------------------------------------------------
# sweep phase
# ---------------------------------------------------------------------------

def sweep_phase(ckpt: Path, seed: int):
    """Score Table-I candidates with the fused scorer into ``ckpt``;
    returns the workloads."""
    import jax
    import numpy as np

    from benchmarks.table1_dse import TOPS, _setup
    from repro.core.dse import TaskResult, grid_candidates, reduce_tasks, run_dse
    from repro.core.evaluator import FUSED_STATS, Evaluator
    from repro.core.sa import SAConfig

    cands, workloads, cfg, _ = _setup(quick=False)
    pick = np.linspace(0, len(cands) - 1, N_TABLE1).astype(int)
    one_core = grid_candidates(
        TOPS, mac_options=(int(TOPS * 1e3 / 2),), cut_options=(1,),
        dram_per_tops=(2.0,), noc_options=(32,), d2d_ratio=(0.5,),
        glb_options=(2048,))
    sweep = [cands[i] for i in pick] + one_core
    assert one_core[0].n_cores == 1 and one_core[0].tops == TOPS
    cfg = dataclasses.replace(
        cfg, keep_mappings=True,
        sa=SAConfig(iters=SA_ITERS, seed=seed, n_chains=4, lockstep=True,
                    backend="jax"))
    _say(f"[sweep] {len(sweep)} candidates of the {TOPS:g}-TOPS Table-I "
         f"space on transformer() ({len(workloads['TF'].layers)} layers, "
         f"batch {cfg.batch}); SA {SA_ITERS} iters x 4 chains, fused "
         f"scorer; checkpoint {ckpt}")
    if ckpt.exists():
        ckpt.unlink()                    # a fresh sweep, never a resume
    t0 = time.perf_counter()
    points = run_dse(sweep, workloads, cfg, n_workers=1, checkpoint=ckpt)
    _say(f"[sweep] wall {time.perf_counter() - t0:.3f} s")
    for p in points:
        _say(f"  {p.arch.label():44s} objective {p.objective!r}")

    platform = jax.devices()[0].platform
    _check(FUSED_STATS["calls"] > 0
           and FUSED_STATS["platforms"] == {platform},
           f"fused pass ran {FUSED_STATS['calls']} times, results on "
           f"{sorted(FUSED_STATS['platforms'])}")

    g = workloads["TF"]
    worst = 0.0
    differ = []
    for p in points:
        mapping = p.mappings["TF"]
        ev = Evaluator(p.arch, g)                     # fresh, exact
        exact = ev.evaluate(mapping, cfg.batch)
        q = reduce_tasks(p.arch, cfg, {"TF": TaskResult(
            energy_j=exact.energy_j, delay_s=exact.delay_s)})
        if (q.objective, exact.energy_j, exact.delay_s) != \
                (p.objective, *p.per_workload["TF"]):
            differ.append((p.arch.label(), p.objective, q.objective))
        fused = ev.eval_requests_batch(list(mapping), cfg.batch,
                                       backend="jax")
        for ge, (gf, _) in zip(exact.groups, fused):
            for a, b in ((ge.delay_s, gf.delay_s),
                         (ge.energy_j, gf.energy_j)):
                worst = max(worst, abs(a - b) / abs(a))
    _check(not differ, f"{len(points) - len(differ)}/{len(points)} reported "
                       f"objectives equal their exact NumPy re-scores bit "
                       f"for bit {differ or ''}")
    _check(worst < FUSED_REL_TOL,
           f"fused group scores within {worst:.3e} relative of exact "
           f"(envelope {FUSED_REL_TOL:g})")
    return workloads


# ---------------------------------------------------------------------------
# realize phase
# ---------------------------------------------------------------------------

def realize_phase(ckpt: Path, workloads, n_cores: int, seed: int):
    """Realize the ``n_cores``-core candidate of ``ckpt`` as a Pallas
    program and as its jnp twin; compare them.  Returns (compile seconds,
    the Pallas program's RealizationReport, the device ids its stage
    meshes span)."""
    import jax

    from repro.realize.measure import measure_candidate
    from repro.realize.plan import load_realize_candidates, plans_for
    from repro.realize.program import build_program

    devices = jax.devices()
    cands = [c for c in load_realize_candidates(ckpt, workloads, verbose=False)
             if c.arch.n_cores == n_cores]
    (cand, plan), = plans_for(cands[:1], len(devices))
    g = cand.graph
    _say(f"[realize] {cand.arch.label()}: {len(plan.stages)} stages, "
         f"batch unit {plan.batch_unit}, {plan.n_devices_needed} device(s)")
    with jax.default_matmul_precision("highest"):
        prog = build_program(g, plan, devices=devices, interpret=False)
        twin = build_program(g, plan, devices=devices, use_pallas=False)
        prog.compile_all()
        twin.compile_all()
        rep = measure_candidate(cand, prog, execute=True, seed=seed)
        got = prog.execute(seed=seed)["outputs"]
        want = twin.execute(seed=seed)["outputs"]
    no_kernel = []
    for sp, tp, sr in zip(prog.stages, twin.stages, rep.stages):
        kernels = sorted({r.split(":")[0] for r in sp.routes.values()}
                         & {"matmul", "scores", "flash", "ssd"})
        custom = "tpu_custom_call" in sp.compiled.as_text()
        _say(f"  stage {sp.index:2d} {'+'.join(sp.stage.layers)[:40]:40s} "
             f"devices {sp.n_devices} routes {','.join(kernels) or '-':12s} "
             f"compile pallas {sp.compile_s:.3f} s jnp {tp.compile_s:.3f} s "
             f"wall {sr.wall_s:.6f} s tpu_custom_call {custom}")
        if kernels and not custom:
            no_kernel.append(sp.index)
    _check(not no_kernel, f"every stage routed through Pallas holds a "
                          f"tpu_custom_call {no_kernel or ''}")
    compile_s = sum(sp.compile_s for sp in prog.stages + twin.stages)
    _say(f"[realize] compile seconds: pallas "
         f"{sum(sp.compile_s for sp in prog.stages):.3f} + jnp "
         f"{sum(sp.compile_s for sp in twin.stages):.3f}")
    _say(f"[realize] ratio_summary (measured/predicted): "
         f"{json.dumps(rep.ratio_summary(), sort_keys=True)}")

    tol = len(g.layers) * PER_DOT_TOL
    worst = max(_rel_err(got[n], want[n]) for n in want)
    _check(set(got) == set(want) and worst <= tol,
           f"{len(want)} realized output cubes: Pallas vs jnp twin worst "
           f"relative error {worst:.3e} <= {tol:.3e} "
           f"({len(g.layers)} chained layers x 2**-13)")
    span = {d.id for sp in prog.stages for d in sp.mesh.devices.flat}
    return compile_s, rep, span


def kernel_phase(seed: int) -> float:
    """ops.matmul / flash_attention / ssd_forward at the Table-I widths
    against their oracles.  Returns compile seconds."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.mamba_ssd import ssd_chunk_dual
    from repro.nn.mamba2 import ssd_chunked

    B, S, D, F = 64, 512, 512, 2048           # batch, seq, d_model, d_ff
    H, P, N, Q = 4, 128, 64, 128              # heads x head dim, state, chunk
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    rnd = lambda *shape: jax.random.normal(next(ks), shape, jnp.float32)

    t = lambda x: x.transpose(0, 2, 1, 3)     # (B,S,H,D) <-> (B,H,S,D)
    x, w = rnd(B * S, D), rnd(D, F)
    q, k, v = rnd(B, S, H, P), rnd(B, S, H, P), rnd(B, S, H, P)
    xs, dt = rnd(B, S, H, P), jax.nn.softplus(rnd(B, S, H)) * 0.1
    A = -jnp.exp(rnd(H))
    Bm, Cm = rnd(B, S, 1, N) * 0.1, rnd(B, S, 1, N) * 0.1
    xc, cum = rnd(B * S // Q, Q, H, P), jnp.cumsum(-0.1 * jnp.abs(
        rnd(B * S // Q, Q, H)), axis=1)
    Bc, Cc = rnd(B * S // Q, Q, N), rnd(B * S // Q, Q, N)
    cases = [
        # name, kernel, oracle, args, chained dots
        ("ops.matmul", ops.matmul, ref.matmul_ref, (x, w), 1),
        ("ops.flash_attention", ops.flash_attention,
         lambda q, k, v: t(ref.attention_ref(t(q), t(k), t(v))),
         (q, k, v), 2),
        ("ssd_chunk_dual", lambda *a: ssd_chunk_dual(*a, interpret=False),
         ref.ssd_chunk_ref, (xc, cum, Bc, Cc), 2),
        ("ops.ssd_forward",
         lambda *a: ops.ssd_forward(*a, chunk=Q)[0],
         lambda *a: ssd_chunked(*a, chunk=Q)[0], (xs, dt, A, Bm, Cm), 3),
    ]
    compile_s = 0.0
    with jax.default_matmul_precision("highest"):
        for name, kernel, oracle, args, depth in cases:
            t0 = time.perf_counter()
            compiled = jax.jit(kernel).lower(*args).compile()
            dt_c = time.perf_counter() - t0
            compile_s += dt_c
            _check("tpu_custom_call" in compiled.as_text(),
                   f"{name} compiles to a Pallas kernel ({dt_c:.3f} s)")
            got = jax.block_until_ready(compiled(*args))
            want = jax.jit(oracle)(*args)
            outs = zip(got, want) if isinstance(got, tuple) \
                else [(got, want)]
            worst = max(_rel_err(a, b) for a, b in outs)
            tol = depth * PER_DOT_TOL
            _check(worst <= tol,
                   f"{name} {tuple(args[0].shape)} vs oracle: relative "
                   f"error {worst:.3e} <= {tol:.3e}")
    return compile_s


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-chip realization phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (REPO / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py: no repro package under "
                         f"{REPO / 'src'}; run it from the repository")
    sys.path.insert(0, str(REPO / "src"))

    import jax
    devices = jax.devices()
    dev = devices[0]
    _say(f"[device] platform {dev.platform} kind {dev.device_kind!r} "
         f"count {len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py: needs a TPU, but JAX's devices "
                         f"are {dev.platform!r}")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke.py: needs {want} chips, JAX sees "
                         f"{len(devices)}")

    from repro.launch.cli import enable_compile_cache
    _say(f"[device] compile cache {enable_compile_cache()}")
    OUT.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()

    if args.four_chips:
        from repro.core.dse import DSEConfig, grid_candidates, run_dse
        from repro.core.workloads import transformer
        g = transformer()
        arch = grid_candidates(72.0, mac_options=(9000,), cut_options=(1,),
                               dram_per_tops=(2.0,), noc_options=(32,),
                               d2d_ratio=(0.5,), glb_options=(2048,))[0]
        assert (arch.x_cores, arch.y_cores) == (2, 2)
        ckpt = OUT / "four_chips.ckpt.jsonl"
        if ckpt.exists():
            ckpt.unlink()
        # the plan's mapping: T-Map, kept in the checkpoint (no SA — this
        # call is for the mesh, not the search)
        run_dse([arch], {"TF": g}, DSEConfig(batch=64, keep_mappings=True),
                use_sa=False, checkpoint=ckpt)
        compile_s, rep, span = realize_phase(ckpt, {"TF": g}, 4, args.seed)
        ici = sum(s.ici_bytes for s in rep.stages)
        _check(len(span) == 4,
               f"stage meshes span {len(span)} distinct devices")
        _check(ici > 0, f"realized program moves {ici:.0f} ICI bytes")
    else:
        ckpt = OUT / "table1_sweep.ckpt.jsonl"
        workloads = sweep_phase(ckpt, args.seed)
        compile_s, _, _ = realize_phase(ckpt, workloads, 1, args.seed)
        compile_s += kernel_phase(args.seed)
    _say(f"[done] compile seconds {compile_s:.3f}; total wall "
         f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
