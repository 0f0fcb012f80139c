"""Space-size table (paper Sec. IV-B), SA/evaluator/DSE throughput, kernel
micro-benchmarks (interpret-mode correctness + measured wall time).

``python -m benchmarks.misc_bench --smoke`` runs only a tiny end-to-end
exercise of the exploration engine (screening + parallel workers + replica
exchange + checkpoint resume + Pareto frontier) sized for CI.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dse import DSEConfig, grid_candidates, run_dse
from repro.core.encoding import space_size_lower_bound, tangram_space_upper_bound
from repro.core.evaluator import CachedEvaluator, Evaluator
from repro.core.explore import merge_checkpoints, pareto_frontier
from repro.core.graph_partition import partition_graph
from repro.core.hw import simba_arch
from repro.core.sa import SAConfig, sa_optimize
from repro.core.tangram import tangram_map
from repro.core.workloads import transformer

from .common import RESULTS, cached


def space_size() -> Dict:
    import math
    rows = []
    for n, m in ((4, 16), (8, 36), (12, 64), (16, 100)):
        ours = space_size_lower_bound(n, m)       # arbitrary-precision int
        theirs = tangram_space_upper_bound(n, m)
        lo, lt = math.log10(ours), math.log10(theirs)
        rows.append({"N": n, "M": m, "ours_log10": lo, "tangram_log10": lt})
        print(f"[space] N={n:3d} M={m:3d}: ours 1e{lo:.0f} "
              f"vs tangram 1e{lt:.1f}")
    return {"rows": rows}


def sa_throughput() -> Dict:
    arch = simba_arch()
    g = transformer()
    groups = partition_graph(g, arch, 64)
    ev = Evaluator(arch, g)
    init = tangram_map(groups, g, arch)
    # warm caches
    sa_optimize(g, arch, groups, 64, SAConfig(iters=50, seed=0),
                init=init, evaluator=ev)
    iters = 1000
    t0 = time.time()
    sa_optimize(g, arch, groups, 64, SAConfig(iters=iters, seed=1),
                init=init, evaluator=ev)
    dt = time.time() - t0
    print(f"[sa] {iters / dt:.0f} SA iters/s ({dt / iters * 1e3:.2f} ms/iter) "
          f"on {g.name} x {arch.label()}")
    return {"iters_per_s": iters / dt, "ms_per_iter": dt / iters * 1e3}


def evaluator_throughput() -> Dict:
    """Evals/sec of the vectorized+cached engine vs the seed scalar engine.

    The seed engine is preserved verbatim in ``repro.core.seed_reference``
    and timed IN THE SAME PROCESS, so the reported speedup is a property of
    the code, not of the machine's load when the benchmark ran.  Regimes:

      * ``sa_iters_per_s`` / ``seed_sa_iters_per_s`` — the SA iteration
        microbenchmark: identical fresh 6000-iteration chains (the paper's
        default SA budget; one touched-group eval per proposal) for both
        engines, interleaved, best of two rounds each;
      * ``cold_evals_per_s``  — ``eval_group`` over a stream of novel SA
        candidates on a fresh evaluator (no content-cache hits);
      * ``cached_evals_per_s`` — repeated mappings through CachedEvaluator
        (the MC-sampling / re-anneal regime, pure cache hits).
    """
    from repro.core.sa import _Op
    from repro.core.seed_reference import ReferenceEvaluator

    arch = simba_arch()
    g = transformer()
    groups = partition_graph(g, arch, 64)
    init = tangram_map(groups, g, arch)

    # --- SA iteration microbenchmark: seed vs new, interleaved -----------
    # identical 6000-iteration chains (the engines walk the same trajectory
    # because their costs are bit-identical); alternating them and keeping
    # the best of two rounds cancels machine-load drift between the timed
    # sections.  Fresh evaluator per round; the module-level intra-core
    # memo warms across rounds for BOTH engines symmetrically.
    def time_chain(evaluator, iters):
        t0 = time.time()
        sa_optimize(g, arch, groups, 64, SAConfig(iters=iters, seed=1),
                    init=init, evaluator=evaluator)
        return iters / (time.time() - t0)

    seed_rate = sa_rate = 0.0
    for _ in range(2):
        seed_rate = max(seed_rate, time_chain(ReferenceEvaluator(arch, g), 6000))
        sa_rate = max(sa_rate, time_chain(CachedEvaluator(arch, g), 6000))

    # --- cold eval_group stream (novel candidates, fresh evaluator) ------
    rng = np.random.default_rng(0)
    ops = _Op(g, arch, rng)
    stream = []
    for grp, lms in init:
        cur = lms
        for _ in range(40):
            cand = ops.op1(grp, cur) or ops.op2(grp, cur) or cur
            stream.append((grp, cand))
            cur = cand
    ev_cold = Evaluator(arch, g)
    t0 = time.time()
    for grp, lms in stream:
        ev_cold.eval_group(grp, lms, 64)
    cold_rate = len(stream) / (time.time() - t0)
    ref_cold = ReferenceEvaluator(arch, g)
    t0 = time.time()
    for grp, lms in stream:
        ref_cold.eval_group(grp, lms, 64)
    seed_cold_rate = len(stream) / (time.time() - t0)

    # --- content-cache hits (repeated mappings) --------------------------
    ev_hot = CachedEvaluator(arch, g)
    ev_hot.evaluate(init, 64)
    reps = 200
    t0 = time.time()
    for _ in range(reps):
        ev_hot.evaluate(init, 64)
    hot_rate = reps * len(init) / (time.time() - t0)

    sa_speedup = sa_rate / seed_rate
    cold_speedup = cold_rate / seed_cold_rate
    print(f"[eval] SA microbenchmark: {sa_rate:.0f} iters/s vs seed "
          f"{seed_rate:.0f} iters/s -> {sa_speedup:.1f}x")
    print(f"[eval] cold eval_group:   {cold_rate:.0f} evals/s vs seed "
          f"{seed_cold_rate:.0f} evals/s -> {cold_speedup:.1f}x")
    print(f"[eval] cached eval_group: {hot_rate:.0f} evals/s "
          f"(cache {ev_hot.cache_info()})")
    return {"sa_iters_per_s": sa_rate,
            "seed_sa_iters_per_s": seed_rate,
            "sa_speedup_vs_seed": sa_speedup,
            "cold_evals_per_s": cold_rate,
            "seed_cold_evals_per_s": seed_cold_rate,
            "cold_speedup_vs_seed": cold_speedup,
            "cached_evals_per_s": hot_rate}


def _dse_grid(n: int):
    """First ``n`` candidates of a trimmed Table-I-style 72-TOPS grid."""
    cands = grid_candidates(
        72.0, mac_options=(512, 1024, 2048), cut_options=(1, 2, 3, 6),
        dram_per_tops=(1.0, 2.0), noc_options=(16, 32), d2d_ratio=(0.5, 1.0),
        glb_options=(1024, 2048))
    assert len(cands) >= n, f"grid too small: {len(cands)} < {n}"
    return cands[:n]


def dse_throughput(n_candidates: int = 64, n_workers: int = 4,
                   iters: int = 1500, n_workloads: int = 1) -> Dict:
    """Wall-clock of a >=64-task SA sweep: serial vs ``n_workers``.

    Screening is OFF, so the speedup is attributable to process parallelism
    alone; the bit-identical check confirms the parallel path computes the
    exact same points.  The SA budget is the Table-I refinement default
    (1500 iters), so per-task work dominates the one-time worker startup as
    it does in a real sweep.  The speedup ceiling is min(n_workers,
    effective cores): on the paper's 80-thread Xeon the same sweep spreads
    over every core; a cgroup-throttled container can sit well below its
    nominal nproc (the CI container measured 1.12x at nproc=2 because only
    ~1.3 cores of capacity were actually grantable), which is why
    cpu_count is recorded next to the ratio.

    ``n_workloads > 1`` is the **(candidate x workload) fan-out mode**: the
    engine's unit of work is one (candidate, workload) pair, so a sweep of
    ``n_candidates`` over ``n_workloads`` schedules their product as
    independently-stealable tasks — with many workloads the pool load-
    balances within a candidate, not just across candidates (a single
    slow candidate no longer serializes its workload list).
    """
    import os
    workloads = {
        f"TF{i}": transformer(n_layers=2, d_model=256, d_ff=512,
                              seq=96 + 32 * i, name=f"tf-m{i}")
        for i in range(n_workloads)}
    cands = _dse_grid(n_candidates)
    cfg = DSEConfig(batch=64, sa=SAConfig(iters=iters, seed=0))
    n_tasks = n_candidates * n_workloads

    t0 = time.time()
    serial = run_dse(cands, workloads, cfg)
    t_serial = time.time() - t0
    t0 = time.time()
    par = run_dse(cands, workloads, cfg, n_workers=n_workers)
    t_parallel = time.time() - t0
    identical = ([(p.arch, p.objective, p.energy_j, p.delay_s) for p in serial]
                 == [(p.arch, p.objective, p.energy_j, p.delay_s) for p in par])
    speedup = t_serial / t_parallel
    print(f"[dse] {n_candidates} candidates x {n_workloads} workloads "
          f"({n_tasks} tasks) x {iters} SA iters: "
          f"serial {t_serial:.1f}s vs {n_workers} workers {t_parallel:.1f}s "
          f"-> {speedup:.2f}x (cores={os.cpu_count()}, "
          f"bit-identical={identical})")
    return {"n_candidates": n_candidates, "sa_iters": iters,
            "n_workloads": n_workloads, "n_tasks": n_tasks,
            "n_workers": n_workers, "cpu_count": os.cpu_count(),
            "serial_s": t_serial, "parallel_s": t_parallel,
            "speedup": speedup, "identical": identical}


def dse_smoke() -> Dict:
    """CI smoke: exercise every engine feature end-to-end on a tiny grid.

    Tiny budget (8 candidates, SA iters <= 200) so it runs on every push:
    (candidate x workload) fan-out, screening, multiprocess workers,
    bit-identical check, replica-exchange SA, checkpoint + resume, sharded
    sweeps + merge, and the Pareto frontier.  Checkpoints are written under
    ``results/smoke_*.jsonl`` (recreated each run) so a failing CI job can
    upload them for post-mortem instead of losing a tempdir.
    """
    g = transformer(n_layers=2, d_model=128, d_ff=256, seq=64, name="tf-s")
    cands = _dse_grid(8)
    workloads = {"TF": g}
    cfg = DSEConfig(batch=8, sa=SAConfig(iters=150, seed=0))
    RESULTS.mkdir(exist_ok=True)
    smoke_files = []

    def _ckpt(name):
        p = RESULTS / f"smoke_{name}.ckpt.jsonl"
        if p.exists():
            p.unlink()                   # smoke always measures from scratch
        smoke_files.append(p)
        return p

    t0 = time.time()
    serial = run_dse(cands, workloads, cfg)
    par = run_dse(cands, workloads, cfg, n_workers=2)
    identical = [p.objective for p in serial] == [p.objective for p in par]
    assert identical, "parallel DSE diverged from serial"
    screened = run_dse(cands, workloads, cfg, screen_keep=0.5)
    assert len(screened) == 4
    ck = _ckpt("resume")
    run_dse(cands, workloads, cfg, checkpoint=ck)
    resumed = run_dse(cands, workloads, cfg, checkpoint=ck)
    assert [p.objective for p in resumed] == [p.objective for p in serial]
    # sharded sweep: 2 shards into independent checkpoints, merged, and the
    # merged checkpoint reconstructs the full sweep bit-identically
    shard_paths = []
    for i in range(2):
        sck = _ckpt(f"shard{i}of2")
        run_dse(cands, workloads, cfg, shard=(i, 2), checkpoint=sck)
        shard_paths.append(sck)
    merged = _ckpt("merged")
    report = merge_checkpoints(shard_paths, merged)
    assert report.n_records == len(cands) and not report.skipped
    remerged = run_dse(cands, workloads, cfg, checkpoint=merged)
    assert [p.objective for p in remerged] == [p.objective for p in serial]
    # n_chains=3 so the swap ladder has two chains and exchanges actually
    # execute (n_chains=2 degenerates and is auto-bumped by sa_optimize)
    re_cfg = DSEConfig(batch=8, sa=SAConfig(iters=150, seed=0, n_chains=3))
    re_pts = run_dse(cands[:2], workloads, re_cfg)
    frontier = pareto_frontier(serial)
    out = {"n_candidates": len(cands), "identical": identical,
           "n_screened": len(screened), "n_frontier": len(frontier),
           "n_merged_records": report.n_records,
           "re_best": re_pts[0].objective, "best": serial[0].objective,
           "_wall_s": time.time() - t0}
    print(f"[smoke] engine end-to-end OK: {out}")
    return out


def _quick_grid():
    """The Table-I --quick grid (benchmarks.table1_dse._setup(quick=True))."""
    return grid_candidates(
        72.0, mac_options=(512, 1024), cut_options=(1, 2),
        dram_per_tops=(2.0,), noc_options=(16, 32), d2d_ratio=(0.5,),
        glb_options=(1024, 2048))


def _tf_quick():
    return transformer(n_layers=2, d_model=128, d_ff=256, seq=64, name="tf-s")


def screening_throughput(rounds: int = 6) -> Dict:
    """Batched vs per-candidate T-Map screening on the Table-I quick grid.

    The reference leg is the engine's per-(candidate x workload) task loop
    (``batched_screen=False`` — the pre-batching code path, still used for
    checkpointed no-SA runs); the batched leg computes one analysis per
    bandwidth-sibling signature group and vectorizes the delay math over
    its candidates.  Interleaved best-of-``rounds`` after a symmetric
    warmup (registry cleared once up front): both legs run against warm
    per-process evaluator state, exactly how the committed
    ``pr4_baseline.json`` screening number was measured, so the
    steady-state screening algorithms are what is compared.  Scores are
    asserted bit-identical.
    """
    from repro.core.evaluator import _REGISTRY
    from repro.core.explore import ExplorationEngine

    cands = _quick_grid()
    g = _tf_quick()
    cfg = DSEConfig(batch=8, sa=SAConfig(iters=150, seed=0))
    _REGISTRY.clear()

    def leg(batched: bool):
        with ExplorationEngine({"TF": g}, cfg, batched_screen=batched) as eng:
            t0 = time.time()
            pts = eng.screen(cands)
        return time.time() - t0, pts

    leg(True); leg(False)                      # symmetric warmup
    tb = tr = 1e9
    for _ in range(rounds):
        t, pr = leg(False); tr = min(tr, t)
        # the reference leg needs 12 evaluators and cannot keep them in
        # the 8-slot registry (every round rebuilds, exactly as PR 4
        # did); the batched leg's 6 signature evaluators DO fit — that
        # registry fit is part of the batched design, so its steady
        # state is the second consecutive run after the reference
        # thrashed the registry
        leg(True)
        t, pb = leg(True); tb = min(tb, t)
    sig = lambda pts: [(p.arch, p.objective, p.energy_j, p.delay_s)
                       for p in pts]
    identical = sig(pb) == sig(pr)
    assert identical, "batched screening diverged from the reference loop"
    print(f"[screen] {len(cands)} candidates: reference {tr*1e3:.0f} ms "
          f"({len(cands)/tr:.0f} cands/s) vs batched {tb*1e3:.0f} ms "
          f"({len(cands)/tb:.0f} cands/s) -> {tr/tb:.1f}x (bit-identical)")
    return {"n_candidates": len(cands), "reference_s": tr, "batched_s": tb,
            "reference_cands_per_s": len(cands) / tr,
            "batched_cands_per_s": len(cands) / tb,
            "speedup": tr / tb, "identical": identical}


def lockstep_sa_throughput(iters: int = 400, rounds: int = 8) -> Dict:
    """Serial-loop vs lockstep n_chains=4 replica exchange, quick-grid arch.

    Same-process A/B of the stepping strategy alone: both legs use
    today's analyzer/evaluator (the serial loop therefore already includes
    this PR's shared cost-model speedups — it is a CONSERVATIVE stand-in
    for the PR-4 engine; see ``pr4_baseline.json`` for the cross-tree
    measurement).  Fresh ``CachedEvaluator`` per run, interleaved
    best-of-``rounds`` (this container's effective CPU fluctuates),
    results asserted identical.
    """
    from dataclasses import replace as _replace

    from repro.core.evaluator import CachedEvaluator
    from repro.core.explore import replica_exchange_sa
    from repro.core.graph_partition import partition_graph

    arch = _quick_grid()[0]
    g = _tf_quick()
    groups = partition_graph(g, arch, 8)
    cfg = SAConfig(iters=iters, seed=3, n_chains=4)

    def leg(lockstep: bool, backend: str = "numpy"):
        t0 = time.time()
        r = replica_exchange_sa(g, arch, groups, 8,
                                _replace(cfg, lockstep=lockstep,
                                         backend=backend),
                                evaluator=CachedEvaluator(arch, g))
        return time.time() - t0, r
    leg(True); leg(False)
    ts = tl = 1e9
    for _ in range(rounds):
        t, rs = leg(False); ts = min(ts, t)
        t, rl = leg(True); tl = min(tl, t)
    identical = (rl.cost == rs.cost and rl.energy_j == rs.energy_j
                 and rl.proposed == rs.proposed
                 and rl.accepted == rs.accepted)
    assert identical, "lockstep trajectory diverged from the serial loop"
    # opt-in fused (backend="jax") leg: parity-grade objectives, exact
    # finalize — measured for the trajectory, never identity-asserted.
    # On a CPU-only container the jit dispatch usually makes this leg
    # SLOWER than the exact engine (recorded honestly); it exists for
    # accelerator runs.
    tf = 1e9
    leg(True, backend="jax")                 # jit warm-up outside timing
    for _ in range(min(rounds, 2)):
        t, _rf = leg(True, backend="jax"); tf = min(tf, t)
    print(f"[sa-n4] {iters} iters x 4 chains: serial loop {ts:.2f}s "
          f"({iters/ts:.0f} iters/s) vs lockstep {tl:.2f}s "
          f"({iters/tl:.0f} iters/s) -> {ts/tl:.2f}x (bit-identical); "
          f"fused-jax leg {tf:.2f}s ({iters/tf:.0f} iters/s)")
    return {"iters": iters, "n_chains": 4,
            "serial_s": ts, "lockstep_s": tl, "fused_s": tf,
            "serial_iters_per_s": iters / ts,
            "lockstep_iters_per_s": iters / tl,
            "fused_iters_per_s": iters / tf,
            "speedup": ts / tl, "identical": identical}


def sweep_n4_throughput(rounds: int = 4) -> Dict:
    """Quick-grid n_chains=4 DSE wall clock (screen 0.5 + lockstep SA).

    The end-to-end figure the Table-I quick run actually pays: batched
    screening + per-candidate n_chains=4 replica-exchange refinement with
    lockstep stepping and the shared geometry caches.  Compare against
    ``pr4_baseline.json`` (same config measured at the PR-4 tree on this
    container) for the before/after of the whole batched engine.
    """
    cands = _quick_grid()
    g = _tf_quick()
    cfg = DSEConfig(batch=8, sa=SAConfig(iters=150, seed=0, n_chains=4))
    best = 1e9
    for _ in range(rounds):
        t0 = time.time()
        pts = run_dse(cands, {"TF": g}, cfg, screen_keep=0.5)
        best = min(best, time.time() - t0)
    print(f"[sweep-n4] quick grid ({len(cands)} candidates, screen 0.5, "
          f"SA 150 x 4 chains): {best:.2f}s")
    return {"n_candidates": len(cands), "wall_s": best,
            "best_objective": pts[0].objective}


def batched_parity(n_random: int = 24) -> Dict:
    """Tiny-grid batched-vs-scalar parity gate (CI bench-smoke).

    Asserts, on the quick grid workload: (1) ``eval_group_batch`` /
    ``eval_requests_batch`` rows bit-identical to scalar ``eval_group``
    over random SA proposal chains (incl. a pack/unpack round-trip);
    (2) batched screening == per-candidate screening; (3) lockstep
    replica exchange == serial loop; (4) the opt-in jax backend replays
    within float32 parity.
    """
    from repro.core.encoding import pack_lms_batch, unpack_lms_batch
    from repro.core.evaluator import CachedEvaluator, Evaluator
    from repro.core.explore import ExplorationEngine, replica_exchange_sa
    from repro.core.graph_partition import partition_graph
    from repro.core.sa import _Op

    arch = _quick_grid()[0]
    g = _tf_quick()
    groups = partition_graph(g, arch, 8)
    init = tangram_map(groups, g, arch)
    rng = np.random.default_rng(0)
    ops = _Op(g, arch, rng)
    reqs = []
    for grp, lms in init:
        cur = lms
        for _ in range(n_random // max(1, len(init))):
            cand = (ops.op1(grp, cur) or ops.op2(grp, cur)
                    or ops.op5(grp, cur) or cur)
            reqs.append((grp, cand))
            cur = cand
    ev_b = Evaluator(arch, g)
    rows = ev_b.eval_requests_batch(reqs, 8)
    ev_s = Evaluator(arch, g)
    for (grp, lms), (geb, anb) in zip(reqs, rows):
        ges, ans = ev_s.eval_group(grp, lms, 8)
        assert (ges.delay_s, ges.energy_j) == (geb.delay_s, geb.energy_j)
        assert ges.energy_breakdown == geb.energy_breakdown
        assert np.array_equal(ans.edge_bytes, anb.edge_bytes)
    grp = reqs[0][0]
    only = [lms for gg, lms in reqs if gg is grp]
    rt = unpack_lms_batch(pack_lms_batch(only, names=grp.names))
    assert [l.cache_key() for l in rt] == [l.cache_key() for l in only]

    cands = _quick_grid()[:6]
    cfg = DSEConfig(batch=8, sa=SAConfig(iters=60, seed=0))
    with ExplorationEngine({"TF": g}, cfg, batched_screen=True) as eng:
        pb = eng.screen(cands)
    with ExplorationEngine({"TF": g}, cfg, batched_screen=False) as eng:
        pr = eng.screen(cands)
    assert [(p.arch, p.objective) for p in pb] \
        == [(p.arch, p.objective) for p in pr]

    from dataclasses import replace as _replace
    re_cfg = SAConfig(iters=120, seed=5, n_chains=4)
    rl = replica_exchange_sa(g, arch, groups, 8, re_cfg,
                             evaluator=CachedEvaluator(arch, g))
    rs = replica_exchange_sa(g, arch, groups, 8,
                             _replace(re_cfg, lockstep=False),
                             evaluator=CachedEvaluator(arch, g))
    assert (rl.cost, rl.proposed, rl.accepted) \
        == (rs.cost, rs.proposed, rs.accepted)

    an = ev_b.analyzer
    ab_np = an.analyze_batch(grp, only, 8, backend="numpy")
    ab_jx = an.analyze_batch(grp, only, 8, backend="jax")
    np.testing.assert_allclose(ab_jx.buf, ab_np.buf, rtol=2e-4, atol=1e-2)

    out = {"n_requests": len(reqs), "n_screen": len(cands),
           "re_cost": rl.cost, "checks": ["batch_rows", "pack_roundtrip",
                                          "screen", "lockstep",
                                          "jax_backend"]}
    print(f"[parity] batched == scalar on {len(reqs)} rows, screening, "
          "lockstep RE and jax backend: OK")
    return out


def fused_parity(tol: float = 1e-4, n_random: int = 4,
                 seed: int = 0) -> Dict:
    """Fused jitted pass vs exact engine parity gate (CI bench-smoke).

    Runs ``eval_requests_batch(..., backend="jax")`` — one jitted
    construction→segment-sum-replay→delay/energy pass in float32 — next
    to the exact float64 numpy engine over random mappings of the
    tf/moe/mla quick workloads and asserts every objective
    (delay / energy / stage time) agrees within the documented relative
    envelope (default 1e-4; see DESIGN.md "Fused jitted pass") and that
    the argmax bottleneck stage matches.  This is the contract that lets
    SA score proposals with the fused path while winners are re-scored
    exactly.
    """
    from repro.core.encoding import random_lms
    from repro.core.evaluator import Evaluator
    from repro.core.graph_partition import partition_graph
    from repro.core.workloads import make_workload

    arch = _quick_grid()[0]
    rng = np.random.default_rng(seed)
    worst = 0.0
    n_rows = 0
    for spec in ("tf-quick", "moe-quick", "mla-quick"):
        g = make_workload(spec)
        groups = partition_graph(g, arch, 8)
        ev = Evaluator(arch, g)
        reqs = []
        for grp in groups:
            for k in range(n_random):
                reqs.append((grp, random_lms(grp, g, arch.n_cores,
                                             arch.n_dram, rng)))
        exact = ev.eval_requests_batch(reqs, 8)
        fused = ev.eval_requests_batch(reqs, 8, backend="jax")
        for (ge, an), (gf, anf) in zip(exact, fused):
            assert anf is None, "fused rows must not carry analyses"
            for a, b in ((ge.delay_s, gf.delay_s),
                         (ge.energy_j, gf.energy_j),
                         (ge.stage_time_s, gf.stage_time_s)):
                rel = abs(a - b) / max(abs(a), 1e-30)
                worst = max(worst, rel)
                assert rel < tol, (
                    f"fused parity violation on {spec}: "
                    f"{a!r} vs {b!r} (rel {rel:.2e} >= {tol:g})")
            assert ge.bottleneck == gf.bottleneck, (
                f"fused bottleneck mismatch on {spec}: "
                f"{ge.bottleneck} vs {gf.bottleneck}")
        n_rows += len(reqs)
    print(f"[fused-parity] {n_rows} rows across tf/moe/mla quick: "
          f"worst rel err {worst:.2e} < {tol:g}: OK")
    return {"n_rows": n_rows, "worst_rel_err": worst, "tol": tol}


def moe_throughput(iters: int = 300, rounds: int = 4) -> Dict:
    """Routed-MoE graph analyze/eval cost vs its equal-expected-FLOP dense
    collapse.

    Same (arch, SA budget, seed) on two lm_graph exports of
    granite-moe-3b-a800m (one block, seq=256): ``family="moe"`` — the real
    expected-traffic graph, 40 expert branches at ``traffic_scale = 8/40``
    — and the legacy ``family="moe-dense"`` collapse into one fat FFN.
    Their total expected MACs agree to <1% (the router is the only extra
    work), so the iters/s ratio isolates what the E-way branch structure
    costs the analyzer/evaluator per SA iteration: the MoE graph has ~6x
    the layers (hence bigger groups, wider contribution streams and more
    NoC flows), which is the price of modeling expert-parallel mappings at
    all.  Recorded in BENCH_dse.json (``moe_eval``).
    """
    from repro.configs import get_config
    from repro.core.workloads.lm_graph import lm_graph

    arch = _quick_grid()[0]
    base = get_config("granite-moe-3b-a800m")
    legs: Dict[str, Dict] = {}
    for fam in ("moe", "moe-dense"):
        g = lm_graph(base.replace(family=fam), seq=256, n_layers=1)
        groups = partition_graph(g, arch, 8)
        ev = CachedEvaluator(arch, g)
        init = tangram_map(groups, g, arch)
        sa_optimize(g, arch, groups, 8, SAConfig(iters=50, seed=0),
                    init=init, evaluator=ev)               # warm caches
        best = 1e9
        for _ in range(rounds):
            t0 = time.time()
            sa_optimize(g, arch, groups, 8, SAConfig(iters=iters, seed=1),
                        init=init, evaluator=ev)
            best = min(best, time.time() - t0)
        legs[fam] = {"n_layers": len(g.layers), "n_groups": len(groups),
                     "expected_macs": float(g.total_expected_macs()),
                     "iters_per_s": iters / best}
    slowdown = (legs["moe-dense"]["iters_per_s"]
                / legs["moe"]["iters_per_s"])
    macs_ratio = (legs["moe"]["expected_macs"]
                  / legs["moe-dense"]["expected_macs"])
    print(f"[moe-eval] routed graph ({legs['moe']['n_layers']} layers): "
          f"{legs['moe']['iters_per_s']:.0f} SA iters/s vs dense collapse "
          f"({legs['moe-dense']['n_layers']} layers): "
          f"{legs['moe-dense']['iters_per_s']:.0f} iters/s -> "
          f"{slowdown:.1f}x branch-structure cost "
          f"(expected-MAC parity {macs_ratio:.4f})")
    return {"iters": iters, "moe": legs["moe"],
            "dense": legs["moe-dense"],
            "dense_over_moe_iters_ratio": slowdown,
            "expected_macs_ratio": macs_ratio}


def serving_throughput(rounds: int = 4) -> Dict:
    """Discrete-event replay throughput of the serving harness.

    T-Map-screens the Table-I quick grid (deterministic), converts the
    best candidate's delay into a per-token service model, and replays
    the registered ``chat-quick`` trace under both scheduling modes —
    wave batching (the ``serve_loop`` policy) and continuous slotting
    (the ``slo`` DSE objective's model).  Reports simulated requests per
    wall-second (how cheap an SLO prediction is inside a sweep) plus the
    predicted p99s, which double as a drift canary for the queueing
    model.  Recorded in BENCH_dse.json (``serving``).
    """
    from repro.serve import (make_trace, replay, resolve_traffic,
                             service_model_from_delay)

    delay = run_dse(_quick_grid(), {"TF": _tf_quick()},
                    DSEConfig(batch=8, sa=SAConfig(iters=150, seed=0)),
                    use_sa=False)[0].delay_s
    model = service_model_from_delay(delay, batch=8, seq_ref=64)
    tm = resolve_traffic("chat-quick")
    trace = make_trace(tm.trace_spec, seed=0)
    out: Dict = {"delay_s": delay, "trace": tm.trace_spec,
                 "n_requests": len(trace.requests)}
    for mode in ("wave", "continuous"):
        rep = replay(trace, model, mode=mode, max_batch=tm.max_batch)
        best = 1e9
        for _ in range(rounds):
            t0 = time.time()
            rep = replay(trace, model, mode=mode, max_batch=tm.max_batch)
            best = min(best, time.time() - t0)
        out[mode] = {"replay_s": best,
                     "req_per_wall_s": len(trace.requests) / best,
                     "p99_ttft_s": rep.p99_ttft_s,
                     "p99_e2e_s": rep.p99_e2e_s}
        print(f"[serving] {mode}: {len(trace.requests) / best:.0f} "
              f"simulated req/s wall ({best * 1e3:.2f} ms/replay), "
              f"p99 e2e {rep.p99_e2e_s:.4g}s")
    return out


def dse_bench(quick: bool = False) -> Dict:
    """The BENCH_dse.json payload: screening / SA / sweep before-vs-after.

    ``quick`` shrinks round counts for CI.  The ``pr4_baseline`` block is
    loaded from ``benchmarks/pr4_baseline.json`` — the same configs
    measured at the PR-4 tree on this container (see its _provenance) —
    and the derived ``vs_pr4`` ratios compare against it.  The
    same-process reference legs are conservative: they already contain
    this PR's shared cost-model speedups.
    """
    import json as _json
    import os as _os
    import platform as _platform
    import sys as _sys
    from pathlib import Path

    rounds = 2 if quick else 6
    out: Dict = {
        "schema": "bench_dse/v1",
        "grid": "table1 --quick (72 TOPS, 12 candidates)",
        # container provenance: throughput numbers are only comparable
        # across runs when these match (this is a 1-CPU container)
        "provenance": {
            "cpu_count": _os.cpu_count(),
            "platform": _platform.platform(),
            "python": _sys.version.split()[0],
            "jax": getattr(jax, "__version__", None),
        },
        "screening": screening_throughput(rounds=rounds),
        "lockstep_sa": lockstep_sa_throughput(rounds=2 if quick else 8),
        "sweep_n4": sweep_n4_throughput(rounds=1 if quick else 4),
        "evaluator": sa_throughput(),
        "moe_eval": moe_throughput(rounds=2 if quick else 4),
        "serving": serving_throughput(rounds=2 if quick else 4),
    }
    base_path = Path(__file__).resolve().parent / "pr4_baseline.json"
    if base_path.exists():
        base = _json.loads(base_path.read_text())
        out["pr4_baseline"] = base
        out["vs_pr4"] = {
            "screening_speedup":
                base["screening"]["wall_s"] / out["screening"]["batched_s"],
            "sa_chain_n4_speedup":
                base["sa_chain_n4"]["wall_s"]
                / out["lockstep_sa"]["lockstep_s"],
            "sweep_n4_speedup":
                base["sweep_n4"]["wall_s"] / out["sweep_n4"]["wall_s"],
        }
        v = out["vs_pr4"]
        print(f"[bench-dse] vs PR4: screening {v['screening_speedup']:.1f}x, "
              f"n_chains=4 chain {v['sa_chain_n4_speedup']:.2f}x, "
              f"n_chains=4 quick-grid sweep {v['sweep_n4_speedup']:.2f}x")
    return out


def re_tuning(iters: int = 600, n_chains: int = 4,
              n_candidates: int = 3) -> Dict:
    """Replica-exchange knob sweep (ROADMAP): ``t_ladder`` x ``swap_every``
    on the --quick Table-I grid, reporting per-pair swap-acceptance rates
    and the best cost found.

    Healthy parallel tempering wants ~20-40% acceptance per adjacent pair:
    near 0% the ladder decouples into independent restarts, near 100% the
    rungs are so close that tempering adds nothing over one chain.  The
    ``core/sa.py`` defaults are set from this sweep (see SAConfig).
    """
    from repro.core.evaluator import evaluator_for
    from repro.core.explore import replica_exchange_sa

    cands = _dse_grid(n_candidates)
    g = transformer(n_layers=2, d_model=128, d_ff=256, seq=64, name="tf-s")
    rows = []
    for t_ladder in (1.5, 2.0, 3.0, 5.0):
        for swap_every in (10, 25, 50, 100):
            rates, costs = [], []
            for arch in cands:
                groups = partition_graph(g, arch, 8)
                cfg = SAConfig(iters=iters, seed=0, n_chains=n_chains,
                               t_ladder=t_ladder, swap_every=swap_every)
                res = replica_exchange_sa(g, arch, groups, 8, cfg,
                                          evaluator=evaluator_for(arch, g))
                rates.extend(res.swap_rates())
                costs.append(res.cost)
            mean_rate = float(np.mean(rates)) if rates else 0.0
            geo_cost = float(np.exp(np.mean(np.log(costs))))
            in_band = 0.20 <= mean_rate <= 0.40
            rows.append({"t_ladder": t_ladder, "swap_every": swap_every,
                         "swap_rate": mean_rate, "geo_cost": geo_cost,
                         "in_band": in_band})
            print(f"[retune] t_ladder={t_ladder:<4g} swap_every="
                  f"{swap_every:<4d} swap-accept={mean_rate:5.1%} "
                  f"geo-cost={geo_cost:.4e}{'  <- 20-40% band' if in_band else ''}")
    best = min(rows, key=lambda r: r["geo_cost"])
    print(f"[retune] best cost at t_ladder={best['t_ladder']} "
          f"swap_every={best['swap_every']} "
          f"(swap-accept {best['swap_rate']:.1%})")
    return {"rows": rows, "best": best}


def kernel_bench() -> Dict:
    from repro.kernels import ops, ref
    out = {}
    rng = np.random.default_rng(0)
    # flash attention (interpret mode on CPU: correctness-grade timing only)
    B, H, S, D = 1, 4, 256, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    o = ops.flash_attention(q, k, v, bq=128, bk=128)
    o.block_until_ready()
    t0 = time.time()
    for _ in range(3):
        ops.flash_attention(q, k, v, bq=128, bk=128).block_until_ready()
    flops = 4 * B * H * S * S * D
    dt = (time.time() - t0) / 3
    out["flash_attention"] = {"us": dt * 1e6, "gflops_workload": flops / 1e9}
    print(f"[kern] flash_attention interp: {dt*1e3:.1f} ms "
          f"({flops/1e9:.2f} GFLOP workload)")
    # tiled matmul
    a = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    ops.matmul(a, b).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        ops.matmul(a, b).block_until_ready()
    dt = (time.time() - t0) / 3
    out["tiled_matmul"] = {"us": dt * 1e6,
                           "gflops_workload": 2 * 512**3 / 1e9}
    print(f"[kern] tiled_matmul interp: {dt*1e3:.1f} ms")
    return out


def main(force: bool = False) -> Dict:
    return cached("misc", lambda: {"space": space_size(),
                                   "sa": sa_throughput(),
                                   "evaluator": evaluator_throughput(),
                                   "dse_throughput": dse_throughput(),
                                   "kernels": kernel_bench()}, force)


if __name__ == "__main__":
    import argparse

    from repro.launch.cli import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny uncached end-to-end engine exercise (CI)")
    ap.add_argument("--fanout", action="store_true",
                    help="uncached (candidate x workload) fan-out "
                    "throughput run (16 candidates x 4 workloads)")
    ap.add_argument("--retune", action="store_true",
                    help="replica-exchange t_ladder/swap_every sweep on "
                    "the quick Table-I grid (sets core/sa.py defaults)")
    ap.add_argument("--parity", action="store_true",
                    help="batched-vs-scalar parity gate on the tiny grid "
                    "(CI bench-smoke job)")
    ap.add_argument("--fused-parity", action="store_true",
                    help="fused jitted pass vs exact engine objective "
                    "parity across the quick workload zoo (CI bench-smoke "
                    "job; asserts the documented ~1e-4 envelope)")
    ap.add_argument("--dse-bench", action="store_true",
                    help="screening/SA/sweep before-vs-after measurement "
                    "(the BENCH_dse.json payload; see benchmarks/run.py "
                    "--json)")
    ap.add_argument("--quick", action="store_true",
                    help="with --dse-bench: fewer timing rounds (CI)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        dse_smoke()
    elif args.parity:
        batched_parity()
    elif args.fused_parity:
        fused_parity()
    elif args.dse_bench:
        dse_bench(quick=args.quick)
    elif args.fanout:
        dse_throughput(n_candidates=16, n_workers=4, iters=600,
                       n_workloads=4)
    elif args.retune:
        re_tuning()
    else:
        main(force=args.force)
