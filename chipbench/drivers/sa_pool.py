"""Driver of ``sa_pool`` mixes: back-to-back sweep tasks over a pool of
Table-I points, each point met once.

One task is ``run_dse([point], {workload: graph}, cfg, n_workers=1)`` with
lockstep replica-exchange SA (``n_chains`` chains) scored by the fused pass
(``SAConfig(backend="jax")``); it ends in the exact NumPy re-score of the
winner, which is what the task reports.  Each task builds its workload
graph anew.

The mix holds two disjoint lists of points.  ``pool`` is what the window
sweeps: fixed blocks of ``block`` points, visited block after block, the
order inside each block drawn from ``--seed``, so every run does the same
work in its own order and a window that ends inside a block differs from
another only there.  No point is met twice in one process: a window that
outran the pool would meet its points again under fresh SA seeds.  Every
task's SA seed comes from the mix's ``sa_seed`` and the point, not from
``--seed``: the trajectories are the work.

Set-up runs the ``warmup`` points (imports, the JAX runtime, the program's
first-call paths and whatever process-wide state a sweep's earlier
candidates leave), never a point of the window, so no result cache of the
process holds a window task.  The window's programs are compiled before
it: a checkout's first run starts a child process that runs every task
once (``fill``), fills the persistent compile cache and exits before this
process touches the chip, and leaves a marker (``filled.json``); a later
run finds the marker and starts none.  Every evaluator binds its
candidate's masks and constants, as operands, to the one fused program
the process shares (``_build_fused_fn``); a window task asks JAX for an
executable only for a (batch bucket, ``buf_len``, padded length) that no
earlier task of the process met, and then loads it from that cache: that
is the sweep's own cost and falls inside the window.

The window runs tasks until ``--seconds`` has passed; the task in flight
finishes and counts.  A traced run traces the first ``trace.tasks`` tasks
and stops there.  Afterwards the answers are checked against the plain
reference (``reference/costmodel.py``):

* ``objective_gap``: every window task's reported (energy, delay) against
  the reference's score of its reported mapping, relative; exact (limit 0);
* ``fused_gap``: the fused pass's on-chip (delay, energy) of a sample of the
  window's proposals, drawn from the seed, against the reference's;
* ``off_chip_fused_calls``: fused calls whose results did not live on the
  chip (limit 0).

The SA steps and fused calls are seen by wrapping three bindings of the
program (``explore.step_chains_lockstep``, ``Evaluator._eval_requests_fused``
and ``evaluator._build_fused_fn``, whose returned call runs the shared
program); a window in which any of them saw nothing fails the run rather
than report without them.  The reference scores on the graph that the
configuration's reference module builds (``build(config)``).
"""

from __future__ import annotations

import hashlib
import json
import shutil
import subprocess
import sys
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench import trace as tr
from chipbench.reference.costmodel import CostModel

# limits; PERF.md gives the readings each was set from
FUSED_GAP_LIMIT = 1e-4        # the fused pass's own stated envelope
OBJECTIVE_GAP_LIMIT = 0.0     # the reported objective is the exact engine's

ARCH_FIELDS = ("x_cores", "y_cores", "xcut", "ycut", "noc_bw", "d2d_bw",
               "dram_bw", "glb_kb", "macs_per_core")
MARKER = "filled.json"


def window_order(n_points: int, block: int, seed: int) -> List[int]:
    """Pool indices in visiting order: the fixed blocks one after another,
    each block in an order drawn from ``seed``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    out: List[int] = []
    for lo in range(0, n_points, block):
        out.extend(lo + int(j) for j in
                   rng.permutation(min(block, n_points - lo)))
    return out


def task_seed(sa_seed: int, role: int, index: int, round_: int = 0) -> int:
    """The SA seed of a task: ``role`` 0 for a window point, 1 for a
    set-up point; ``round_`` counts how often the window has met the
    point (0 unless it outran the pool)."""
    return int(np.random.SeedSequence([sa_seed, role, index, round_])
               .generate_state(1)[0])


def window_tasks(mix: Dict[str, Any], seed: int):
    """Endless (pool index, SA seed) of the window, in visiting order."""
    pool = mix["pool"]
    order = window_order(len(pool), int(mix["block"]), seed)
    k = 0
    while True:
        i = order[k % len(order)]
        yield i, task_seed(mix["sa_seed"], 0, i, k // len(order))
        k += 1


def setup_tasks(mix: Dict[str, Any]) -> List[Tuple[Dict[str, Any], int]]:
    return [(p, task_seed(mix["sa_seed"], 1, j))
            for j, p in enumerate(mix["warmup"])]


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want) if want else abs(got)


def _fill_key(root: Path, cell) -> str:
    """What the filled cache depends on: the cell's files, the JAX build
    and the program's core sources."""
    import importlib.metadata as md
    h = hashlib.sha256()
    h.update(json.dumps([cell.name, cell.config, cell.mix],
                        sort_keys=True).encode())
    for pkg in ("jax", "jaxlib"):
        try:
            h.update(md.version(pkg).encode())
        except md.PackageNotFoundError:
            pass
    for f in sorted((root / "src" / "repro" / "core").rglob("*.py")):
        h.update(f.read_bytes())
    return h.hexdigest()


def prepare(cell, root: Path, work_dir: Path, fill_cmd: Sequence[str],
            fail) -> None:
    """Before this process touches JAX: on a checkout whose cache was not
    filled for this cell, run ``fill_cmd`` (this cell's ``fill``) in a
    child process and wait for it."""
    marker = work_dir / MARKER
    key = _fill_key(root, cell)
    cache = root / ".jax_cache"
    try:
        have = json.loads(marker.read_text())
    except (OSError, ValueError):
        have = {}
    if have.get("key") == key and cache.is_dir() and \
            len(list(cache.iterdir())) >= int(have.get("entries", 1)):
        return
    marker.unlink(missing_ok=True)
    proc = subprocess.run(list(fill_cmd), cwd=root, stdout=sys.stderr)
    if proc.returncode != 0:
        fail(f"filling the compile cache failed (exit {proc.returncode})")
    marker.write_text(json.dumps(
        {"key": key, "entries": len(list(cache.iterdir()))}))


class Hooks:
    """Spans and records around the program's SA step and fused pass,
    installed by wrapping (the program itself is not changed)."""

    def __init__(self, trace: bool, platform: str):
        self.trace = trace
        self.platform = platform        # where fused results must live
        self.task = -1                  # window task in flight; -1: set-up
        self.steps: List[Tuple[int, float]] = []          # (task, seconds)
        # (task, group, lms, batch, fused delay, fused energy)
        self.rows: List[Tuple[int, Any, Any, int, float, float]] = []
        self.shapes: List[Tuple[int, int, int]] = []      # (B, buf_len, n)
        self.off_chip = 0
        self.fused_calls = 0            # calls of the built fused programs
        self.compiles = 0               # compile requests in the window
        self.cache_loads = 0            # ... served by the persistent cache
        self._undo: List[Tuple[Any, str, Any]] = []
        self._listening = False

    def _patch(self, obj, name, new) -> None:
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    def install(self) -> None:
        import jax
        from repro.core import evaluator as ev_mod
        from repro.core import explore

        hooks = self
        step = explore.step_chains_lockstep

        def timed_step(chains, backend="numpy"):
            t0 = time.perf_counter()
            with tr.annotate("step", hooks.trace):
                step(chains, backend=backend)
            if hooks.task >= 0:
                hooks.steps.append((hooks.task, time.perf_counter() - t0))
        self._patch(explore, "step_chains_lockstep", timed_step)

        fused = ev_mod.Evaluator._eval_requests_fused

        def recorded_fused(ev, requests, total_batch):
            with tr.annotate("fused", hooks.trace):
                out = fused(ev, requests, total_batch)
            if hooks.task >= 0:
                for (grp, lms), (ge, _) in zip(requests, out):
                    hooks.rows.append((hooks.task, grp, lms, total_batch,
                                       ge.delay_s, ge.energy_j))
            return out
        self._patch(ev_mod.Evaluator, "_eval_requests_fused", recorded_fused)

        build = ev_mod._build_fused_fn

        def shaped_build(layout, buf_len, *a, **kw):
            fn = build(layout, buf_len, *a, **kw)

            def call(B, idx, vals, *rest):
                out = fn(B, idx, vals, *rest)
                if hooks.task >= 0:
                    hooks.fused_calls += 1
                    platforms = {d.platform for d in out[0].devices()}
                    hooks.off_chip += platforms != {hooks.platform}
                    if hooks.trace:
                        n = int(np.count_nonzero(idx != B * buf_len))
                        hooks.shapes.append((B, buf_len, n))
                return out
            return call
        self._patch(ev_mod, "_build_fused_fn", shaped_build)

        if not self._listening:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self._listening = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if self.task >= 0 and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if self.task >= 0 and event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def remove(self) -> None:
        for obj, name, old in reversed(self._undo):
            setattr(obj, name, old)
        self._undo.clear()
        self.task = -1


def _task(cfg, mix, spec: str, point: Dict[str, Any], sa_seed: int):
    from repro.core.dse import DSEConfig, run_dse
    from repro.core.hw import ArchConfig
    from repro.core.sa import SAConfig
    from repro.core.workloads import make_workload

    arch = ArchConfig(**{k: point[k] for k in ARCH_FIELDS})
    g = make_workload(spec)
    dcfg = DSEConfig(batch=int(cfg["batch"]), keep_mappings=True,
                     sa=SAConfig(iters=int(cfg["sa_iters"]), seed=sa_seed,
                                 n_chains=int(mix["n_chains"]),
                                 lockstep=True, backend="jax"))
    (res,) = run_dse([arch], {cfg["workload"]["name"]: g}, dcfg,
                     n_workers=1)
    return res


def fill(r) -> None:
    """The child of a checkout's first run: every task of set-up and of a
    window that covers the pool once, so that each program they compile
    is in the persistent cache."""
    cfg, mix = r.cell.config, r.cell.mix
    for point, s in setup_tasks(mix):
        _task(cfg, mix, r.cell.spec, point, s)
    tasks = window_tasks(mix, 0)
    for _ in range(len(mix["pool"])):
        i, s = next(tasks)
        _task(cfg, mix, r.cell.spec, mix["pool"][i], s)


def run(r, t_start: float) -> None:
    from repro.core.analyzer import PREFETCH_STATS

    cfg, mix = r.cell.config, r.cell.mix
    iters, chains = int(cfg["sa_iters"]), int(mix["n_chains"])
    pool = mix["pool"]

    hooks = Hooks(r.trace, r.devices[0].platform)
    hooks.install()
    try:
        t_tasks = time.perf_counter()
        for point, s in setup_tasks(mix):
            _task(cfg, mix, r.cell.spec, point, s)
        r.obs["setup_tasks_s"] = time.perf_counter() - t_tasks
        trace_dir = r.work_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        n_traced = int(mix["trace"]["tasks"])
        builds0 = sum(PREFETCH_STATS.values())
        tasks: List[Dict[str, Any]] = []
        todo = window_tasks(mix, r.seed)
        ctx = tr.capture(str(trace_dir)) if r.trace else nullcontext()
        with ctx:
            t0 = time.perf_counter()
            r.setup_s = t0 - t_start
            deadline = t0 + r.seconds
            with tr.annotate("window", r.trace):
                while (len(tasks) < n_traced if r.trace
                       else time.perf_counter() < deadline):
                    i, s = next(todo)
                    hooks.task = len(tasks)
                    with tr.annotate("task", r.trace):
                        res = _task(cfg, mix, r.cell.spec, pool[i], s)
                    tasks.append({"at": pool[i], "sa_seed": s, "point": res})
            r.window_s = time.perf_counter() - t0
            hooks.task = -1
    finally:
        hooks.remove()
    r.read_memory_peak()
    for what, n in (("SA steps", len(hooks.steps)),
                    ("fused results", len(hooks.rows)),
                    ("fused calls", hooks.fused_calls)):
        if n == 0:
            r.fail(f"the window's {len(tasks)} tasks recorded no {what}: "
                   f"a wrapped binding of the program has moved")
    evals = len(tasks) * iters * chains
    r.attempted = len(tasks)
    r.obs.update({
        "evals": evals, "tasks": len(tasks),
        "pool_rounds": -(-len(tasks) // len(pool)),
        "step_s": [s for _, s in hooks.steps],
        "builds": sum(PREFETCH_STATS.values()) - builds0,
        "compiles": hooks.compiles, "cache_loads": hooks.cache_loads,
        "fused_shapes": hooks.shapes,
    })
    if r.trace:
        ev = tr.events_from_xplane(tr.find_xplane(str(trace_dir)))
        r.trace_summary = tr.summarize(ev)
        r.obs["trace_events"] = ev
        shutil.rmtree(trace_dir, ignore_errors=True)
    _check(r, cfg, mix, tasks, hooks)


def _check(r, cfg, mix, tasks, hooks) -> None:
    """Compare the window's answers with the reference (or, in a control
    run, the control's answers in their place)."""
    import ml_dtypes

    wl, batch = cfg["workload"]["name"], int(cfg["batch"])
    graph = r.cell.reference.build(cfg)
    models: Dict[Tuple[int, Any], CostModel] = {}

    def ref(k: int, dt=np.float64) -> CostModel:
        if (k, dt) not in models:
            models[(k, dt)] = CostModel(tasks[k]["at"], cfg["tech"], graph,
                                        dt)
        return models[(k, dt)]

    worst_obj, failed = 0.0, 0
    for k, t in enumerate(tasks):
        p = t["point"]
        E_ref, D_ref = ref(k).mapping(p.mappings[wl], batch)
        E, D = p.per_workload[wl]
        if r.control:
            E, D = ref(k, np.float32).mapping(p.mappings[wl], batch)
        gap = max(rel_gap(E, E_ref), rel_gap(D, D_ref))
        failed += gap > OBJECTIVE_GAP_LIMIT
        worst_obj = max(worst_obj, gap)
    r.failed = int(failed)
    r.check("objective_gap", worst_obj, OBJECTIVE_GAP_LIMIT)

    rows = hooks.rows
    n = min(len(rows), int(mix["check"]["fused_rows"]))
    rng = np.random.default_rng(np.random.SeedSequence([r.seed, 0xF05ED]))
    worst_fused = 0.0
    for j in sorted(rng.choice(len(rows), size=n, replace=False)):
        k, grp, lms, tb, d, e = rows[j]
        want = ref(k).group(grp, lms, tb)
        if r.control:
            got = ref(k, ml_dtypes.bfloat16).group(grp, lms, tb)
            d, e = got.delay_s, got.energy_j
        worst_fused = max(worst_fused, rel_gap(d, want.delay_s),
                          rel_gap(e, want.energy_j))
    r.obs["fused_rows_checked"] = n
    r.check("fused_gap", worst_fused if n else float("inf"), FUSED_GAP_LIMIT)
    r.check("off_chip_fused_calls", hooks.off_chip, 0)
