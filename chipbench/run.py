#!/usr/bin/env python3
"""Chip benchmark of the co-exploration system: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything a cell needs is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* ``chipbench/configs/<config>.json`` holds the configuration's sizes; its
  ``workload`` names the program's workload kind (and model), which of
  those sizes make up the workload spec the program is given, and the
  plain reference ``chipbench/reference/models/<reference>.py`` that the
  checks compare against (``build(config)``, the layer graph; a realize
  cell's also ``realized_layers(config)``);
* ``chipbench/traffic/<mix>.json`` holds the mix's parameters, and its
  ``kind`` names the driver ``chipbench/drivers/<kind>.py`` that builds the
  system under test, warms it, runs the measured window and checks its
  answers against the configuration's plain reference (a driver names
  the functions it needs of it in ``REFERENCE_NEEDS``, and may also have a
  ``prepare`` step, run before this process touches JAX, that starts the
  same command with ``--fill 1`` in a child process to fill the
  checkout's compile cache);
* each metric is a reader ``chipbench/metrics/<name>.py`` whose
  ``read(run)`` returns a number, or ``None`` where it finds nothing.

With ``--trace 0`` the run reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, from a profiled run.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), and last
``checks``, each number compared with its limit; the same numbers close
standard error.  The run needs a TPU: on any other platform, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()         # set-up is timed from process start

import argparse
import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if str(ROOT) not in sys.path:     # drivers, metrics and references import
    sys.path.insert(0, str(ROOT))  # chipbench's shared modules
# libtpu would otherwise log to the fixed /tmp/tpu_logs
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def stop(why: str) -> None:
    """End the run without a result."""
    raise BenchError(why)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_module(path: Path):
    """Import a Python file of the benchmark by path."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # where a dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    return json.loads(path.read_text())


def require(module, needs, what: str) -> None:
    """Refuse ``module`` unless it has every function in ``needs``."""
    missing = [f for f in needs if not callable(getattr(module, f, None))]
    if missing:
        raise BenchError(f"{what} has no {', '.join(missing)}")


def load_reference(config: Dict[str, Any], root: Path = ROOT):
    """The plain reference that the configuration's ``workload.reference``
    names: ``chipbench/reference/models/<name>.py``, which builds the
    configuration's layer graph (``build``)."""
    name = str(config["workload"].get("reference", ""))
    if not name or name.startswith(".") or Path(name).name != name:
        raise BenchError(f"configuration {config.get('name')!r} names no "
                         f"reference module: {name!r}")
    path = root / "chipbench" / "reference" / "models" / f"{name}.py"
    module = load_module(path)
    require(module, ("build",), f"reference module {path}")
    return module


def workload_spec(config: Dict[str, Any]) -> str:
    """The program's workload spec, built from the configuration's own
    sizes: ``<kind>[:<model>]:<arg>=<value>,...``, where ``workload.args``
    maps each argument of the program to the configuration's key."""
    w = config["workload"]
    head = w["kind"] + (f":{w['model']}" if w.get("model") else "")
    return head + ":" + ",".join(f"{arg}={config[key]}"
                                 for arg, key in w["args"].items())


@dataclass
class Cell:
    """One cell of BENCHMARK.json with everything it names, loaded."""
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    reference: Any = None              # the configuration's reference module

    @property
    def spec(self) -> str:
        return workload_spec(self.config)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json; cells: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=cfg, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                reference=load_reference(cfg, root))


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    table = load_json(root / "chipbench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    """What a driver is given and what it fills in."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Dict[str, float]
    work_dir: Path
    control: bool = False              # answers from the control (control.py)
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    obs: Dict[str, Any] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    trace_summary: Optional[Dict[str, Any]] = None

    def fail(self, why: str) -> None:
        stop(why)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)

    def read_memory_peak(self) -> None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks, default=0)


def check_devices(chips: int, require_tpu: bool = True) -> List[Any]:
    """The devices the cell runs on; refuses anything but enough TPUs."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's devices are {platform!r}; "
                         f"there is no CPU fallback")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def read_metrics(run: Run, metrics: List[Dict[str, Any]],
                 root: Path = ROOT) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        reader = load_module(root / "chipbench" / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def fill_command(cell_name: str) -> List[str]:
    return [sys.executable, str(Path(__file__).resolve()), "--workload",
            cell_name, "--seed", "0", "--seconds", "0", "--fill", "1"]


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            root: Path = ROOT, require_tpu: bool = True,
            control: bool = False, fill: bool = False,
            fill_cmd: Optional[List[str]] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result object.  With ``fill`` it
    only runs the driver's ``fill`` (the child that ``prepare`` starts)
    and returns an empty object."""
    cell = find_cell(cell_name, root)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no repro package under {src}: the system under "
                         f"test is missing from this checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    work_dir = root / ".chipbench" / cell.name
    work_dir.mkdir(parents=True, exist_ok=True)
    driver = load_module(root / "chipbench" / "drivers"
                         / f"{cell.mix['kind']}.py")
    require(cell.reference, getattr(driver, "REFERENCE_NEEDS", ()),
            f"the reference of {cell.name}, which a {cell.mix['kind']} "
            f"mix needs,")
    if not fill and hasattr(driver, "prepare"):
        driver.prepare(cell, root, work_dir,
                       fill_cmd or fill_command(cell_name), stop)
    devices = check_devices(cell.chips, require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_for(kind, root) if require_tpu else {}
    import jax
    from repro.launch.cli import enable_compile_cache
    enable_compile_cache()
    # the cache lives at a fixed path inside the checkout, whatever the
    # environment names: only a checkout's first run of a cell compiles
    (root / ".jax_cache").mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              devices=devices, peaks=peaks, work_dir=work_dir,
              control=control)
    if fill:
        driver.fill(run)
        return {}
    cache = root / ".jax_cache"
    entries = lambda: len(list(cache.glob("*"))) if cache.is_dir() else 0
    run.obs["cache_entries_before"] = entries()
    driver.run(run, t_start=T_START)
    run.obs["cache_entries_after"] = entries()
    diag = {k: v for k, v in run.obs.items() if isinstance(v, (int, float))}
    if run.trace_summary is not None:
        diag["idle_by_span_s"] = run.trace_summary["idle_by_span_s"]
    print("chipbench: " + json.dumps(diag), file=sys.stderr)
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end,
                           root)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result: Dict[str, Any] = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = run.trace_summary["breakdown"]
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fill", type=int, choices=(0, 1), default=0,
                    help="only fill the compile cache (a driver's child)")
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), fill=bool(args.fill))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if args.fill:
        return 0
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
