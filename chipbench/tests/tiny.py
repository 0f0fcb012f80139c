"""Small cells for the benchmark's own tests, run on the CPU.

:func:`make_root` builds a checkout in a temporary directory: a copy of
``chipbench/`` (without its tests), the repository's ``src/`` linked in, and
a ``BENCHMARK.json`` that adds the ``tiny`` configuration and the
``tiny.sa`` / ``tiny.realize`` cells beside the real ones -- by adding
files and entries only, as a later change would.  :func:`drive` runs one
cell in a fresh process through ``drive.py``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Optional

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold

TINY_CONFIG = {
    "name": "tiny",
    "source": "small transformer for tests",
    "workload": {"name": "TF", "kind": "transformer",
                 "reference": "transformer",
                 "args": {k: k for k in ("n_layers", "d_model", "d_ff",
                                         "seq")}},
    "n_layers": 2, "d_model": 128, "d_ff": 256, "seq": 64,
    "bytes_per_elem": 1, "batch": 8, "tops": 72.0, "sa_iters": 8,
    "tech": json.loads((ROOT / "chipbench" / "configs" / "table1-tf.json")
                       .read_text())["tech"],
}


def _point(x, y, xcut, ycut, dram, noc, d2d, glb):
    return {"x_cores": x, "y_cores": y, "xcut": xcut, "ycut": ycut,
            "macs_per_core": 512, "dram_bw": dram, "noc_bw": noc,
            "d2d_bw": d2d, "glb_kb": glb}


TINY_POOL = {
    "kind": "sa_pool", "n_chains": 4, "sa_seed": 7, "block": 2,
    "warmup": [_point(4, 4, 1, 1, 144.0, 32.0, 16.0, 1024)],
    "pool": [_point(4, 4, 1, 1, 72.0, 16.0, 16.0, 2048),
             _point(4, 4, 2, 2, 72.0, 16.0, 16.0, 2048),
             _point(4, 4, 2, 2, 144.0, 32.0, 16.0, 1024),
             _point(4, 4, 1, 1, 144.0, 64.0, 32.0, 4096)],
    "check": {"fused_rows": 32},
    "trace": {"tasks": 2},
}

TINY_REALIZE = {
    "kind": "realize_passes",
    "arch": {"macs_per_core": 36000, "xcut": 1, "ycut": 1,
             "dram_per_tops": 2.0, "noc_bw": 32.0, "d2d_ratio": 0.5,
             "glb_kb": 2048},
    "devices": 1,
    "check": {"passes": 2, "among_first": 3},
    "trace": {"passes": 3},
}


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cb = root / "chipbench"
    (cb / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (cb / "traffic" / "tiny-pool.json").write_text(json.dumps(TINY_POOL))
    (cb / "traffic" / "tiny-realize.json").write_text(json.dumps(TINY_REALIZE))
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"] += [
        {"name": "tiny.sa", "config": "tiny", "traffic": "tiny-pool",
         "chips": 1, "why": "tests"},
        {"name": "tiny.realize", "config": "tiny", "traffic": "tiny-realize",
         "chips": 1, "why": "tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        for real, tiny in (("table1-tf.sa", "tiny.sa"),
                           ("table1-tf.realize", "tiny.realize")):
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def drive(root: Path, cell: str, fault: Optional[str] = None,
          control: bool = False, trace: bool = False, seconds: float = 1.0,
          seed: int = SEED) -> Dict[str, Any]:
    """One run of ``cell`` on the CPU in a fresh process; its result.  The
    checkout's first run of a cell also starts the cell's cache fill."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "tests" / "drive.py"),
         str(root), cell, fault or "-", str(int(control)), str(int(trace)),
         str(seconds), str(seed)],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise AssertionError(f"drive.py failed ({proc.returncode}):\n"
                             f"{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
