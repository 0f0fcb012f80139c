"""Small cells for the benchmark's own tests, run on the CPU.

:func:`make_root` builds a checkout in a temporary directory: a copy of
``chipbench/`` (without its tests), the repository's ``src/`` linked in, and
a ``BENCHMARK.json`` that adds the ``tiny`` configuration and the
``tiny.sa`` / ``tiny.realize`` cells beside the real ones -- by adding
files and entries only (:func:`add_cell`), as a later change would.
:func:`drive` runs one cell in a fresh process through ``drive.py``.
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


# a 2x2-core candidate over 4 host devices: its T-Map plan has stages of
# several layers, one of them with three source layers, and puts each
# scores layer in another stage than the context layer that reads it
TINY_MESH_CONFIG = dict(TINY_CONFIG, name="tiny-mesh", d_model=512,
                        d_ff=1024, seq=64, batch=256)

TINY_REALIZE4 = dict(TINY_REALIZE, arch=dict(TINY_REALIZE["arch"],
                                             macs_per_core=9000),
                     devices=4)


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    add_cell(root, "tiny.sa", TINY_CONFIG, "tiny-pool", TINY_POOL,
             like="table1-tf.sa")
    add_cell(root, "tiny.realize", TINY_CONFIG, "tiny-realize", TINY_REALIZE,
             like="table1-tf.realize")
    return root


def add_cell(root: Path, cell: str, config: Dict[str, Any], traffic: str,
             mix: Dict[str, Any], like: str, chips: int = 1) -> None:
    """Add ``cell`` to the checkout at ``root`` as files and entries only:
    its configuration (unless there), its mix, its ``workloads`` entry,
    and its name beside ``like`` in every metric's list of cells."""
    cb = root / "chipbench"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = config["name"]
    if name not in {c["name"] for c in bench["configs"]}:
        (cb / "configs" / f"{name}.json").write_text(json.dumps(config))
        bench["configs"].append({"name": name, "source": "tests",
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "tests"})
    (cb / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": chips,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def drive(root: Path, cell: str, fault: Optional[str] = None,
          control: bool = False, trace: bool = False, seconds: float = 1.0,
          seed: int = SEED, host_devices: int = 1) -> Dict[str, Any]:
    """One run of ``cell`` on the CPU in a fresh process, which sees
    ``host_devices`` devices; its result.  The checkout's first run of a
    cell also starts the cell's cache fill."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    if host_devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={host_devices}").strip()
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "tests" / "drive.py"),
         str(root), cell, fault or "-", str(int(control)), str(int(trace)),
         str(seconds), str(seed)],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise AssertionError(f"drive.py failed ({proc.returncode}):\n"
                             f"{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])
