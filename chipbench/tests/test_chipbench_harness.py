"""The harness's contract on the CPU: it refuses to run off the TPU or
without the system under test, knows only the chips in its peak table,
and finds configurations, mixes and metrics by name."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as harness  # noqa: E402
from chipbench.tests.tiny import drive, make_root  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cwd / ".jax_cache"))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "table1-tf.sa",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_platform_other_than_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_refuses_a_directory_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks_for("TPU v99")


def test_benchmark_file_names_only_files_it_has():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file() and NAME.match(c["name"])
    for name, w in cells.items():
        assert NAME.match(name)
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()
        cell = harness.find_cell(name)
        mix_kind = cell.mix["kind"]
        assert (ROOT / "chipbench" / "drivers" / f"{mix_kind}.py").is_file()
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A cell, a configuration, a mix and a metric added as new files and
    entries only (``make_root`` adds ``tiny``), with no existing file
    edited, run and report."""
    root = make_root(tmp_path)
    (root / "chipbench" / "metrics" / "tiny_tasks.py").write_text(
        "def read(run):\n    return run.obs.get('tasks')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "tiny_tasks", "unit": "tasks", "better": "higher",
         "source": "program_counter", "layer": "sweep driver",
         "moves": "sa_evals_per_s", "workloads": ["tiny.sa"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for f in (ROOT / "chipbench").rglob("*"):
        rel = f.relative_to(ROOT / "chipbench")
        if f.is_file() and "tests" not in rel.parts and \
                "__pycache__" not in rel.parts:
            assert (root / "chipbench" / rel).read_bytes() == f.read_bytes()
    cell = harness.find_cell("tiny.sa", root)
    assert cell.config["name"] == "tiny" and cell.mix["kind"] == "sa_pool"
    res = drive(root, "tiny.sa", trace=True)
    assert res["correct"]
    assert res["metrics"]["tiny_tasks"]["value"] == 2
    assert {"fused_compiles_per_kevals", "construct_builds_per_eval"} \
        <= set(res["metrics"])
