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
from chipbench.tests.tiny import (TINY_CONFIG, TINY_POOL,  # noqa: E402
                                  TINY_REALIZE, add_cell, drive, make_root)

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
    _assert_unchanged(root)
    cell = harness.find_cell("tiny.sa", root)
    assert cell.config["name"] == "tiny" and cell.mix["kind"] == "sa_pool"
    res = drive(root, "tiny.sa", trace=True)
    assert res["correct"]
    assert res["metrics"]["tiny_tasks"]["value"] == 2
    assert {"fused_compiles_per_kevals", "construct_builds_per_eval"} \
        <= set(res["metrics"])


def _assert_unchanged(root: Path) -> None:
    """Every file of the benchmark, tests aside, is in ``root`` as it is
    here, byte for byte."""
    for f in (ROOT / "chipbench").rglob("*"):
        rel = f.relative_to(ROOT / "chipbench")
        if f.is_file() and "tests" not in rel.parts and \
                "__pycache__" not in rel.parts:
            assert (root / "chipbench" / rel).read_bytes() == f.read_bytes()


# A configuration of a layer kind that no reference of the benchmark
# builds: an MLA encoder stack (the program's ``mla:`` spec), with its
# own reference module.  ``{kv_down}`` is the width of the latent KV cube.
MLA_REFERENCE = '''"""Reference of the MLA encoder stacks: query and KV low-rank
compressions, per-head re-expansion, scores, context, output projection,
residual add, two-layer FFN, residual add."""

from chipbench.reference.graphs import Graph, Layer


def build(c):
    d, ff, seq = int(c["d_model"]), int(c["d_ff"]), int(c["seq"])
    heads, qr, kvr = int(c["n_heads"]), int(c["q_rank"]), int(c["kv_rank"])
    bpe = int(c["bytes_per_elem"])
    dh = heads * (d // heads)
    g = Graph()
    prev = None
    for i in range(int(c["n_layers"])):
        t = f"l{{i}}"
        src = [prev] if prev else []
        fc = lambda n, K, C, ins: g.add(Layer(  # noqa: E731
            f"{{t}}_{{n}}", "fc", K, seq, C=C, bytes_per_elem=bpe), ins)
        qu = fc("qup", dh, qr, [fc("qdown", qr, d, src)])
        kvd = fc("kvdown", {kv_down}, d, src)
        ku, vu = fc("kup", dh, kvr, [kvd]), fc("vup", dh, kvr, [kvd])
        s = g.add(Layer(f"{{t}}_qk", "matmul", seq, seq, C=dh,
                        bytes_per_elem=bpe), [qu, ku])
        a = g.add(Layer(f"{{t}}_av", "matmul", dh, seq, C=seq,
                        bytes_per_elem=bpe), [s, vu])
        a1 = g.add(Layer(f"{{t}}_add1", "eltwise", d, seq, n_inputs=2,
                         bytes_per_elem=bpe), [fc("o", d, dh, [a])] + src)
        down = fc("down", d, ff, [fc("up", 2 * ff, d, [a1])])
        prev = g.add(Layer(f"{{t}}_add2", "eltwise", d, seq, n_inputs=2,
                           bytes_per_elem=bpe), [down, a1])
    return g
'''

MLA_CONFIG = dict(
    TINY_CONFIG, name="tiny-mla", source="tests",
    workload={"name": "MLA", "kind": "mla", "reference": "mla_encoder",
              "args": {"n_layers": "n_layers", "d_model": "d_model",
                       "n_heads": "n_heads", "q_rank": "q_rank",
                       "kv_rank": "kv_rank", "d_ff": "d_ff", "seq": "seq",
                       "bpe": "bytes_per_elem"}},
    n_layers=2, d_model=128, n_heads=4, q_rank=32, kv_rank=16, d_ff=256,
    seq=64, bytes_per_elem=2)


def _mla_root(tmp: Path, kv_down: str) -> Path:
    """A checkout with the MLA configuration, its reference module and a
    ``.sa`` cell, added as files and entries only."""
    root = make_root(tmp)
    (root / "chipbench" / "reference" / "models" / "mla_encoder.py"
     ).write_text(MLA_REFERENCE.format(kv_down=kv_down))
    add_cell(root, "tiny-mla.sa", MLA_CONFIG, "tiny-pool", TINY_POOL,
             like="table1-tf.sa")
    _assert_unchanged(root)
    return root


def test_new_config_with_its_own_reference_module_runs_correct(tmp_path):
    root = _mla_root(tmp_path, "kvr")
    cell = harness.find_cell("tiny-mla.sa", root)
    assert harness.workload_spec(cell.config).startswith("mla:")
    from repro.core.workloads import make_workload
    want = make_workload(harness.workload_spec(cell.config))
    got = cell.reference.build(cell.config)
    assert list(got.layers) == list(want.layers)
    assert got.edges == want.edges
    res = drive(root, "tiny-mla.sa")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_new_reference_module_with_a_wrong_width_is_not_correct(tmp_path):
    """The same module with the latent KV cube twice as wide as the
    configuration states."""
    res = drive(_mla_root(tmp_path, "2 * kvr"), "tiny-mla.sa")
    assert not res["correct"]
    c = res["checks"]["objective_gap"]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("reference,cell,why", [
    ("no_such_reference", "tiny-mla.sa", "no such file"),
    ("mla_encoder", "tiny-mla.realize", "has no realized_layers"),
])
def test_reference_is_refused_before_set_up(tmp_path, reference, cell, why):
    """A name that names no module, or a module without a function its
    cell's driver needs, ends the run before it builds anything."""
    root = _mla_root(tmp_path, "kvr")
    cfg = dict(MLA_CONFIG, workload=dict(MLA_CONFIG["workload"],
                                         reference=reference))
    (root / "chipbench" / "configs" / "tiny-mla.json").write_text(
        json.dumps(cfg))
    add_cell(root, "tiny-mla.realize", cfg, "tiny-realize", TINY_REALIZE,
             like="table1-tf.realize")
    with pytest.raises(harness.BenchError, match=why):
        harness.execute(cell, 1, 1.0, False, root=root)


def test_reference_module_may_define_dataclasses(tmp_path):
    """A reference module is loaded by path as a module of its own, so
    one that keeps its layers in a dataclass loads."""
    root = make_root(tmp_path)
    (root / "chipbench" / "reference" / "models" / "with_dataclass.py"
     ).write_text(
        "from __future__ import annotations\n"
        "from dataclasses import dataclass\n"
        "from chipbench.reference.graphs import Graph\n\n\n"
        "@dataclass(frozen=True)\nclass Block:\n    width: int\n\n\n"
        "def build(c):\n    return Graph()\n")
    cfg = dict(TINY_CONFIG, workload=dict(TINY_CONFIG["workload"],
                                          reference="with_dataclass"))
    ref = harness.load_reference(cfg, root)
    assert ref.Block(3).width == 3 and not ref.build(cfg).layers
