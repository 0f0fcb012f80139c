"""A realize cell whose plan has stages of several layers: a 2x2-core
T-Map candidate over four host devices (CPU).  A sound run is correct; a
reference that draws its inputs layer by layer, not stage by stage as the
program consumes them, is not."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run as harness  # noqa: E402
from chipbench.reference import realized  # noqa: E402
from chipbench.tests.tiny import (TINY_MESH_CONFIG, TINY_REALIZE4,  # noqa: E402
                                  add_cell, drive, make_root)

CELL = "tiny-mesh.realize4"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("chipbench"))
    add_cell(root, CELL, TINY_MESH_CONFIG, "tiny-realize4", TINY_REALIZE4,
             like="table1-tf.realize", chips=4)
    return root


def _stages(tmp_path):
    """The cell's plan, lowered as its driver lowers it (no device)."""
    from repro.core.dse import DSEConfig, grid_candidates, run_dse
    from repro.core.workloads import make_workload
    from repro.realize.plan import load_realize_candidates, plans_for

    cfg, a = TINY_MESH_CONFIG, TINY_REALIZE4["arch"]
    (arch,) = grid_candidates(
        cfg["tops"], mac_options=(a["macs_per_core"],),
        cut_options=(a["xcut"],), dram_per_tops=(a["dram_per_tops"],),
        noc_options=(a["noc_bw"],), d2d_ratio=(a["d2d_ratio"],),
        glb_options=(a["glb_kb"],))
    g = make_workload(harness.workload_spec(cfg))
    ckpt = tmp_path / "plan.ckpt.jsonl"
    run_dse([arch], {"TF": g}, DSEConfig(batch=cfg["batch"],
                                         keep_mappings=True),
            use_sa=False, n_workers=1, checkpoint=ckpt)
    cands = load_realize_candidates(ckpt, {"TF": g}, verbose=False)
    (_, plan), = plans_for(cands[:1], TINY_REALIZE4["devices"])
    return [list(st.layers) for st in plan.stages], plan.batch_unit


def test_the_plan_has_what_one_layer_stages_lack(tmp_path):
    """Stages of several layers, one of them with more than one source
    layer, so that the draw order matters; every scores layer is exported
    to a context layer in a later stage."""
    stages, bu = _stages(tmp_path)
    ref = harness.load_reference(TINY_MESH_CONFIG)
    layers = {l.name: l for l in ref.realized_layers(TINY_MESH_CONFIG)}
    assert max(len(st) for st in stages) > 1
    assert any(sum(not layers[n].preds for n in st) > 1 for st in stages)
    scores = {n for n, l in layers.items() if l.kind == "qk"}
    assert scores <= set(realized.exported(layers, stages))
    by_stage = realized.draws(layers, stages, bu, 5)
    by_layer = realized.draws(layers, [[n] for st in stages for n in st],
                              bu, 5)
    assert by_stage.keys() == by_layer.keys()
    assert any((by_stage[k] != by_layer[k]).any() for k in by_stage)


def test_sound_run_is_correct(root):
    res = drive(root, CELL, host_devices=4)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4


def test_reference_drawing_layer_by_layer_is_not_correct(root):
    res = drive(root, CELL, fault="reference-draws-by-layer", host_devices=4)
    assert not res["correct"]
    c = res["checks"]["cube_rel_err"]
    assert c["value"] > c["limit"], res["checks"]
