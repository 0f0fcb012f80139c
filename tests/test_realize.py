"""Realization-loop tests: checkpoint -> MeshPlan round-trip, plan
validation, Pallas-vs-jnp parity of a realized stage (subprocess with
forced host devices), and the calibration overlay invariants."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.bridge import MeshPlan, StagePlan, lms_to_plan
from repro.core.dse import DSEConfig, run_dse
from repro.core.evaluator import Evaluator
from repro.core.explore import mapping_to_jsonable
from repro.core.hw import ArchConfig, TECH_12NM
from repro.core.sa import SAConfig
from repro.core.tangram import tangram_map
from repro.core.workload import LayerGroup
from repro.core.workloads import transformer
from repro.realize.calibrate import (TechOverlay, calibrated_candidates,
                                     fit_overlay, load_overlay, save_overlay)
from repro.realize.plan import (graph_from_spec, load_realize_candidates,
                                plans_for, validate_plan)

REPO = Path(__file__).resolve().parent.parent


def _arch(xcut: int = 1) -> ArchConfig:
    return ArchConfig(x_cores=2, y_cores=2, xcut=xcut, ycut=1, noc_bw=32.0,
                      d2d_bw=16.0, dram_bw=64.0, glb_kb=512,
                      macs_per_core=1024)


def _graph():
    return transformer(n_layers=1, d_model=64, d_ff=128, seq=32, name="tf-t")


def _keep_ckpt(tmp_path, g, cands):
    cfg = DSEConfig(batch=4, sa=SAConfig(iters=40, seed=0),
                    keep_mappings=True)
    ck = tmp_path / "rt.ckpt.jsonl"
    pts = run_dse(cands, {"TF": g}, cfg, checkpoint=ck)
    return ck, cfg, pts


# ---------------------------------------------------------------------------
# checkpoint -> MeshPlan round-trip
# ---------------------------------------------------------------------------

def test_checkpoint_to_plan_roundtrip(tmp_path):
    g = _graph()
    cands = [_arch(1), _arch(2)]
    ck, cfg, pts = _keep_ckpt(tmp_path, g, cands)
    rcands = load_realize_candidates(ck, {"TF": g}, top=0, verbose=False)
    assert len(rcands) == 2
    # loaded mappings are the exact serialized ones from the sweep
    by_label = {p.arch.label(): p for p in pts}
    for rc in rcands:
        src = by_label[rc.arch.label()]
        assert mapping_to_jsonable(rc.mapping) == \
            mapping_to_jsonable(src.mappings["TF"])
        plan = rc.lower()
        # the lowered plan mirrors the mapping group-for-group
        assert len(plan.stages) == len(rc.mapping)
        for st, (grp, lms) in zip(plan.stages, rc.mapping):
            assert st.layers == grp.names
            assert set(st.devices) == set(lms.cores_used())
            for name in grp.names:
                assert st.parts[name] == lms.ms[name].part
                assert st.cgs[name] == lms.ms[name].cg
        assert plan.batch_unit == rc.mapping[-1][0].batch_unit
        validate_plan(plan, n_devices=rc.arch.n_cores, arch=rc.arch)


def test_load_rejects_wrong_graph(tmp_path):
    g = _graph()
    ck, _, _ = _keep_ckpt(tmp_path, g, [_arch(1)])
    other = transformer(n_layers=1, d_model=32, d_ff=64, seq=32, name="tf-t")
    with pytest.raises(ValueError, match="content-match"):
        load_realize_candidates(ck, {"TF": other}, verbose=False)


def test_load_refuses_metrics_only(tmp_path):
    g = _graph()
    cfg = DSEConfig(batch=4, sa=SAConfig(iters=30, seed=0))  # no mappings
    ck = tmp_path / "nomap.ckpt.jsonl"
    run_dse([_arch(1)], {"TF": g}, cfg, checkpoint=ck)
    with pytest.raises(ValueError, match="keep_mappings"):
        load_realize_candidates(ck, {"TF": g}, verbose=False)


def test_graph_from_spec():
    g = graph_from_spec("transformer:n_layers=1,d_model=64,d_ff=128,"
                        "seq=32,name=tf-t")
    assert g.layers.keys() == _graph().layers.keys()
    with pytest.raises(ValueError, match="unknown workload spec"):
        graph_from_spec("nonsense")


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------

def test_validate_plan_rejects_device_mismatch():
    g = _graph()
    arch = _arch(1)
    groups = [LayerGroup(names=tuple(g.topo_order()), batch_unit=2)]
    # tangram needs >= 1 core per layer: use a wider arch for the mapping
    wide = ArchConfig(x_cores=4, y_cores=4, noc_bw=32.0, d2d_bw=16.0,
                      dram_bw=64.0, glb_kb=512, macs_per_core=1024)
    mapping = tangram_map(groups, g, wide)
    plan = lms_to_plan(mapping)
    validate_plan(plan, n_devices=16, arch=wide)
    with pytest.raises(ValueError, match="devices"):
        validate_plan(plan, n_devices=4)           # pool too small
    with pytest.raises(ValueError, match="corrupt"):
        validate_plan(plan, n_devices=16, arch=arch)   # 4-core arch
    # structural damage: Part product != |CG|
    bad = MeshPlan(stages=[StagePlan(layers=("l",), devices=(0, 1),
                                     parts={"l": (1, 1, 1, 1)},
                                     cgs={"l": (0, 1)})], batch_unit=1)
    with pytest.raises(ValueError, match="product"):
        validate_plan(bad, n_devices=4)


# ---------------------------------------------------------------------------
# realized stage parity + measurement (subprocess: forced host devices)
# ---------------------------------------------------------------------------

def _run_sub(code: str, n_devices: int = 12, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.splitlines()[-1])


def test_realized_stage_pallas_vs_oracle_parity():
    code = textwrap.dedent("""
        import json
        import numpy as np
        from repro.core.bridge import lms_to_plan
        from repro.core.hw import ArchConfig
        from repro.core.tangram import tangram_map
        from repro.core.workload import LayerGroup
        from repro.core.workloads import transformer
        from repro.realize.measure import measure_candidate
        from repro.realize.plan import RealizeCandidate
        from repro.realize.program import build_program

        arch = ArchConfig(x_cores=4, y_cores=3, xcut=2, ycut=1, noc_bw=32,
                          d2d_bw=16, dram_bw=64, glb_kb=1024,
                          macs_per_core=1024)
        g = transformer(n_layers=1, d_model=64, d_ff=128, seq=32,
                        name="tf-par")
        groups = [LayerGroup(names=tuple(g.topo_order()), batch_unit=2)]
        mapping = tangram_map(groups, g, arch)
        plan = lms_to_plan(mapping)
        out = {}
        runs = {}
        for use_pallas in (True, False):
            prog = build_program(g, plan, use_pallas=use_pallas)
            prog.compile_all()
            runs[use_pallas] = prog.execute(seed=0)
            if use_pallas:
                routes = prog.stages[0].routes
                out["has_flash"] = any(r.startswith("flash:")
                                       for r in routes.values())
                cand = RealizeCandidate(
                    key="k", workload="TF", arch=arch, mapping=mapping,
                    graph=g, energy_j=1.0, delay_s=1.0)
                rep = measure_candidate(cand, prog, execute=False)
                st = rep.stages[0]
                out["flops"] = st.flops
                out["pred_flops"] = st.pred_flops
                out["hbm"] = st.hbm_bytes
                out["pred_dram"] = st.pred_dram_bytes
                out["ratios"] = st.ratios()
        errs = []
        for name, a in runs[True]["outputs"].items():
            b = runs[False]["outputs"][name]
            errs.append(float(np.abs(np.asarray(a) - np.asarray(b)).max()
                              / (np.abs(np.asarray(b)).max() + 1e-9)))
        out["max_rel_err"] = max(errs)
        print(json.dumps(out))
    """)
    rec = _run_sub(code)
    # the realized stage must actually exercise the flash kernel route
    assert rec["has_flash"]
    assert rec["max_rel_err"] < 2e-4
    # measured/predicted of the same stage are within calibration range
    assert rec["flops"] > 0 and rec["pred_flops"] > 0
    assert 0.2 < rec["ratios"]["flops"] < 20.0
    assert rec["hbm"] > 0 and rec["pred_dram"] > 0


def test_realize_driver_end_to_end(tmp_path):
    """checkpoint -> CLI driver (--top 2 --calibrate) -> report + overlay."""
    g = _graph()
    ck, _, _ = _keep_ckpt(tmp_path, g, [_arch(1), _arch(2)])
    out = tmp_path / "realize.jsonl"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "repro.launch.realize",
           "--ckpt", str(ck),
           "--workload",
           "TF=transformer:n_layers=1,d_model=64,d_ff=128,seq=32,name=tf-t",
           "--top", "2", "--calibrate", "--host-devices", "8",
           "--out", str(out)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    recs = [json.loads(l) for l in out.read_text().splitlines()
            if "_key" in l]
    assert len(recs) == 2
    for rec in recs:
        assert rec["totals"]["flops"] > 0
        assert rec["stages"]
    overlay = load_overlay(out.with_suffix(".overlay.json"))
    assert overlay.n_stages > 0
    # resumed run: no re-measurement, same record count
    r2 = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                        env=env)
    assert r2.returncode == 0
    assert r2.stdout.count("resumed from") == 2


# ---------------------------------------------------------------------------
# calibration invariants
# ---------------------------------------------------------------------------

def _synthetic_report(ratio: float):
    from repro.realize.measure import RealizationReport, StageReport
    st = StageReport(index=0, layers=("l",), n_devices=2, routes={},
                     flops=2.0e6, pred_flops=1.0e6,
                     hbm_bytes=ratio * 1e6, pred_dram_bytes=1e6,
                     ici_bytes=ratio * 1e5, pred_noc_bytes=1e5,
                     dci_bytes=ratio * 1e4, pred_d2d_bytes=1e4)
    return RealizationReport(key="k", workload="TF", arch_label="a",
                             tech=TECH_12NM.name, batch_unit=1, stages=[st])


def test_overlay_identity_is_bitwise_noop():
    overlay = TechOverlay()
    assert overlay.is_identity()
    assert overlay.apply(TECH_12NM) is TECH_12NM
    arch = _arch(2)
    assert overlay.apply_arch(arch) is arch
    cands = [_arch(1), _arch(2)]
    assert all(a is b for a, b in
               zip(calibrated_candidates(cands, overlay), cands))
    # run_dse under the identity overlay is bit-identical to baseline
    g = _graph()
    cfg = DSEConfig(batch=4, sa=SAConfig(iters=30, seed=0))
    base = run_dse(cands, {"TF": g}, cfg)
    cal = run_dse(calibrated_candidates(cands, overlay), {"TF": g}, cfg)
    assert [(p.objective, p.energy_j, p.delay_s) for p in base] == \
        [(p.objective, p.energy_j, p.delay_s) for p in cal]


def test_overlay_shifts_evaluator_toward_measurement():
    """measured > predicted traffic => calibrated evaluator reports MORE
    energy for the same mapping (and vice versa)."""
    g = _graph()
    wide = ArchConfig(x_cores=4, y_cores=4, xcut=2, ycut=1, noc_bw=32.0,
                      d2d_bw=16.0, dram_bw=64.0, glb_kb=512,
                      macs_per_core=1024)
    groups = [LayerGroup(names=tuple(g.topo_order()), batch_unit=2)]
    mapping = tangram_map(groups, g, wide)
    base_e = Evaluator(wide, g).evaluate(mapping, 4).energy_j
    for ratio, direction in ((3.0, 1), (0.3, -1)):
        overlay = fit_overlay([_synthetic_report(ratio)])
        assert not overlay.is_identity()
        np.testing.assert_allclose(
            [overlay.f_dram, overlay.f_noc, overlay.f_d2d],
            [ratio] * 3, rtol=1e-9)
        cal_arch = overlay.apply_arch(wide)
        assert cal_arch.tech.name.startswith(TECH_12NM.name + "+cal")
        cal_e = Evaluator(cal_arch, g).evaluate(mapping, 4).energy_j
        assert direction * (cal_e - base_e) > 0
    # different overlays must yield differently-named Techs: checkpoints
    # identify techs by name, so a collision would let a sweep calibrated
    # under one overlay resume under another's constants
    a = fit_overlay([_synthetic_report(3.0)]).apply(TECH_12NM)
    b = fit_overlay([_synthetic_report(0.3)]).apply(TECH_12NM)
    assert a.name != b.name
    # fit is clamped against degenerate stages
    wild = fit_overlay([_synthetic_report(1e6)])
    assert wild.f_dram == 10.0


def test_overlay_json_roundtrip(tmp_path):
    overlay = fit_overlay([_synthetic_report(2.5)], source="test")
    p = save_overlay(overlay, tmp_path / "ov.json")
    back = load_overlay(p)
    assert back == overlay


def test_calibrated_sweep_resumable(tmp_path):
    """A non-identity overlay registers its Tech: calibrated checkpoints
    must survive resume (arch_from_dict refuses unknown tech names)."""
    overlay = fit_overlay([_synthetic_report(2.0)])
    g = _graph()
    cands = calibrated_candidates([_arch(1)], overlay)
    cfg = DSEConfig(batch=4, sa=SAConfig(iters=30, seed=0))
    ck = tmp_path / "cal.ckpt.jsonl"
    first = run_dse(cands, {"TF": g}, cfg, checkpoint=ck)
    again = run_dse(cands, {"TF": g}, cfg, checkpoint=ck)
    assert [p.objective for p in first] == [p.objective for p in again]


def test_split_attention_pair_consumes_probabilities():
    """With qk and av in different stages, the scores layer applies the
    softmax the fused flash route applies: its cube holds probabilities
    and a deep realized chain stays finite."""
    from repro.realize.program import build_program
    arch = ArchConfig(x_cores=1, y_cores=1, noc_bw=32.0, d2d_bw=16.0,
                      dram_bw=64.0, glb_kb=512, macs_per_core=1024)
    g = transformer(n_layers=4, d_model=64, d_ff=128, seq=64, name="tf-d")
    groups = [LayerGroup(names=(n,), batch_unit=2) for n in g.topo_order()]
    plan = lms_to_plan(tangram_map(groups, g, arch))
    prog = build_program(g, plan, use_pallas=False)
    routes = {n: r for sp in prog.stages for n, r in sp.routes.items()}
    assert routes["l0_qk"] == "scores" and routes["l0_av"] == "matmul"
    out = prog.execute(seed=0)["outputs"]
    np.testing.assert_allclose(np.asarray(out["l0_qk"]).sum(-1), 1.0,
                               rtol=1e-5)
    assert all(np.isfinite(np.asarray(v)).all() for v in out.values())
