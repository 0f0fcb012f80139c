"""Measured-vs-predicted extraction (realization stage 3).

For every compiled stage program this module pulls the *measured* side from
the XLA artifacts — trip-count-aware FLOPs and HBM bytes plus collective
bytes from the compiled HLO (``launch/hlo_analysis``, the same walker the
512-device dry-run trusts), compile-time memory from
``compiled.memory_analysis()``, and the inter-stage activation bytes the
executor actually moved — and the *predicted* side from the analytical
evaluator for the exact same LMS: per-group MACs, NoC bytes, D2D bytes and
DRAM bytes out of ``GroupAnalysis`` (``Evaluator.traffic_summary``).

Axis correspondence (the bridge contract of ``core/bridge.mesh_as_arch``):

  measured intra-stage collective bytes  <->  predicted NoC-link bytes (ICI)
  measured inter-stage transfer bytes    <->  predicted D2D bytes      (DCI)
  measured HLO HBM bytes                 <->  predicted DRAM bytes
  measured HLO FLOPs                     <->  2 x predicted MACs

Absolute agreement is not expected — the realized program runs f32 while
the cost model prices int8/bf16 dataflows — but the *ratios* are stable
per technology, which is exactly what
:mod:`.calibrate` fits.  Everything is per ONE pipeline pass (batch-unit
batch), matching ``GroupAnalysis``'s per-pass convention.

Expected-traffic graphs (MoE / routed workloads, ``graph.is_scaled``)
lower to *dense-equivalent* programs — XLA executes the full cubes, while
the analytical prediction carries the expected-traffic scales.  To keep
the measured/predicted ratios comparable to the dense case (one stable
factor per technology axis), each stage's measured numbers are multiplied
by the per-axis expected-traffic factor ``pred_scaled / pred_dense``
recovered from a :func:`repro.core.workload.dense_twin` evaluation of the
identical LMS.  Dense graphs take the exact historical path (the twin IS
the graph; no extra evaluation, no float ops).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

from .. import obs as _obs
from ..core.evaluator import evaluator_for
from ..core.workload import dense_twin
from ..launch.hlo_analysis import analyze_hlo_text
from .plan import RealizeCandidate
from .program import RealizedProgram, StageProgram


@dataclass
class StageReport:
    """Measured and predicted traffic of one realized pipeline stage."""
    index: int
    layers: Tuple[str, ...]
    n_devices: int
    routes: Dict[str, str]
    # measured (global across the stage mesh, one pass)
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0             # intra-stage collective bytes
    dci_bytes: float = 0.0             # inter-stage activation transfer
    coll_by_kind: Dict[str, float] = field(default_factory=dict)
    temp_bytes: float = 0.0            # compile-time scratch per device
    arg_bytes: float = 0.0
    compile_s: float = 0.0
    wall_s: float = 0.0
    # predicted (analytical, one pass)
    pred_flops: float = 0.0
    pred_dram_bytes: float = 0.0
    pred_noc_bytes: float = 0.0
    pred_d2d_bytes: float = 0.0
    pred_delay_s: float = 0.0
    pred_energy_j: float = 0.0
    pred_glb_overflow: float = 0.0
    # expected-traffic factors applied to the measured side (scaled graphs
    # only; empty for dense graphs — see module docstring)
    expected_scale: Dict[str, float] = field(default_factory=dict)

    def ratios(self) -> Dict[str, float]:
        """measured / predicted per axis; only well-defined pairs appear."""
        out: Dict[str, float] = {}
        for key, meas, pred in (
                ("flops", self.flops, self.pred_flops),
                ("dram_bytes", self.hbm_bytes, self.pred_dram_bytes),
                ("noc_bytes", self.ici_bytes, self.pred_noc_bytes),
                ("d2d_bytes", self.dci_bytes, self.pred_d2d_bytes)):
            if pred > 0 and meas > 0:
                out[key] = meas / pred
        return out

    def to_record(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in (
            "index", "n_devices", "flops", "hbm_bytes", "ici_bytes",
            "dci_bytes", "temp_bytes", "arg_bytes", "compile_s", "wall_s",
            "pred_flops", "pred_dram_bytes", "pred_noc_bytes",
            "pred_d2d_bytes", "pred_delay_s", "pred_energy_j")}
        d["layers"] = list(self.layers)
        d["routes"] = dict(self.routes)
        d["coll_by_kind"] = dict(self.coll_by_kind)
        d["ratios"] = self.ratios()
        if self.expected_scale:        # dense records keep their old shape
            d["expected_scale"] = dict(self.expected_scale)
        return d


@dataclass
class RealizationReport:
    """Full measured-vs-predicted record of one realized candidate."""
    key: str
    workload: str
    arch_label: str
    tech: str
    batch_unit: int
    stages: List[StageReport]
    pred_energy_j: float = 0.0         # checkpoint's analytical prediction
    pred_delay_s: float = 0.0

    def totals(self) -> Dict[str, float]:
        t: Dict[str, float] = {}
        for f in ("flops", "hbm_bytes", "ici_bytes", "dci_bytes",
                  "pred_flops", "pred_dram_bytes", "pred_noc_bytes",
                  "pred_d2d_bytes", "wall_s", "compile_s"):
            t[f] = sum(getattr(s, f) for s in self.stages)
        return t

    def ratio_summary(self) -> Dict[str, float]:
        """Geometric-mean measured/predicted ratio per traffic axis."""
        acc: Dict[str, List[float]] = {}
        for s in self.stages:
            for k, v in s.ratios().items():
                acc.setdefault(k, []).append(v)
        return {k: float(np.exp(np.mean(np.log(v))))
                for k, v in acc.items()}

    def to_record(self) -> Dict[str, Any]:
        return {"workload": self.workload, "arch": self.arch_label,
                "tech": self.tech, "batch_unit": self.batch_unit,
                "pred_energy_j": self.pred_energy_j,
                "pred_delay_s": self.pred_delay_s,
                "totals": self.totals(),
                "ratio_summary": self.ratio_summary(),
                "stages": [s.to_record() for s in self.stages]}


def _measure_stage(sp: StageProgram) -> Dict[str, float]:
    """Measured traffic of one compiled stage, scaled mesh-global."""
    compiled = sp.compiled
    n_dev = sp.n_devices
    costs = analyze_hlo_text(compiled.as_text())
    out = {"flops": costs.flops * n_dev,
           "hbm_bytes": costs.bytes * n_dev,
           "ici_bytes": costs.coll_bytes * n_dev,
           "coll_by_kind": {k: v * n_dev
                            for k, v in costs.coll_by_kind.items()}}
    ma = compiled.memory_analysis()
    out["temp_bytes"] = float(ma.temp_size_in_bytes)
    out["arg_bytes"] = float(ma.argument_size_in_bytes)
    return out


def measure_candidate(cand: RealizeCandidate, prog: RealizedProgram,
                      execute: bool = True, seed: int = 0
                      ) -> RealizationReport:
    """Compile (if needed), measure and optionally execute one candidate.

    The predicted side re-runs the analytical evaluator on the candidate's
    own (arch, graph, LMS) — the identical code path the DSE scored it
    with, so the diff isolates model-vs-measurement error, not drift."""
    ev = evaluator_for(cand.arch, cand.graph)
    # scaled graphs execute their dense-equivalent cubes; recover the
    # per-axis expected-traffic factor from a dense-twin evaluation of the
    # same LMS (dense graphs: twin IS the graph, no second evaluator)
    twin = dense_twin(cand.graph)
    ev_dense = ev if twin is cand.graph else evaluator_for(cand.arch, twin)
    reports: List[StageReport] = []
    for sp, (grp, lms) in zip(prog.stages, cand.mapping):
        with _obs.span("realize.measure_stage", key=cand.key,
                       stage=sp.index, n_devices=sp.n_devices):
            if sp.compiled is None:
                sp.lower_and_compile()
            # total_batch = batch_unit: ONE pipeline pass, with weight
            # loads unamortized — exactly what the realized stage executes
            pred = ev.traffic_summary(grp, lms, grp.batch_unit)
            meas = _measure_stage(sp)
            esc: Dict[str, float] = {}
            if ev_dense is not ev:
                dense = ev_dense.traffic_summary(grp, lms, grp.batch_unit)
                esc = {k: (pred[k] / dense[k]) if dense[k] > 0 else 1.0
                       for k in ("flops", "dram_bytes", "noc_bytes",
                                 "d2d_bytes")}
                meas["flops"] *= esc["flops"]
                meas["hbm_bytes"] *= esc["dram_bytes"]
                meas["ici_bytes"] *= esc["noc_bytes"]
            reports.append(StageReport(
                index=sp.index, layers=sp.stage.layers,
                n_devices=sp.n_devices,
                routes=dict(sp.routes),
                flops=meas["flops"], hbm_bytes=meas["hbm_bytes"],
                ici_bytes=meas["ici_bytes"],
                coll_by_kind=meas["coll_by_kind"],
                temp_bytes=meas["temp_bytes"], arg_bytes=meas["arg_bytes"],
                compile_s=sp.compile_s,
                pred_flops=pred["flops"],
                pred_dram_bytes=pred["dram_bytes"],
                pred_noc_bytes=pred["noc_bytes"],
                pred_d2d_bytes=pred["d2d_bytes"],
                pred_delay_s=pred["delay_s"],
                pred_energy_j=pred["energy_j"],
                pred_glb_overflow=pred["glb_overflow_bytes"],
                expected_scale=esc))
    if execute:
        with _obs.span("realize.execute", key=cand.key,
                       n_stages=len(reports)):
            run = prog.execute(seed=seed)
        for sr, wall, dci in zip(reports, run["wall_s"], run["dci_bytes"]):
            sr.wall_s = wall
            sr.dci_bytes = float(dci) * sr.expected_scale.get("d2d_bytes",
                                                              1.0)
    return RealizationReport(
        key=cand.key, workload=cand.workload, arch_label=cand.arch.label(),
        tech=cand.arch.tech.name, batch_unit=prog.batch_unit,
        stages=reports, pred_energy_j=cand.energy_j,
        pred_delay_s=cand.delay_s)
