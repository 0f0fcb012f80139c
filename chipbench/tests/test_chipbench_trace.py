"""The trace reduction: busy union, idle share, kernel sums, breakdown."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace as tr  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _synthetic():
    # window 0..1000 ns; ops overlap at 100-300/200-400 and stick out of
    # the window at 950-1100; host spans name what ran in each gap
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 100, 200], ["fusion.2", 200, 200],
            ["_mm_kernel", 600, 100], ["fusion.3", 950, 150],
            ["early", -50, 20]]},
        "modules": {"/device:TPU:0": [["jit_fused(1)", 100, 300],
                                      ["jit_stage", 600, 100]]},
        "host": [["chipbench.window", 0, 1000],
                 ["chipbench.task", 0, 1000],
                 ["chipbench.step", 400, 200],
                 ["chipbench.fused", 700, 250]],
    }


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]


def test_busy_idle_and_gaps_on_a_synthetic_trace():
    ev = _synthetic()
    s = tr.summarize(ev)
    assert s["window_s"] == 1e-6
    # busy: 100-400, 600-700, 950-1000 -> 450 ns
    assert s["busy_s"] == pytest.approx(450e-9)
    assert s["idle_share"] == pytest.approx(0.55)
    gaps = s["breakdown"]["idle_gaps"]
    # gaps 0-100 (task), 400-600 (step), 700-950 (fused)
    assert [g[0] for g in gaps] == ["fused", "step", "task"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9, 100e-9])
    ops = dict(s["breakdown"]["device_ops"])
    assert "fusion.3" not in ops and "early" not in ops
    assert ops["fusion.1"] == pytest.approx(200e-9)


def test_kernel_sums_by_name_inside_the_window():
    ev = _synthetic()
    w = tr.window_of(ev)
    assert tr.kernel_ns(ev, w, lambda n: "_mm_kernel" in n) == (100, 1)
    assert tr.kernel_ns(ev, w, lambda n: n.startswith("jit_fused"),
                        key="modules") == (300, 1)


def test_a_trace_without_device_ops_has_no_idle_share():
    ev = _synthetic()
    ev["devices"] = {}
    assert tr.summarize(ev)["idle_share"] is None


@pytest.mark.parametrize("name", ["v5e_tiny_realize.json",
                                  "v5e_tiny_sa.json"])
def test_recorded_chip_trace(name):
    """Traces recorded on a TPU v5e (``--trace 1`` runs of the test cells,
    cut down to :func:`trace.events_from_xplane`'s form) reduce to the
    numbers the chip run printed.  The realize trace holds 3 passes of the
    tiny transformer's 16 GEMMs: 48 matmul kernels."""
    rec = json.loads((FIXTURES / name).read_text())
    ev, want = rec["events"], rec["expect"]
    s = tr.summarize(ev)
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(s["idle_by_span_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    assert s["breakdown"]["idle_gaps"][0][0] == want["longest_gap_span"]
    assert len(s["breakdown"]["device_ops"]) == 10
    w = tr.window_of(ev)
    for key, kernels in want["kernels"].items():
        for kernel, (ns, count) in kernels.items():
            assert list(tr.kernel_ns(ev, w, lambda n: kernel in n, key=key)) \
                == [ns, count]


def test_metric_readers_find_their_kernels_in_recorded_traces():
    from chipbench import run as harness
    metrics = ROOT / "chipbench" / "metrics"
    mm = harness.load_module(metrics / "matmul_roofline.py")
    fused = harness.load_module(metrics / "fused_roofline.py")
    rec = json.loads((FIXTURES / "v5e_tiny_realize.json").read_text())
    ev = rec["events"]
    assert tr.kernel_ns(ev, tr.window_of(ev), mm.is_kernel)[1] == 48
    rec = json.loads((FIXTURES / "v5e_tiny_sa.json").read_text())
    ev = rec["events"]
    assert tr.kernel_ns(ev, tr.window_of(ev),
                        lambda n: n.startswith(fused.MODULE),
                        key="modules")[1] == 16
