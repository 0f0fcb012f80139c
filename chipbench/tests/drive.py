"""Run one benchmark cell on the CPU, with an optional fault planted in the
program underneath the harness (or, for ``reference-*`` faults, in the
plain reference); prints the result line.

    python drive.py <root> <cell> <fault|-> <control 0|1> <trace 0|1> <seconds> <seed> [fill]

The harness's look for a chip is skipped (``require_tpu=False``); every
other step of a run is the real one.  A driver's cache fill runs this
script again with ``fill`` (and no fault) as its child.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _wrap(obj, name, make):
    setattr(obj, name, make(getattr(obj, name)))


def plant(fault: str) -> None:
    """Break the timed path the way ``fault`` names."""
    from repro.core.evaluator import Evaluator
    from repro.realize.program import RealizedProgram

    if fault == "fused-answer-altered":          # a wrong score, on chip
        def make(f):
            def g(ev, requests, tb):
                out = f(ev, requests, tb)
                for ge, _ in out:
                    ge.delay_s *= 1 + 1e-3
                return out
            return g
        _wrap(Evaluator, "_eval_requests_fused", make)
    elif fault == "fused-half-batch":            # half the rows left out
        def make(f):
            def g(ev, requests, tb):
                out = f(ev, requests[:(len(requests) + 1) // 2], tb)
                return (out * 2)[:len(requests)]
            return g
        _wrap(Evaluator, "_eval_requests_fused", make)
    elif fault == "exact-state-unchanged":       # re-score returns old state
        def make(f):
            prev = {}

            def g(ev, mapping, tb):
                res = f(ev, mapping, tb)
                out = prev.get("res", res)
                prev["res"] = res
                return out
            return g
        _wrap(Evaluator, "evaluate", make)
    elif fault == "exact-answer-altered":        # reported score altered
        def make(f):
            def g(ev, mapping, tb):
                res = f(ev, mapping, tb)
                res.energy_j *= 1 + 1e-12
                return res
            return g
        _wrap(Evaluator, "evaluate", make)
    elif fault == "pass-state-unchanged":        # a pass returns old cubes
        def make(f):
            first = {}

            def g(prog, seed=0):
                res = f(prog, seed=seed)
                first.setdefault("outputs", res["outputs"])
                return dict(res, outputs=first["outputs"])
            return g
        _wrap(RealizedProgram, "execute", make)
    elif fault == "pass-half-batch":             # half the batch left out
        def make(f):
            def g(prog, seed=0):
                res = f(prog, seed=seed)
                half = prog.batch_unit // 2
                res["outputs"] = {n: x.at[half:].set(0.0)
                                  for n, x in res["outputs"].items()}
                return res
            return g
        _wrap(RealizedProgram, "execute", make)
    elif fault == "pass-answer-altered":         # one cube altered
        def make(f):
            def g(prog, seed=0):
                res = f(prog, seed=seed)
                name = sorted(res["outputs"])[0]
                res["outputs"][name] = res["outputs"][name] * (1 + 1e-3)
                return res
            return g
        _wrap(RealizedProgram, "execute", make)
    elif fault == "reference-draws-by-layer":    # the reference's inputs
        from chipbench.reference import realized  # drawn layer by layer

        def make(f):
            def g(layers, stages, bu, seed):
                return f(layers, [[n] for st in stages for n in st], bu,
                         seed)
            return g
        _wrap(realized, "draws", make)
    elif fault != "-":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    root, cell, fault, control, trace, seconds, seed = sys.argv[1:8]
    fill = sys.argv[8:] == ["fill"]
    plant(fault)
    from chipbench import run as harness
    fill_cmd = [sys.executable, __file__, root, cell, "-", "0", "0", "0",
                "0", "fill"]
    res = harness.execute(cell, int(seed), float(seconds), trace == "1",
                          root=Path(root), require_tpu=False,
                          control=control == "1", fill=fill,
                          fill_cmd=fill_cmd)
    if not fill:
        print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
