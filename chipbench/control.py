#!/usr/bin/env python3
"""Run a cell with its control in the program's place, on several seeds.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The control is the plain reference computed one precision below what the
configuration states: float32 for the exact (float64) score and bfloat16
for the float32 fused pass in a sweep cell; three bfloat16 passes for the
float32 matmuls at ``highest`` in a realize cell.  Each run drives the
window as a benchmark run does, then puts the control's answers in place
of the program's; every run prints its result line, and ``correct`` has
to come out false.  The benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.run import BenchError, execute  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        try:
            res = execute(args.workload, seed, args.seconds, False,
                          control=True)
        except BenchError as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
