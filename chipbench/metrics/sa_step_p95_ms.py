"""95th percentile, in ms, of the window's lockstep SA steps: one step
proposes for every chain, scores the proposals with the fused pass and runs
the acceptances.  Compiles that land inside the window land here."""

import numpy as np


def read(run):
    steps = run.obs.get("step_s")
    if not steps or len(steps) < 200:       # ten samples beyond the p95
        return None
    return float(np.percentile(np.asarray(steps), 95)) * 1e3
