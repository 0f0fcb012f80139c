"""Contribution pieces built by the analyzer (``PREFETCH_STATS``: batched
plus scalar builds, one per first-level cache miss) in the window, per SA
eval."""


def read(run):
    evals = run.obs.get("evals")
    if not evals or "builds" not in run.obs:
        return None
    return run.obs["builds"] / evals
