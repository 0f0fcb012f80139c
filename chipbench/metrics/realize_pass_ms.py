"""Mean wall time (ms) of a realized pass: the window's wall time over the
passes completed in it, each ending on block_until_ready."""


def read(run):
    passes = run.obs.get("passes")
    if not passes or run.window_s <= 0:
        return None
    return run.window_s / len(passes) * 1e3
