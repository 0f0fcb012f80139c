"""SA proposals scored (chain-iterations: iterations x chains of every task
completed in the window) over the window's whole wall time, which includes
the task that was in flight when the time ran out."""


def read(run):
    evals = run.obs.get("evals")
    if not evals or run.window_s <= 0:
        return None
    return evals / run.window_s
