"""Host share of a realized pass (ms): each pass's wall time minus the
stage times ``RealizedProgram.execute`` reports (``wall_s``, from dispatch
to ready), averaged over the window: weight and input synthesis on the
host and their ``device_put``."""


def read(run):
    passes = run.obs.get("passes")
    if not passes:
        return None
    return sum(p["wall_s"] - p["stage_s"] for p in passes) / len(passes) * 1e3
