"""Share of the traced sweep window (%) in which no operation ran on the
device: 1 - union of device-op intervals / window."""


def read(run):
    s = run.trace_summary
    if not s or s["idle_share"] is None or run.cell.mix["kind"] != "sa_pool":
        return None
    return 100.0 * s["idle_share"]
