"""Set-up seconds: process start to the window's start (imports, device
init, building the system under test, warm-up and any compilation)."""


def read(run):
    return run.setup_s if run.setup_s > 0 else None
