"""Share (%) of the chip's bf16 peak that a realized pass reaches: the
pass's operations (2 x its MACs, counted from the layer shapes by
``reference/realized.pass_macs``) over the mean pass time over the peak."""


def read(run):
    passes, macs = run.obs.get("passes"), run.obs.get("pass_macs")
    if not passes or not macs or not run.peaks or run.window_s <= 0:
        return None
    pass_s = run.window_s / len(passes)
    return 100.0 * 2 * macs / pass_s / run.peaks["bf16_flops"]
