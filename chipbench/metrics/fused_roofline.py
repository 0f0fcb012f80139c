"""Share (%) of its roofline that the fused scorer's device time reaches.

Work per fused call is counted from the *unpadded* work, so padding waste
lowers the share: the segment-sum reads ``n`` real contribution entries
(int32 index + float32 value, 8 bytes each) and writes the ``B x buf_len``
float32 buffer (4 bytes a cell); one add per entry.  The least time of a
call is the larger of its operations over the peak and its bytes over the
HBM bandwidth; the share is the sum of least times over the summed device
time of the fused program's modules (``jit_fused``) in the traced window.
"""

MODULE = "jit_fused"


def call_bytes(B: int, buf_len: int, n: int) -> int:
    return 8 * n + 4 * B * buf_len


def call_flops(B: int, buf_len: int, n: int) -> int:
    return n


def least_seconds(shapes, peaks) -> float:
    return sum(max(call_flops(*s) / peaks["bf16_flops"],
                   call_bytes(*s) / peaks["hbm_bytes_per_s"])
               for s in shapes)


def read(run):
    s, ev = run.trace_summary, run.obs.get("trace_events")
    shapes = run.obs.get("fused_shapes")
    if not s or not ev or not shapes or not run.peaks:
        return None
    from chipbench.trace import kernel_ns
    ns, count = kernel_ns(ev, s["window"], lambda n: n.startswith(MODULE),
                          key="modules")
    if ns <= 0 or count != len(shapes):
        return None
    return 100.0 * least_seconds(shapes, run.peaks) / (ns / 1e9)
