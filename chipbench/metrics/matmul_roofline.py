"""Share (%) of its roofline that the tiled-matmul Pallas kernel reaches.

Each GEMM of a pass, ``(M, K) @ (K, N)`` in float32, needs ``2 M K N``
operations and at least ``4 (M K + K N + M N)`` bytes of HBM traffic; its
least time is the larger of operations over the bf16 peak and bytes over
the HBM bandwidth (the bound is named by :func:`bound`).  The share is
the least time of every traced pass's GEMMs over the summed device time
of the kernel's events in the traced window: the Pallas custom calls
(``tpu_custom_call``) of the realized stages' ``matmul`` instructions.
"""


def is_kernel(op: str) -> bool:
    """A tiled-matmul kernel event: the device op of the HLO instruction
    ``%matmul... = ... custom-call(...), custom_call_target="tpu_custom_call"``."""
    return op.startswith("%matmul") and '"tpu_custom_call"' in op


def gemm_flops(M: int, K: int, N: int) -> int:
    return 2 * M * K * N


def gemm_bytes(M: int, K: int, N: int) -> int:
    return 4 * (M * K + K * N + M * N)


def least_seconds(shapes, peaks) -> float:
    return sum(max(gemm_flops(*s) / peaks["bf16_flops"],
                   gemm_bytes(*s) / peaks["hbm_bytes_per_s"])
               for s in shapes)


def bound(shape, peaks) -> str:
    return ("compute" if gemm_flops(*shape) / peaks["bf16_flops"]
            >= gemm_bytes(*shape) / peaks["hbm_bytes_per_s"] else "memory")


def read(run):
    s, ev = run.trace_summary, run.obs.get("trace_events")
    shapes, passes = run.obs.get("gemm_shapes"), run.obs.get("passes")
    if not s or not ev or not shapes or not passes or not run.peaks:
        return None
    from chipbench.trace import kernel_ns
    ns, count = kernel_ns(ev, s["window"], is_kernel)
    if ns <= 0 or count != len(shapes) * len(passes):
        return None
    return 100.0 * least_seconds(shapes, run.peaks) * len(passes) / (ns / 1e9)
