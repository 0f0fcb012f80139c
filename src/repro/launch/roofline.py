"""Roofline-term extraction from compiled XLA artifacts.

compute term    = per-device HLO FLOPs / peak_FLOPs
memory term     = per-device HLO bytes / HBM bw
collective term = per-device collective bytes / ICI link bw

The peaks are the target chip's, looked up by its ``device_kind`` in
``core.hw.TPU_CHIPS`` (v5e: 197e12 bf16 FLOP/s, 819e9 B/s HBM, 50e9 B/s
per ICI link); a kind with no published peaks is refused.

``cost_analysis()`` on the SPMD-partitioned executable reports *per-device*
FLOPs/bytes (verified empirically: a 256-way-sharded matmul reports 1/256 of
the global FLOPs), so the terms below already match the prompt's
global/(chips x peak) formulas.  Collective bytes are parsed from the
compiled HLO text: the summed output-tensor sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (+ their
async -start variants; -done ops are skipped to avoid double counting).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

from ..core.hw import TPUChip, chip_for

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "e4m3": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum byte sizes of every dtype[dims] occurrence in a type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes from (post-SPMD) HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        typestr, op = m.group(1), m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            out[base] += _shape_bytes(typestr)
    return out


@dataclass
class Roofline:
    name: str
    chip: TPUChip
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_by_kind: Dict[str, int] = field(default_factory=dict)
    # memory proof
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    model_flops: float = 0.0           # 6*N*D (or 2*N*D serve), GLOBAL
    n_devices: int = 256
    compile_s: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.chip.peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / self.chip.ici_bw

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO FLOPs (remat/redundancy waste metric)."""
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute share of the bound: (model-FLOPs time) / t_bound."""
        t_useful = (self.model_flops / self.n_devices
                    / self.chip.peak_flops_bf16)
        return t_useful / self.t_bound if self.t_bound else 0.0

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "coll_by_kind": self.coll_by_kind,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "model_flops": self.model_flops,
            "n_devices": self.n_devices,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "compile_s": self.compile_s,
        }


def flash_kernel_adjustment(cfg, shape, data_ax: int = 16,
                            model_ax: int = 16, n_pod: int = 1,
                            block: int = 1024) -> float:
    """Bytes/device the Pallas flash kernel saves vs the jnp-lowered path.

    The dry-run lowers the jnp flash scan (Pallas cannot compile on the CPU
    backend); its per-kv-block score/prob tensors are materialized between
    fusions and show up as HBM traffic, but on TPU the kernel keeps them in
    VMEM.  This analytic adjustment = (scan-internal s/p traffic) minus
    (ideal kernel q/k/v/o traffic), with x4 for train (fwd + remat-fwd +
    2-pass bwd), x1 for prefill, 0 for decode (einsum path, no scan).
    Napkin math, reported alongside the as-lowered term — never替换 it.
    """
    if cfg.family == "ssm" or shape.kind == "decode":
        return 0.0
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    B, S = shape.global_batch, shape.seq_len
    if S * S <= 256 * 2048:
        return 0.0                              # einsum path, no scan
    bshard = 1
    for ax in (n_pod, data_ax):
        if B % (bshard * ax) == 0:
            bshard *= ax
    B_loc = B // bshard
    # attention layout (mirrors launch.steps.derive_attn_rules)
    if KV % model_ax == 0 or H % model_ax == 0:
        heads_loc = max(1, H // model_ax)
        Sq_loc = S
    else:
        heads_loc = H
        Sq_loc = max(1, S // model_ax)
    nblocks = -(-S // block)
    per_call = nblocks * 2 * B_loc * heads_loc * Sq_loc * block * 4 * 2
    ideal = B_loc * S * (H + 2 * KV) * hd * 2 * 2
    n_attn = cfg.n_layers if cfg.family != "hybrid" else cfg.n_shared_attn()
    if cfg.family == "encdec":
        n_attn = cfg.n_enc_layers + 2 * cfg.n_layers
    passes = 4.0 if shape.kind == "train" else 1.0
    return max(0.0, (per_call - ideal) * n_attn * passes)


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N*D for inference tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n * tokens


def analyze_compiled(name: str, compiled, lowered_text: Optional[str],
                     model_flops: float, n_devices: int,
                     device_kind: str, compile_s: float = 0.0) -> Roofline:
    """Roofline terms from the compiled per-device module, against the
    peaks of the chip JAX names ``device_kind``.

    Primary source is the trip-count-aware HLO walker (hlo_analysis) —
    XLA's own cost_analysis counts while bodies once, which would be wrong
    by ~n_layers x n_micro for scanned models (verified; see
    hlo_analysis docstring).  The raw cost_analysis numbers are kept for
    cross-checking in the record.
    """
    from .hlo_analysis import analyze_hlo_text
    ca = compiled.cost_analysis()
    text = compiled.as_text()
    costs = analyze_hlo_text(text)
    ma = compiled.memory_analysis()
    return Roofline(
        name=name,
        chip=chip_for(device_kind),
        flops_per_device=costs.flops,
        bytes_per_device=costs.bytes,
        coll_bytes_per_device=costs.coll_bytes,
        coll_by_kind={k: int(v) for k, v in costs.coll_by_kind.items()},
        argument_bytes=float(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=float(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0)),
        model_flops=model_flops,
        n_devices=n_devices,
        compile_s=compile_s)
