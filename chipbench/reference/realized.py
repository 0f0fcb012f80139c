"""Plain reference of one realized pass of a plan.

What the realized program computes, layer by layer, in straightforward
``jax.numpy`` float32 with every matmul at an explicit precision, built
from the configuration's sizes alone: it imports nothing of the program.
A configuration's reference module gives the layers (``realized_layers``:
:class:`RefLayer` objects, each ``fc``, ``qk``, ``av`` or ``add``); the
caller gives the plan's stages as lists of layer names in execution order.
For the paper's transformer encoder block (Vaswani et al.):

    q, k, v = fc(x); qk = softmax(q k^T / sqrt(d)); av = qk v / sqrt(seq)
    o = fc(av); add1 = o + x; ff1 = fc(add1); ff2 = fc(ff1); add2 = ff2 + add1

with each ``fc`` a GEMM against its own weight scaled by 1/sqrt(C).  Every
layer's output is a cube ``(batch unit, H, 1, K)``.  Where an operand
has another size than the contraction wants (the weight side of ``qk`` and
``av`` is the first ``C x K`` elements of the producer's cube), it is
tiled or truncated in row-major order, as the realization's operand
bridge does.  This arithmetic is the layers' own: it does not change with
how a plan groups them into stages.

Inputs and weights come from a seed, drawn in the order the program's
stages consume them: ``numpy.random.default_rng(seed)``, then stage by
stage, first a standard-normal source ifmap for each of the stage's layers
that has no producer, in stage order, then a weight for each of its ``fc``
layers, in stage order (float64 draws cast to float32).  With one layer a
stage this is that layer's ifmap, then its weight.  A pass exports the
cubes that a later stage reads, and those that nothing reads.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class RefLayer:
    name: str
    kind: str                 # fc | qk | av | add
    H: int
    C: int                    # contraction width (0 for add)
    K: int
    preds: Tuple[str, ...]


def gemm_shapes(layers: Sequence[RefLayer], bu: int
                ) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every GEMM of one pass: one per fc, qk and av layer."""
    return [(bu * l.H, l.C, l.K) for l in layers if l.kind != "add"]


def pass_macs(layers: Sequence[RefLayer], bu: int) -> int:
    """Multiply-accumulates of one pass, counted from the layer shapes as
    the paper counts them (an add counts its two inputs per element)."""
    macs = 0
    for l in layers:
        macs += l.H * l.K * (2 if l.kind == "add" else l.C)
    return macs * bu


def draws(layers: Dict[str, RefLayer], stages: Sequence[Sequence[str]],
          bu: int, seed: int) -> Dict[str, np.ndarray]:
    """Source ifmaps (key ``<layer>:x``) and weights (``<layer>:w``),
    drawn stage by stage."""
    rng = np.random.default_rng(seed)
    out: Dict[str, np.ndarray] = {}
    for stage in stages:
        for l in (layers[n] for n in stage):
            if not l.preds:
                cin = max(l.C, 1) if l.kind != "add" else l.K
                out[f"{l.name}:x"] = rng.normal(
                    size=(bu, l.H, 1, cin)).astype(np.float32)
        for l in (layers[n] for n in stage):
            if l.kind == "fc":
                out[f"{l.name}:w"] = rng.normal(size=(l.C, l.K)).astype(
                    np.float32)
    return out


def _consumers(layers: Dict[str, RefLayer]) -> Dict[str, List[str]]:
    succs: Dict[str, List[str]] = {n: [] for n in layers}
    for l in layers.values():
        for p in l.preds:
            succs[p].append(l.name)
    return succs


def exported(layers: Dict[str, RefLayer], stages: Sequence[Sequence[str]]
             ) -> List[str]:
    """The cubes a pass exports: read by a later stage, or by nothing."""
    stage_of = {n: i for i, stage in enumerate(stages) for n in stage}
    succs = _consumers(layers)
    return [n for stage in stages for n in stage
            if not succs[n] or any(stage_of[s] > stage_of[n]
                                   for s in succs[n])]


def _fit(x, shape):
    """Row-major tile/truncate of ``x`` onto ``shape``."""
    import jax.numpy as jnp
    flat = x.reshape(-1)
    n = int(np.prod(shape))
    reps = -(-n // flat.size)
    if reps > 1:
        flat = jnp.tile(flat, reps)
    return flat[:n].reshape(shape)


def _dot_highest(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _dot_bf16x3(a, b):
    """float32 matmul at ``high``: each operand split into two bfloat16
    pieces, the three largest cross products kept (``a1 b1 + a1 b2 +
    a2 b1``), accumulated in float32 -- what TPU's ``high`` precision does,
    written out so that it means the same on any backend.  The pieces are
    rounded with ``reduce_precision``, which XLA keeps (a float32 ->
    bfloat16 -> float32 round trip it may drop as excess precision), and
    their products are exact in float32."""
    import jax
    import jax.numpy as jnp
    rp = lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)
    a1 = rp(a)
    a2 = rp(a - a1)
    b1 = rp(b)
    b2 = rp(b - b1)
    dot = lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    return dot(a1, b1) + dot(a1, b2) + dot(a2, b1)


def forward(layers: Dict[str, RefLayer], stages: Sequence[Sequence[str]],
            bu: int, seed: int, precision: str = "highest"
            ) -> Dict[str, np.ndarray]:
    """Every exported cube of one pass, as host float32 arrays.
    ``precision="high"`` is the control: every matmul at three bfloat16
    passes instead of float32."""
    import jax
    import jax.numpy as jnp

    w = draws(layers, stages, bu, seed)
    mm = jax.jit({"highest": _dot_highest, "high": _dot_bf16x3}[precision])
    vals: Dict[str, jax.Array] = {}
    for l in (layers[n] for stage in stages for n in stage):
        shape = (bu, l.H, 1, l.K)
        if l.kind == "add":
            y = sum(_fit(vals[p], shape) for p in l.preds)
        else:
            src = vals[l.preds[0]] if l.preds else jnp.asarray(
                w[f"{l.name}:x"])
            a = _fit(src, (bu * l.H, l.C))
            b = jnp.asarray(w[f"{l.name}:w"]) if l.kind == "fc" \
                else _fit(vals[l.preds[-1]], (l.C, l.K))
            y = (mm(a, b) / np.sqrt(l.C)).reshape(shape)
            if l.kind == "qk":
                y = jax.nn.softmax(y, axis=-1)
        vals[l.name] = y
    return {n: np.asarray(vals[n]) for n in exported(layers, stages)}


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """||got - want|| / ||want|| in float64; inf for a non-finite or
    mis-shaped answer."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    if g.shape != w.shape or not np.isfinite(g).all():
        return float("inf")
    return float(np.linalg.norm(g - w) / np.linalg.norm(w))
