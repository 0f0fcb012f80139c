"""Reference of the ``transformer`` configurations: the paper's encoder
stack (Vaswani et al.), as the layer graph the cost model scores and as the
layer list one realized pass computes.

A configuration names this module by ``"reference": "transformer"``; its
sizes are ``n_layers``, ``d_model``, ``d_ff``, ``seq`` and
``bytes_per_elem``.  It imports nothing of the program.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench.reference.graphs import Graph, Layer
from chipbench.reference.realized import RefLayer

# the sizes the benchmark's CPU checks run this reference at
SMALL = {"n_layers": 2, "d_model": 128, "d_ff": 256, "seq": 64, "batch": 8}


def build(c: Dict[str, Any]) -> Graph:
    """The paper's Transformer (Vaswani et al. encoder blocks): Q, K, V
    projections, scores Q K^T, context (scores) V, output projection,
    residual add, two-layer FFN, residual add."""
    d, ff, seq = int(c["d_model"]), int(c["d_ff"]), int(c["seq"])
    bpe = int(c["bytes_per_elem"])
    g = Graph()
    prev = None
    for i in range(int(c["n_layers"])):
        t = f"l{i}"
        src = [prev] if prev else []
        fc = lambda n, K, C, ins: g.add(  # noqa: E731
            Layer(f"{t}_{n}", "fc", K, seq, C=C, bytes_per_elem=bpe), ins)
        q, k, v = (fc(n, d, d, src) for n in ("q", "k", "v"))
        s = g.add(Layer(f"{t}_qk", "matmul", seq, seq, C=d,
                        bytes_per_elem=bpe), [q, k])
        a = g.add(Layer(f"{t}_av", "matmul", d, seq, C=seq,
                        bytes_per_elem=bpe), [s, v])
        o = fc("o", d, d, [a])
        a1 = g.add(Layer(f"{t}_add1", "eltwise", d, seq, n_inputs=2,
                         bytes_per_elem=bpe), [o] + src)
        f1 = fc("ff1", ff, d, [a1])
        f2 = fc("ff2", d, ff, [f1])
        prev = g.add(Layer(f"{t}_add2", "eltwise", d, seq, n_inputs=2,
                           bytes_per_elem=bpe), [f2, a1])
    return g


def realized_layers(c: Dict[str, Any]) -> List[RefLayer]:
    """The layers of one realized pass, in graph order."""
    n_layers, d_model = int(c["n_layers"]), int(c["d_model"])
    d_ff, seq = int(c["d_ff"]), int(c["seq"])
    out: List[RefLayer] = []
    prev: Tuple[str, ...] = ()
    for i in range(n_layers):
        t = f"l{i}"
        out += [RefLayer(f"{t}_q", "fc", seq, d_model, d_model, prev),
                RefLayer(f"{t}_k", "fc", seq, d_model, d_model, prev),
                RefLayer(f"{t}_v", "fc", seq, d_model, d_model, prev),
                RefLayer(f"{t}_qk", "qk", seq, d_model, seq,
                         (f"{t}_q", f"{t}_k")),
                RefLayer(f"{t}_av", "av", seq, seq, d_model,
                         (f"{t}_qk", f"{t}_v")),
                RefLayer(f"{t}_o", "fc", seq, d_model, d_model, (f"{t}_av",)),
                RefLayer(f"{t}_add1", "add", seq, 0, d_model,
                         (f"{t}_o",) + prev),
                RefLayer(f"{t}_ff1", "fc", seq, d_model, d_ff,
                         (f"{t}_add1",)),
                RefLayer(f"{t}_ff2", "fc", seq, d_ff, d_model, (f"{t}_ff1",)),
                RefLayer(f"{t}_add2", "add", seq, 0, d_model,
                         (f"{t}_ff2", f"{t}_add1"))]
        prev = (f"{t}_add2",)
    return out
