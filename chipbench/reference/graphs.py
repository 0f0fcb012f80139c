"""Layer graphs of the benchmark's configurations, built from their sizes.

The plain reference of the cost model scores mappings on these graphs, not
on the graph the program builds: a layer the program gets wrong (a width,
an edge, an expected-traffic scale) then shows as a wrong score.  Layer
names are the program's naming, which is how a mapping refers to layers.

Each graph is the paper's IR: ``fc``/``matmul``/``eltwise`` layers with the
sequence as the ofmap height.  ``configs/<config>.json`` names the function
that builds its graph in ``workload.reference``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple, Union


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str
    K: int
    H: int = 1
    W: int = 1
    C: int = 0
    R: int = 1
    S: int = 1
    stride: int = 1
    groups: int = 1
    bytes_per_elem: int = 1
    n_inputs: int = 1
    traffic_scale: float = 1.0          # expected share a routed layer moves
    weight_traffic_scale: float = 1.0


@dataclass
class Graph:
    layers: Dict[str, Layer] = field(default_factory=dict)
    edges: List[Tuple[str, str]] = field(default_factory=list)
    edge_mults: Dict[Tuple[str, str], float] = field(default_factory=dict)

    def add(self, layer: Layer,
            inputs: Sequence[Union[str, Tuple[str, float]]] = ()) -> str:
        self.layers[layer.name] = layer
        for item in inputs:
            src, mult = item if isinstance(item, tuple) else (item, 1.0)
            self.edges.append((src, layer.name))
            if mult != 1.0:
                self.edge_mults[(src, layer.name)] = float(mult)
        return layer.name


def transformer(c: Dict[str, Any]) -> Graph:
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


def moe_lm(c: Dict[str, Any]) -> Graph:
    """A decoder with grouped-query attention and a routed-expert FFN in
    every block (Phi-3.5-MoE): fused QKV projection (query heads plus two
    sets of KV heads), scores and context over all query heads, output
    projection and residual add; then a router over the experts, each
    expert a gated up projection (2 x intermediate) and a down projection,
    and a combine of the ``top_k`` expected active experts with the
    residual.  An expert computes and moves ``top_k / n_experts`` of the
    dense volume, and reads that share of its inputs."""
    d, ff, seq = (int(c["hidden_size"]), int(c["intermediate_size"]),
                  int(c["seq"]))
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    n_exp, top_k = int(c["num_local_experts"]), int(c["num_experts_per_tok"])
    bpe = int(c["bytes_per_elem"])
    hd = d // heads
    frac = top_k / n_exp
    g = Graph()
    prev = None
    for i in range(int(c["num_hidden_layers"])):
        t = f"l{i}"
        src = [prev] if prev else []
        qkv = g.add(Layer(f"{t}_qkv", "fc", (heads + 2 * kv) * hd, seq, C=d,
                          bytes_per_elem=bpe), src)
        s = g.add(Layer(f"{t}_qk", "matmul", seq, seq, C=heads * hd,
                        bytes_per_elem=bpe), [qkv])
        a = g.add(Layer(f"{t}_av", "matmul", heads * hd, seq, C=seq,
                        bytes_per_elem=bpe), [s])
        o = g.add(Layer(f"{t}_o", "fc", d, seq, C=heads * hd,
                        bytes_per_elem=bpe), [a])
        a1 = g.add(Layer(f"{t}_add1", "eltwise", d, seq, n_inputs=2,
                         bytes_per_elem=bpe), [o] + src)
        router = g.add(Layer(f"{t}_router", "fc", n_exp, seq, C=d,
                             bytes_per_elem=bpe), [a1])
        downs = []
        for e in range(n_exp):
            up = g.add(Layer(f"{t}_e{e}_up", "fc", 2 * ff, seq, C=d,
                             bytes_per_elem=bpe, traffic_scale=frac),
                       [(a1, frac), (router, frac)])
            downs.append(g.add(Layer(f"{t}_e{e}_down", "fc", d, seq, C=ff,
                                     bytes_per_elem=bpe,
                                     traffic_scale=frac), [up]))
        prev = g.add(Layer(f"{t}_combine", "eltwise", d, seq,
                           n_inputs=top_k + 1, bytes_per_elem=bpe),
                     downs + [a1])
    return g


GRAPHS = {"transformer": transformer, "moe_lm": moe_lm}


def build(config: Dict[str, Any]) -> Graph:
    return GRAPHS[config["workload"]["reference"]](config)
