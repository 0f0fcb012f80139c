"""Reference of the ``moe_lm`` configurations: a routed-expert decoder
(Phi-3.5-MoE), as the layer graph the cost model scores.

A configuration names this module by ``"reference": "moe_lm"``; its sizes
are the Hugging Face config's ``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``num_local_experts``,
``num_experts_per_tok`` and ``num_hidden_layers``, with ``seq`` and
``bytes_per_elem``.  It imports nothing of the program.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.reference.graphs import Graph, Layer

# the sizes the benchmark's CPU checks run this reference at
SMALL = {"num_hidden_layers": 1, "seq": 128, "batch": 8}


def build(c: Dict[str, Any]) -> Graph:
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
