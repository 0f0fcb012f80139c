"""Layer graphs of the benchmark's configurations, built from their sizes.

The plain reference of the cost model scores mappings on these graphs, not
on the graph the program builds: a layer the program gets wrong (a width,
an edge, an expected-traffic scale) then shows as a wrong score.  Layer
names are the program's naming, which is how a mapping refers to layers.

Each graph is the paper's IR: ``fc``/``matmul``/``eltwise`` layers with the
sequence as the ofmap height.  ``configs/<config>.json`` names the module
that builds its graph in ``workload.reference``:
``reference/models/<name>.py``, whose ``build(config)`` returns a
:class:`Graph` of this file's :class:`Layer` objects.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple, Union


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

