"""MeshPlan -> executable sharded JAX program (realization stage 2).

Each plan stage becomes one jit-compiled, sharded stage function:

* the stage **mesh** is the dominant layer's ``CG`` reshaped to its
  ``Part = (ph, pw, pb, pk)`` with axes ``("h", "w", "b", "k")`` — the
  Correspondence Rule's row-major (h, w, b, k) nesting IS the device
  order, so the realized placement matches the placement the analytical
  router priced;
* every layer's ofmap is materialized as the paper's 4-D cube
  ``(B, H, W, K)`` with ``PartitionSpec("b", "h", "w", "k")`` — the
  cube partitioning the ``Part`` describes;
* compute routes through the Pallas kernels of :mod:`repro.kernels`
  (auto mode: compiled on TPU, interpreted on CPU), each call run per
  device of the stage mesh under ``shard_map`` — the TPU compiler cannot
  partition a Pallas kernel by itself:
  ``fc``/``matmul`` -> the tiled GEMM, detected (qk, av) score/context
  pairs -> flash attention (scores never materialized, as on real TPU;
  a pair split across stages -> GEMM + softmax, then GEMM),
  ``*_ssd`` layers -> the chunked SSD kernel, eltwise -> VPU adds.
  ``use_pallas=False`` swaps in the jnp oracles of ``kernels/ref.py``
  (the parity target for tests);
* stage-to-stage activation hops are explicit ``device_put`` resharding
  onto the next stage's mesh — the realized analogue of the D2D/DCI
  transfers the evaluator priced (``runtime/pipeline.py`` is the
  microbatched production form of the same schedule).

Operand tensors whose producers live outside the stage arrive as program
inputs; where an abstract Gemini operand has no exact runtime tensor (a
matmul's weight-side activations, SSD's dt/B/C streams) it is derived
deterministically from the producer's output via ``jnp.resize`` — the MAC
count and operand sizes the cost model priced are preserved exactly, which
is what the measurement stage diffs against.

Expected-traffic graphs (routed MoE: ``graph.is_scaled``) lower to their
**dense-equivalent** programs: every expert branch executes its full cube
(fc layers take their first in-stage predecessor as the activation operand
— the dispatch/router edges are modeling-only — and a many-producer
combine eltwise sums all expert outputs, which is the dense execution of
the routed reduction).  The expected-traffic correction happens on the
measurement side (``measure.py`` dense-twin factors), not here; MLA graphs
are plain dense cubes and need nothing special.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.bridge import MeshPlan, StagePlan
from ..core.workload import Graph, Layer

STAGE_AXES = ("h", "w", "b", "k")
# cube dim order (B, H, W, K) -> mesh axis carrying it
CUBE_DIM_AXES = ("b", "h", "w", "k")


def cube_spec_for(shape: Tuple[int, ...], mesh: Mesh,
                  dim_axes: Tuple[Optional[str], ...] = CUBE_DIM_AXES) -> P:
    """PartitionSpec for ``shape`` on ``mesh``, sharding only dims the mesh
    axis divides evenly (jit argument shardings require divisibility; an
    indivisible dim is replicated, mirroring the analytical model's
    approximately-equal ``split_points`` with the remainder broadcast)."""
    spec = []
    for dim, ax in zip(shape, dim_axes):
        n = mesh.shape[ax] if ax is not None else 1
        spec.append(ax if ax is not None and n > 1 and dim % n == 0
                    else None)
    return P(*spec)


def _fit(x: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """Deterministic tile/truncate of ``x`` onto ``shape`` (jnp.resize).

    Bridges abstract Gemini operands to concrete runtime tensors without
    changing the contraction sizes the cost model priced."""
    return jnp.resize(x.astype(jnp.float32), shape)


def _cube(layer: Layer, bu: int) -> Tuple[int, int, int, int]:
    return (bu, layer.H, layer.W, layer.K)


def _heads_for(d: int) -> Tuple[int, int]:
    """(heads, head_dim) factorization of a model width for the MXU kernels."""
    for hd in (128, 64, 32):
        if d % hd == 0:
            return d // hd, hd
    return 1, d


def _per_device(fn, mesh: Mesh, args: Sequence[jax.Array],
                dims: Sequence[Optional[int]]) -> jax.Array:
    """``fn(*args)`` run once per device of ``mesh`` under ``shard_map``.

    ``dims[i]`` names the dim of ``args[i]`` split across every device of
    the mesh (``None`` keeps the argument whole on each device); the
    result is split along its leading dim.  When any of those dims does
    not divide evenly, every device computes the whole call instead."""
    n = mesh.devices.size
    split = all(d is None or a.shape[d] % n == 0 for a, d in zip(args, dims))

    def spec(ndim: int, d: Optional[int]) -> P:
        if not split or d is None:
            return P()
        return P(*(STAGE_AXES if i == d else None for i in range(ndim)))

    out_ndim = jax.eval_shape(fn, *args).ndim
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(a.ndim, d) for a, d in zip(args, dims)),
        out_specs=spec(out_ndim, 0), check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Kernel routing
# ---------------------------------------------------------------------------

def _route_layers(g: Graph, st: StagePlan) -> Dict[str, str]:
    """layer -> route tag.  Attention (qk, av) pairs fuse into one flash
    call at the av layer's position when the scores layer has no other
    consumer (flash never materializes the score matrix, so another reader
    would see nothing).  A pair split across two stages routes its scores
    layer as ``scores``: the GEMM followed by the softmax flash would have
    applied, so the av layer consumes probabilities either way (without
    it, activations square at every attention and overflow f32 a few
    layers deep)."""
    routes: Dict[str, str] = {}
    in_stage = set(st.layers)
    for name in st.layers:
        lyr = g.layers[name]
        if lyr.kind == "eltwise":
            routes[name] = "add"
        elif lyr.kind in ("pool", "depthwise"):
            routes[name] = "jnp"
        elif lyr.kind == "matmul" and name.endswith("_ssd"):
            routes[name] = "ssd"
        else:
            routes[name] = "matmul"
    for name in st.layers:
        lyr = g.layers[name]
        if lyr.kind != "matmul" or lyr.K != lyr.H:
            continue                       # not a square score matrix
        succs = g.succs(name)
        if routes[name] != "matmul" or len(succs) != 1:
            continue
        av = succs[0]
        av_l = g.layers[av]
        if av_l.kind != "matmul" or av_l.C != lyr.K:
            continue                       # consumer doesn't contract scores
        if av in in_stage:
            routes[name] = f"flash-scores:{av}"
            routes[av] = f"flash:{name}"
        else:
            routes[name] = "scores"
    return routes


# ---------------------------------------------------------------------------
# Stage programs
# ---------------------------------------------------------------------------

@dataclass
class StageProgram:
    index: int
    stage: StagePlan
    mesh: Mesh
    routes: Dict[str, str]
    ext_inputs: Tuple[str, ...]        # producer layers feeding this stage
    src_inputs: Tuple[str, ...]        # graph-input layers synthesized here
    out_layers: Tuple[str, ...]        # cubes later stages / callers need
    jfn: Any = None                    # jitted stage function
    arg_structs: List[Any] = field(default_factory=list)
    in_shardings: List[Any] = field(default_factory=list)
    compiled: Any = None
    compile_s: float = 0.0

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def lower_and_compile(self) -> Any:
        t0 = time.time()
        self.compiled = self.jfn.lower(*self.arg_structs).compile()
        self.compile_s = time.time() - t0
        return self.compiled


@dataclass
class RealizedProgram:
    graph: Graph
    plan: MeshPlan
    stages: List[StageProgram]
    batch_unit: int
    interpret: Optional[bool]

    def compile_all(self) -> None:
        for sp in self.stages:
            sp.lower_and_compile()

    def execute(self, seed: int = 0) -> Dict[str, Any]:
        """Run the pipeline once (one batch-unit pass).

        Returns per-stage wall seconds, the DCI bytes moved between stage
        meshes, and every stage's exported cubes (``out_layers``)."""
        rng = np.random.default_rng(seed)
        outputs: Dict[str, jax.Array] = {}
        wall: List[float] = []
        dci_bytes: List[float] = []
        for sp in self.stages:
            args = []
            moved = 0.0
            for i, name in enumerate(sp.ext_inputs):
                x = outputs[name]
                shd = sp.in_shardings[i]
                # an already-identically-sharded cube (adjacent stages on
                # one device set) moves nothing — don't bill it as DCI
                if not x.sharding.is_equivalent_to(shd, x.ndim):
                    moved += x.size * x.dtype.itemsize
                args.append(jax.device_put(x, shd))
            # source ifmaps + weights: synthesized deterministically
            for struct, shd in zip(sp.arg_structs[len(sp.ext_inputs):],
                                   sp.in_shardings[len(sp.ext_inputs):]):
                a = rng.normal(size=struct.shape).astype(struct.dtype)
                args.append(jax.device_put(jnp.asarray(a), shd))
            fn = sp.compiled if sp.compiled is not None else sp.jfn
            t0 = time.time()
            outs = fn(*args)
            jax.block_until_ready(outs)
            wall.append(time.time() - t0)
            dci_bytes.append(moved)
            outputs.update(zip(sp.out_layers, outs))
        return {"wall_s": wall, "dci_bytes": dci_bytes, "outputs": outputs}


def _stage_mesh(st: StagePlan, devices: Sequence) -> Mesh:
    dom = st.dominant_layer()
    ph, pw, pb, pk = st.parts[dom]
    cg = st.cgs[dom]
    devs = np.asarray([devices[c] for c in cg], dtype=object)
    return Mesh(devs.reshape(ph, pw, pb, pk), STAGE_AXES)


def build_program(g: Graph, plan: MeshPlan, devices: Optional[Sequence] = None,
                  interpret: Optional[bool] = None,
                  use_pallas: bool = True) -> RealizedProgram:
    """Compile-ready realization of ``plan`` over ``devices``.

    ``devices`` defaults to ``jax.devices()``; Gemini core id ``c`` maps to
    ``devices[c]`` (the plan must already be validated against the pool —
    see ``realize.plan.validate_plan``).  ``interpret=None`` lets the
    kernels auto-select (interpret off-TPU).  ``use_pallas=False`` routes
    through the jnp oracles instead — same program structure, reference
    numerics (the parity target)."""
    from ..kernels import ops, ref

    devices = list(devices) if devices is not None else jax.devices()
    bu = plan.batch_unit
    stage_of: Dict[str, int] = {}
    for i, st in enumerate(plan.stages):
        for n in st.layers:
            stage_of[n] = i

    stages: List[StageProgram] = []
    for si, st in enumerate(plan.stages):
        routes = _route_layers(g, st)
        in_stage = set(st.layers)
        ext: List[str] = []
        src: List[str] = []
        for name in st.layers:
            for p in g.preds(name):
                if p not in in_stage and p not in ext:
                    if stage_of.get(p, si) >= si:
                        raise ValueError(
                            f"stage {si} layer {name} depends on {p} of a "
                            f"later stage — plan stages are not topological")
                    ext.append(p)
            if not g.preds(name):
                src.append(name)
        # outputs: cubes needed by later stages, plus graph outputs
        outs = [n for n in st.layers
                if any(stage_of.get(s2, -1) > si for s2 in g.succs(n))
                or not g.succs(n)]
        mesh = _stage_mesh(st, devices)
        sp = StageProgram(index=si, stage=st, mesh=mesh, routes=routes,
                          ext_inputs=tuple(ext), src_inputs=tuple(src),
                          out_layers=tuple(outs))

        def shd(shape: Tuple[int, ...],
                dim_axes: Tuple[Optional[str], ...] = CUBE_DIM_AXES
                ) -> NamedSharding:
            return sp.sharding(cube_spec_for(shape, mesh, dim_axes))

        # per-layer output cube shardings (the Part-derived constraint)
        lay_shd = {name: shd(_cube(g.layers[name], bu))
                   for name in st.layers}

        # argument structs: ext cubes, then source-layer ifmaps, then weights
        arg_structs: List[jax.ShapeDtypeStruct] = []
        in_shardings: List[NamedSharding] = []
        for name in ext:
            shape = _cube(g.layers[name], bu)
            arg_structs.append(jax.ShapeDtypeStruct(shape, jnp.float32))
            in_shardings.append(shd(shape))
        for name in src:
            lyr = g.layers[name]
            cin = max(lyr.C, 1) if lyr.kind in ("conv", "fc", "matmul") \
                else lyr.K
            shape = (bu, lyr.H * lyr.stride, lyr.W * lyr.stride, cin)
            arg_structs.append(jax.ShapeDtypeStruct(shape, jnp.float32))
            in_shardings.append(shd(shape))
        weighted = [n for n in st.layers if g.layers[n].has_weight]
        for name in weighted:
            lyr = g.layers[name]
            cin = max(1, (lyr.C // lyr.groups)) * lyr.R * lyr.S
            arg_structs.append(jax.ShapeDtypeStruct((cin, lyr.K),
                                                    jnp.float32))
            in_shardings.append(shd((cin, lyr.K), (None, "k")))

        def stage_fn(*args, _st=st, _routes=routes, _ext=tuple(ext),
                     _src=tuple(src), _weighted=tuple(weighted),
                     _outs=tuple(outs), _lay_shd=lay_shd, _mesh=mesh):
            vals: Dict[str, jax.Array] = {}
            na, ns = len(_ext), len(_src)
            for i2, name in enumerate(_ext):
                vals[name] = args[i2]
            srcs = {name: args[na + i2] for i2, name in enumerate(_src)}
            wts = {name: args[na + ns + i2]
                   for i2, name in enumerate(_weighted)}

            def operand(name: str, lyr: Layer) -> jax.Array:
                """The layer's activation operand, from preds or source."""
                preds = [p for p in g.preds(name) if p in vals]
                if preds:
                    return vals[preds[0]]
                return srcs[name]

            def mm(a2: jax.Array, b2: jax.Array) -> jax.Array:
                if use_pallas:
                    return _per_device(
                        lambda a, b: ops.matmul(a, b, interpret=interpret),
                        _mesh, (a2, b2), (0, None))
                return ref.matmul_ref(a2, b2)

            for name in _st.layers:
                lyr = g.layers[name]
                route = _routes[name]
                shape = _cube(lyr, bu)
                if route.startswith("flash-scores:"):
                    continue            # materialized inside the av layer
                if route.startswith("flash:"):
                    qk = route.split(":", 1)[1]
                    qk_l = g.layers[qk]
                    S = qk_l.H
                    heads, hd = _heads_for(lyr.K)
                    qk_preds = [p for p in g.preds(qk) if p in vals] \
                        or [qk]
                    q_src = vals.get(qk_preds[0], srcs.get(qk))
                    k_src = vals.get(qk_preds[-1], q_src)
                    v_pr = [p for p in g.preds(name)
                            if p != qk and p in vals]
                    v_src = vals[v_pr[0]] if v_pr else k_src
                    q = _fit(q_src, (bu, S, heads, hd))
                    k = _fit(k_src, (bu, S, heads, hd))
                    v = _fit(v_src, (bu, S, heads, hd))
                    if use_pallas:
                        o = _per_device(
                            lambda q, k, v: ops.flash_attention(
                                q, k, v, interpret=interpret,
                                bq=min(512, S), bk=min(512, S)),
                            _mesh, (q, k, v), (0, 0, 0))
                    else:
                        t = lambda x: x.transpose(0, 2, 1, 3)
                        o = t(ref.attention_ref(t(q), t(k), t(v)))
                    out = o.reshape(bu, S, 1, heads * hd)
                    out = _fit(out, shape) if out.shape != shape else out
                elif route == "ssd":
                    heads, hd = _heads_for(lyr.K)
                    S = lyr.H
                    a_in = operand(name, lyr)
                    x = _fit(a_in, (bu, S, heads, hd))
                    dt = jax.nn.softplus(_fit(a_in, (bu, S, heads)) * 0.1)
                    A = -0.5 * jnp.ones((heads,), jnp.float32)
                    N = max(16, min(64, lyr.C))
                    Bm = _fit(a_in, (bu, S, 1, N)) * 0.1
                    Cm = _fit(a_in * 0.5 + 1.0, (bu, S, 1, N)) * 0.1
                    y = _per_device(
                        lambda x, dt, A, Bm, Cm: ops.ssd_forward(
                            x, dt, A, Bm, Cm, chunk=min(128, S),
                            interpret=interpret)[0],
                        _mesh, (x, dt, A, Bm, Cm), (0, 0, None, 0, 0))
                    out = y.reshape(bu, S, 1, heads * hd)
                    out = _fit(out, shape) if out.shape != shape else out
                elif route in ("matmul", "scores"):
                    a2 = _fit(operand(name, lyr),
                              (bu * lyr.H * lyr.W, max(lyr.C, 1)))
                    if lyr.has_weight:
                        b2 = wts[name]
                    else:
                        preds = [p for p in g.preds(name) if p in vals]
                        b_src = vals[preds[-1]] if preds else a2
                        b2 = _fit(b_src, (max(lyr.C, 1), lyr.K))
                    out = mm(a2, b2).reshape(shape) / np.sqrt(max(lyr.C, 1))
                    if route == "scores":
                        out = jax.nn.softmax(out, axis=-1)
                elif route == "add":
                    preds = [p for p in g.preds(name) if p in vals]
                    if preds:
                        out = sum(_fit(vals[p], shape) for p in preds)
                    else:
                        out = _fit(srcs[name], shape)
                else:  # "jnp": pool / depthwise — VPU-style reduction
                    out = _fit(operand(name, lyr), shape) \
                        / (lyr.R * lyr.S)
                vals[name] = jax.lax.with_sharding_constraint(
                    out.astype(jnp.float32), _lay_shd[name])
            return tuple(vals[n] for n in _outs)

        sp.jfn = jax.jit(stage_fn,
                         in_shardings=tuple(in_shardings),
                         out_shardings=tuple(lay_shd[n] for n in outs))
        sp.arg_structs = arg_structs
        sp.in_shardings = in_shardings
        stages.append(sp)
    return RealizedProgram(graph=g, plan=plan, stages=stages,
                           batch_unit=bu, interpret=interpret)
