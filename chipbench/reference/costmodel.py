"""Plain reference of the Gemini cost model (delay and energy of a mapping).

A copy of the repository's seed evaluation engine (the scalar, per-region
loops that the vectorized engine is pinned to bit for bit), written so that
it imports nothing of the program under test.  Its inputs are data: the
architecture point as the traffic mix states it, the configuration's
technology constants, the layer graph that ``graphs.py`` builds from the
configuration's sizes, and the mapping under test (layer groups, Parts,
core groups and DRAM endpoints).  Every quantity derived from them (router
grid, XY paths, layer MACs and bytes, part regions, intra-core dataflows,
traffic, delay, energy) is computed here.

``dtype`` is the precision of the analysis accumulators and of the delay and
energy arithmetic.  ``np.float64`` is the configuration's stated precision
(the exact engine); a lower one (``np.float32``, ``ml_dtypes.bfloat16``)
makes this module the *control* of the comparison that decides ``correct``.

Expected traffic (routed experts): a layer's ``traffic_scale`` multiplies
what it computes and moves per token (MACs, compute time, GLB traffic and
fmap footprint, ifmap and ofmap DRAM flows), its ``weight_traffic_scale``
its weight loads, and a dependency edge carries the producer's
``traffic_scale`` times the edge's multiplicity (``edge_mults``).  Each
multiplies the dense quantity once it is formed, as the cost model states
it; at 1.0 everything is the dense value.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Sequence, Tuple

import numpy as np

_PSUM_BYTES = 4
_ORDERS = ("ws", "os", "is")


# ---------------------------------------------------------------------------
# layers and architecture (data in, derived quantities computed here)
# ---------------------------------------------------------------------------

def _has_weight(lyr) -> bool:
    return lyr.kind in ("conv", "fc", "depthwise")


def _ofmap_elems(lyr) -> int:
    return lyr.K * lyr.H * lyr.W


def _weight_bytes(lyr) -> int:
    if lyr.kind == "conv":
        e = lyr.K * (lyr.C // lyr.groups) * lyr.R * lyr.S
    elif lyr.kind == "fc":
        e = lyr.K * lyr.C
    elif lyr.kind == "depthwise":
        e = lyr.K * lyr.R * lyr.S
    else:
        e = 0
    return e * lyr.bytes_per_elem


def layer_macs(lyr) -> int:
    """Multiply-accumulates of one sample of a layer (dense)."""
    if lyr.kind == "conv":
        return lyr.K * lyr.H * lyr.W * (lyr.C // lyr.groups) * lyr.R * lyr.S
    if lyr.kind == "fc":
        return lyr.K * lyr.H * lyr.W * lyr.C
    if lyr.kind == "matmul":
        return lyr.H * lyr.K * lyr.C
    if lyr.kind == "depthwise":
        return lyr.K * lyr.H * lyr.W * lyr.R * lyr.S
    if lyr.kind == "pool":
        return lyr.K * lyr.H * lyr.W * lyr.stride * lyr.stride
    return _ofmap_elems(lyr) * lyr.n_inputs          # eltwise


class _Arch:
    """Geometry of an architecture point, with the technology constants
    (``freq_ghz``, ``n_dram`` and the energies ``e_*`` in joules)."""

    def __init__(self, point: Dict, tech: Dict):
        self.x, self.y = int(point["x_cores"]), int(point["y_cores"])
        self.xcut, self.ycut = int(point["xcut"]), int(point["ycut"])
        self.n_cores = self.x * self.y
        self.n_dram = int(tech["n_dram"])
        self.noc_bw, self.d2d_bw, self.dram_bw = (
            float(point["noc_bw"]), float(point["d2d_bw"]),
            float(point["dram_bw"]))
        self.glb_bytes = int(point["glb_kb"]) * 1024
        self.macs_per_core = int(point["macs_per_core"])
        self.freq_ghz = float(tech["freq_ghz"])
        self.tech = tech
        self.gw, self.gh = self.x + 2, self.y

    def core_node(self, c: int) -> int:
        y, x = divmod(c, self.x)
        return y * self.gw + (x + 1)

    def dram_node(self, dram_id: int) -> int:
        d = dram_id - 1
        side = d % 2
        row = (d // 2) * max(1, self.y // max(1, (self.n_dram + 1) // 2))
        row = min(row, self.y - 1)
        return row * self.gw + (0 if side == 0 else self.gw - 1)

    def node_chiplet(self, node: int) -> int:
        y, x = divmod(node, self.gw)
        if x == 0:
            return -1
        if x == self.gw - 1:
            return -2
        cw, ch = self.x // self.xcut, self.y // self.ycut
        return (y // ch) * self.xcut + ((x - 1) // cw)


@dataclass(frozen=True)
class _Grid:
    n_edges: int
    edge_is_d2d: np.ndarray
    paths: np.ndarray


def _build_grid(a: _Arch) -> _Grid:
    gw, gh = a.gw, a.gh
    n_nodes = gw * gh
    n_h = (gw - 1) * gh
    n_v = gw * (gh - 1)
    n_edges = 2 * n_h + 2 * n_v

    def east_id(x, y):  return y * (gw - 1) + x
    def west_id(x, y):  return n_h + y * (gw - 1) + (x - 1)
    def south_id(x, y): return 2 * n_h + y * gw + x
    def north_id(x, y): return 2 * n_h + n_v + (y - 1) * gw + x

    is_d2d = np.zeros(n_edges, dtype=bool)
    for y in range(gh):
        for x in range(gw - 1):
            d2d = a.node_chiplet(y * gw + x) != a.node_chiplet(y * gw + x + 1)
            is_d2d[east_id(x, y)] = d2d
            is_d2d[west_id(x + 1, y)] = d2d
    for y in range(gh - 1):
        for x in range(gw):
            d2d = a.node_chiplet(y * gw + x) != a.node_chiplet((y + 1) * gw + x)
            is_d2d[south_id(x, y)] = d2d
            is_d2d[north_id(x, y + 1)] = d2d
    max_len = (gw - 1) + (gh - 1)
    paths = np.full((n_nodes, n_nodes, max(max_len, 1)), -1, dtype=np.int64)
    for s in range(n_nodes):
        sy, sx = divmod(s, gw)
        for d in range(n_nodes):
            if s == d:
                continue
            dy, dx = divmod(d, gw)
            e: List[int] = []
            x, y = sx, sy
            while x < dx:
                e.append(east_id(x, y)); x += 1
            while x > dx:
                e.append(west_id(x, y)); x -= 1
            while y < dy:
                e.append(south_id(x, y)); y += 1
            while y > dy:
                e.append(north_id(x, y)); y -= 1
            paths[s, d, :len(e)] = e
    return _Grid(n_edges, is_d2d, paths)


# ---------------------------------------------------------------------------
# regions (the Correspondence Rule)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Region:
    h0: int; h1: int
    w0: int; w1: int
    b0: int; b1: int
    k0: int; k1: int

    @property
    def elems(self) -> int:
        return ((self.h1 - self.h0) * (self.w1 - self.w0)
                * (self.b1 - self.b0) * (self.k1 - self.k0))


def _split_points(dim: int, parts: int) -> np.ndarray:
    if parts > dim:
        raise ValueError(f"cannot split dim {dim} into {parts} parts")
    base, extra = divmod(dim, parts)
    sizes = [base + (1 if i < extra else 0) for i in range(parts)]
    return np.concatenate([[0], np.cumsum(sizes)])


def _parse_regions(ms, lyr, bu: int) -> Dict[int, Region]:
    ph, pw, pb, pk = ms.part
    hs, ws = _split_points(lyr.H, ph), _split_points(lyr.W, pw)
    bs, ks = _split_points(bu, pb), _split_points(lyr.K, pk)
    ih, iw, ib, ik = np.indices((ph, pw, pb, pk)).reshape(4, -1)
    rows = np.stack([hs[ih], hs[ih + 1], ws[iw], ws[iw + 1],
                     bs[ib], bs[ib + 1], ks[ik], ks[ik + 1]], axis=1)
    return {int(c): Region(*row) for c, row in zip(ms.cg, rows.tolist())}


def _ifmap_region(lyr, r: Region, in_K: int) -> Region:
    if lyr.kind == "eltwise":
        return r
    s = lyr.stride
    if lyr.kind in ("pool", "depthwise"):
        return Region(r.h0 * s, min(r.h1 * s + lyr.R - 1, lyr.H * s),
                      r.w0 * s, min(r.w1 * s + lyr.S - 1, lyr.W * s),
                      r.b0, r.b1, r.k0, r.k1)
    h_in, w_in = lyr.H * s, lyr.W * s
    return Region(min(r.h0 * s, h_in - 1), min(r.h1 * s + lyr.R - 1, h_in),
                  min(r.w0 * s, w_in - 1), min(r.w1 * s + lyr.S - 1, w_in),
                  r.b0, r.b1, 0, in_K)


def _regions_to_array(regions: Dict[int, Region]):
    cores = np.array(sorted(regions), dtype=np.int64)
    arr = np.array([[regions[c].h0, regions[c].h1, regions[c].w0,
                     regions[c].w1, regions[c].b0, regions[c].b1,
                     regions[c].k0, regions[c].k1] for c in cores],
                   dtype=np.int64)
    return cores, arr


def _overlap_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lo = np.maximum(a[:, None, 0::2], b[None, :, 0::2])
    hi = np.minimum(a[:, None, 1::2], b[None, :, 1::2])
    d = np.clip(hi - lo, 0, None)
    return d[..., 0] * d[..., 1] * d[..., 2] * d[..., 3]


# ---------------------------------------------------------------------------
# intra-core dataflow (NVDLA-style tiling search, scalar form)
# ---------------------------------------------------------------------------

def _pow2_tiles(dim: int, cap: int) -> Tuple[int, ...]:
    out = []
    t = 1
    while t < min(dim, cap):
        out.append(t)
        t *= 2
    out.append(min(dim, cap))
    return tuple(sorted(set(out)))


@lru_cache(maxsize=200_000)
def intra_core(K: int, C: int, HW: int, R: int, S: int, bpe: int,
               glb_bytes: int, macs_per_core: int, kind: str
               ) -> Tuple[float, float, float]:
    """(GLB read bytes, GLB write bytes, MAC utilization) of the cheapest
    tiling of one core's share of a layer."""
    kvec = 16
    cvec = max(1, macs_per_core // kvec)
    if kind in ("eltwise", "pool", "depthwise"):
        vol = K * HW * bpe
        return float(vol * (2 if kind == "eltwise" else 1)), float(vol), 1.0
    C_eff = max(1, C)
    w_elems = K * C_eff * R * S if kind in ("conv", "fc") else 0
    if_elems = C_eff * HW * (R * S if kind == "conv" else 1)
    of_elems = K * HW
    best = None
    for tk in _pow2_tiles(K, 512):
        for tc in _pow2_tiles(C_eff, 512):
            for thw in _pow2_tiles(HW, 4096):
                buf = (tk * tc * R * S * bpe + tc * thw * bpe * 2
                       + tk * thw * _PSUM_BYTES)
                if buf > glb_bytes:
                    continue
                nk, nc, nhw = -(-K // tk), -(-C_eff // tc), -(-HW // thw)
                for order in _ORDERS:
                    if order == "ws":
                        rd = (w_elems * 1.0 + if_elems * nk) * bpe \
                            + of_elems * (nc - 1) * _PSUM_BYTES
                        wr = of_elems * nc * _PSUM_BYTES
                    elif order == "os":
                        rd = (w_elems * nhw + if_elems * nk) * bpe
                        wr = of_elems * _PSUM_BYTES
                    else:
                        rd = (w_elems * nhw + if_elems * 1.0) * bpe \
                            + of_elems * (nc - 1) * _PSUM_BYTES
                        wr = of_elems * nc * _PSUM_BYTES
                    uk = K / (-(-K // kvec) * kvec)
                    uc = C_eff / (-(-C_eff // cvec) * cvec)
                    if best is None or rd + wr < best[0] + best[1]:
                        best = (rd, wr, uk * uc)
    if best is None:                      # nothing fits: spill
        rd = (w_elems * HW + if_elems * K) * bpe
        wr = of_elems * C_eff * _PSUM_BYTES
        return float(rd), float(wr), 1.0 / (kvec * cvec)
    return best


# ---------------------------------------------------------------------------
# the evaluator
# ---------------------------------------------------------------------------

@dataclass
class GroupScore:
    delay_s: float
    energy_j: float


class CostModel:
    """Delay and energy of (layer group, LMS) pairs on one architecture."""

    def __init__(self, point: Dict, tech: Dict, g, dtype=np.float64):
        self.a = _Arch(point, tech)
        self.g = g
        self.dt = dtype
        self.grid = _build_grid(self.a)
        self._preds = {n: [s for s, d in g.edges if d == n] for n in g.layers}
        self._ts = {n: l.traffic_scale for n, l in g.layers.items()}
        self._ws = {n: l.weight_traffic_scale for n, l in g.layers.items()}
        self._mult = g.edge_mults
        self._core_nodes = np.array(
            [self.a.core_node(c) for c in range(self.a.n_cores)], np.int64)
        self._dram_nodes = np.array(
            [self.a.dram_node(d) for d in range(1, self.a.n_dram + 1)],
            np.int64)

    # -- routing -------------------------------------------------------------
    def _route(self, edge_bytes, src, dst, vols) -> None:
        mask = vols > 0
        if not mask.any():
            return
        s, d, v = src[mask], dst[mask], vols[mask]
        paths = self.grid.paths[s, d]
        flat = paths.reshape(-1)
        keep = flat >= 0
        np.add.at(edge_bytes, flat[keep],
                  np.repeat(v, paths.shape[1])[keep].astype(self.dt))

    def _route_multicast(self, edge_bytes, src_node, dst_nodes, vol) -> None:
        if vol <= 0 or not len(dst_nodes):
            return
        paths = self.grid.paths[src_node, np.asarray(dst_nodes, np.int64)]
        edges = np.unique(paths[paths >= 0])
        edge_bytes[edges] += self.dt(vol)

    def _dram_flow(self, edge_bytes, dram_bytes, fd, nodes, vols,
                   to_core) -> None:
        vols = np.asarray(vols, dtype=float)
        if np.ndim(vols) == 0:
            vols = np.full(len(nodes), float(vols))
        if fd == 0:
            share = vols / self.a.n_dram
            for d in range(self.a.n_dram):
                dn = np.full(len(nodes), self._dram_nodes[d])
                if to_core:
                    self._route(edge_bytes, dn, nodes, share)
                else:
                    self._route(edge_bytes, nodes, dn, share)
                dram_bytes[d] += self.dt(float(share.sum()))
        else:
            dn = np.full(len(nodes), self._dram_nodes[fd - 1])
            if to_core:
                self._route(edge_bytes, dn, nodes, vols)
            else:
                self._route(edge_bytes, nodes, dn, vols)
            dram_bytes[fd - 1] += self.dt(float(vols.sum()))

    def _external_ifmap_bytes(self, lyr, rarr, bu):
        s = lyr.stride
        dh = (rarr[:, 1] - rarr[:, 0]) * s + (lyr.R - 1)
        dw = (rarr[:, 3] - rarr[:, 2]) * s + (lyr.S - 1)
        db = rarr[:, 5] - rarr[:, 4]
        if lyr.kind in ("eltwise", "pool", "depthwise"):
            dk = (rarr[:, 7] - rarr[:, 6]) * (
                lyr.n_inputs if lyr.kind == "eltwise" else 1)
        elif lyr.kind == "matmul":
            return (rarr[:, 1] - rarr[:, 0]) * db * lyr.C \
                + (rarr[:, 7] - rarr[:, 6]) * db * lyr.C
        else:
            dk = np.full(len(rarr), max(1, lyr.C), dtype=np.int64)
        return dh * dw * db * dk

    def _dep_traffic(self, edge_bytes, core_in, core_out, prod, prod_regs,
                     cons, cons_regs) -> None:
        p_cores, p_arr = _regions_to_array(prod_regs)
        c_cores, c_arr = _regions_to_array(cons_regs)
        bpe = prod.bytes_per_elem
        escale = self._ts[prod.name] * self._mult.get((prod.name, cons.name),
                                                      1.0)
        need = np.empty_like(c_arr)
        for i, cc in enumerate(c_cores):
            nr = _ifmap_region(cons, cons_regs[cc], prod.K)
            need[i] = [nr.h0, nr.h1, nr.w0, nr.w1, nr.b0, nr.b1, nr.k0, nr.k1]
        ov = _overlap_matrix(p_arr, need)
        if not ov.any():
            return
        p_nodes = self._core_nodes[p_cores]
        c_nodes = self._core_nodes[c_cores]
        if cons.kind in ("conv", "fc", "matmul"):
            groups: Dict[Tuple, List[int]] = {}
            for qi, row in enumerate(need):
                groups.setdefault(tuple(row), []).append(qi)
            for qis in groups.values():
                vols = ov[:, qis[0]].astype(float) * bpe * escale
                for pi in np.nonzero(vols)[0]:
                    dsts = [int(c_nodes[q]) for q in qis
                            if c_nodes[q] != p_nodes[pi]]
                    self._route_multicast(edge_bytes, int(p_nodes[pi]),
                                          dsts, float(vols[pi]))
                    core_out[p_cores[pi]] += self.dt(
                        vols[pi] * (1 if dsts else 0))
                    for q in qis:
                        if c_nodes[q] != p_nodes[pi]:
                            core_in[c_cores[q]] += self.dt(vols[pi])
        else:
            vols = ov.astype(float) * bpe * escale
            same = p_nodes[:, None] == c_nodes[None, :]
            vols_off = np.where(same, 0.0, vols)
            P, Q = vols.shape
            self._route(edge_bytes, np.repeat(p_nodes, Q),
                        np.tile(c_nodes, P), vols_off.reshape(-1))
            np.add.at(core_out, p_cores, vols_off.sum(axis=1).astype(self.dt))
            np.add.at(core_in, c_cores, vols_off.sum(axis=0).astype(self.dt))

    # -- one group -------------------------------------------------------------
    def _analyze(self, group, lms, total_batch):
        a, g, dt = self.a, self.g, self.dt
        bu = group.batch_unit
        n_passes = max(1, -(-total_batch // bu))
        in_group = set(group.names)
        z = lambda n: np.zeros(n, dtype=dt)
        core_macs, glb_need = z(a.n_cores), z(a.n_cores)
        core_in, core_out = z(a.n_cores), z(a.n_cores)
        edge_bytes, edge_amort = z(self.grid.n_edges), z(self.grid.n_edges)
        dram_bytes, dram_amort = z(a.n_dram), z(a.n_dram)
        weight_total = 0.0
        regions_of = {n: _parse_regions(lms.ms[n], g.layers[n], bu)
                      for n in group.names}
        for name in group.names:
            lyr = g.layers[name]
            ms = lms.ms[name]
            regs = regions_of[name]
            cores, rarr = _regions_to_array(regs)
            nodes = self._core_nodes[cores]
            bpe = lyr.bytes_per_elem
            ts, ws = self._ts[name], self._ws[name]
            elems = (rarr[:, 1] - rarr[:, 0]) * (rarr[:, 3] - rarr[:, 2]) \
                * (rarr[:, 5] - rarr[:, 4]) * (rarr[:, 7] - rarr[:, 6])
            mac_per_elem = layer_macs(lyr) / max(1, _ofmap_elems(lyr))
            np.add.at(core_macs, cores,
                      (elems * mac_per_elem * ts).astype(dt))
            w_share = _weight_bytes(lyr) / max(1, ms.part[3]) \
                if _has_weight(lyr) else 0
            np.add.at(glb_need, cores,
                      (elems * bpe * 2 * ts + w_share).astype(dt))
            if _has_weight(lyr):
                k_span = rarr[:, 7] - rarr[:, 6]
                w_core = k_span / max(1, lyr.K) * _weight_bytes(lyr) * ws
                weight_total += float(w_core.sum())
                self._dram_flow(edge_amort, dram_amort, ms.fd[1], nodes,
                                w_core / n_passes, to_core=True)
            preds = self._preds[name]
            internal = [p for p in preds if p in in_group]
            external = (not preds) or any(p not in in_group for p in preds)
            for p in internal:
                self._dep_traffic(edge_bytes, core_in, core_out,
                                  g.layers[p], regions_of[p], lyr, regs)
            if external and ms.fd[0] >= 0:
                if_bytes = self._external_ifmap_bytes(lyr, rarr, bu) * bpe * ts
                self._dram_flow(edge_bytes, dram_bytes, ms.fd[0], nodes,
                                if_bytes, to_core=True)
                np.add.at(core_in, cores, if_bytes.astype(dt))
            if ms.fd[2] >= 0:
                of_bytes = elems * bpe * ts
                self._dram_flow(edge_bytes, dram_bytes, ms.fd[2], nodes,
                                of_bytes.astype(float), to_core=False)
                np.add.at(core_out, cores, of_bytes.astype(dt))
        return dict(core_macs=core_macs, edge_bytes=edge_bytes,
                    edge_amort=edge_amort, dram_bytes=dram_bytes,
                    dram_amort=dram_amort, glb_need=glb_need,
                    core_in=core_in, weight_total=dt(weight_total),
                    regions=regions_of)

    def _depth(self, group) -> int:
        names = set(group.names)
        depth: Dict[str, int] = {}
        indeg = {n: 0 for n in self.g.layers}
        for _, d in self.g.edges:
            indeg[d] += 1
        frontier = [n for n in self.g.layers if indeg[n] == 0]
        order: List[str] = []
        while frontier:
            n = frontier.pop(0)
            order.append(n)
            for s, d in self.g.edges:
                if s == n:
                    indeg[d] -= 1
                    if indeg[d] == 0:
                        frontier.append(d)
        for n in order:
            if n in names:
                preds = [p for p in self._preds[n] if p in names]
                depth[n] = 1 + max((depth[p] for p in preds), default=0)
        return max(depth.values(), default=1)

    def group(self, group, lms, total_batch: int) -> GroupScore:
        """Delay and energy of one layer group under ``lms``."""
        a, g, dt = self.a, self.g, self.dt
        tech = a.tech
        an = self._analyze(group, lms, total_batch)
        n_passes = dt(max(1, -(-total_batch // group.batch_unit)))
        depth = dt(self._depth(group))
        core_time = np.zeros(a.n_cores, dtype=dt)
        glb_rd = glb_wr = dt(0.0)
        peak = dt(a.macs_per_core * a.freq_ghz * 1e9)
        for name, regs in an["regions"].items():
            lyr = g.layers[name]
            mac_per_elem = layer_macs(lyr) / max(1, _ofmap_elems(lyr))
            ts = self._ts[name]
            for core, r in regs.items():
                rk = r.k1 - r.k0
                hwb = max(1, r.elems // max(1, rk))
                rd, wr, util = intra_core(
                    rk, lyr.C, hwb, lyr.R, lyr.S, lyr.bytes_per_elem,
                    a.glb_bytes, a.macs_per_core, lyr.kind)
                macs = dt(r.elems * mac_per_elem)
                core_time[core] += macs / (peak * dt(max(util, 1e-3))) \
                    * dt(ts)
                glb_rd += dt(rd * ts)
                glb_wr += dt(wr * ts)
        edge_tot = an["edge_bytes"] + an["edge_amort"]
        is_d2d = self.grid.edge_is_d2d
        t_noc = (edge_tot[~is_d2d] / dt(a.noc_bw * 1e9)).max(initial=0.0)
        t_d2d = (edge_tot[is_d2d] / dt(a.d2d_bw * 1e9)).max(initial=0.0) \
            if is_d2d.any() else dt(0.0)
        dram_port_bw = dt(a.dram_bw / a.n_dram * 1e9)
        t_dram = ((an["dram_bytes"] + an["dram_amort"])
                  / dram_port_bw).max(initial=0.0)
        t_comp = core_time.max(initial=0.0)
        stage = max(t_comp, t_noc, t_d2d, t_dram, dt(1e-12))
        glb_cap = dt(a.glb_bytes)
        over = np.maximum(an["glb_need"] - glb_cap, dt(0.0))
        overflow = over.sum()
        spill = overflow * dt(2.0)
        stage = stage * (dt(1.0) + overflow / (glb_cap * dt(a.n_cores)))
        stage = stage + spill / dt(a.dram_bw * 1e9)
        delay = stage * (n_passes + depth - dt(1.0))
        noc_bytes = edge_tot[~is_d2d].sum() * n_passes
        d2d_bytes = edge_tot[is_d2d].sum() * n_passes
        dram_b = an["dram_bytes"].sum() * n_passes + an["weight_total"] \
            + spill * n_passes
        e_mac = an["core_macs"].sum() * n_passes * dt(tech["e_mac"])
        e_glb = (glb_rd + glb_wr + an["core_in"].sum()) * n_passes \
            * dt(tech["e_glb_byte"])
        e_noc = (noc_bytes + d2d_bytes) * dt(tech["e_noc_hop_byte"])
        e_d2d = d2d_bytes * dt(tech["e_d2d_byte"])
        e_dram = dram_b * dt(tech["e_dram_byte"])
        energy = self._sum([e_mac, e_glb, e_noc, e_d2d, e_dram])
        return GroupScore(delay_s=float(delay), energy_j=float(energy))

    def _sum(self, xs):
        """The engine's ``sum()``: at float64 Python's float sum (which is
        compensated since Python 3.12), below it a plain left fold."""
        if self.dt is np.float64:
            return sum(float(x) for x in xs)
        acc = self.dt(0.0)
        for x in xs:
            acc = acc + self.dt(x)
        return acc

    def mapping(self, mapping: Sequence, total_batch: int
                ) -> Tuple[float, float]:
        """(energy J, delay s) of a whole mapping: sums over its groups."""
        scores = [self.group(grp, lms, total_batch) for grp, lms in mapping]
        return (float(self._sum(s.energy_j for s in scores)),
                float(self._sum(s.delay_s for s in scores)))
