"""Fused jitted construct->replay->eval pass (``backend="jax"``) and the
jax replay backend: parity envelopes across the workload zoo, bad-backend /
bad-dtype refusal, fused-vs-exact cache separation in CachedEvaluator, and
the rescore-winners contract of ``SAConfig(backend="jax")``."""

from itertools import combinations

import numpy as np
import pytest

from repro.core.analyzer import _jax_replay
from repro.core.encoding import random_lms
from repro.core.evaluator import (FUSED_STATS, CachedEvaluator, Evaluator,
                                  _fused_program)
from repro.core.explore import replica_exchange_sa
from repro.core.graph_partition import partition_graph
from repro.core.hw import ArchConfig
from repro.core.sa import SAConfig
from repro.core.workloads import make_workload

# the documented fused parity envelope (DESIGN.md "Fused jitted pass"):
# float32 math + unordered segment reduction, never bit-identical
REL_TOL = 1e-4

ZOO = ("tf-quick", "moe-quick", "mla-quick")


def _arch(**kw):
    return ArchConfig(**{**dict(x_cores=4, y_cores=3, xcut=2, ycut=1,
                                noc_bw=16.0, d2d_bw=8.0, dram_bw=64.0,
                                glb_kb=512, macs_per_core=256), **kw})


def _requests(g, arch, seed=0, n=3):
    groups = partition_graph(g, arch, 8)
    rng = np.random.default_rng(seed)
    return [(grp, random_lms(grp, g, arch.n_cores, arch.n_dram, rng))
            for grp in groups for _ in range(n)]


def _assert_parity(exact, fused):
    assert len(fused) == len(exact)
    for (ge, an), (gf, anf) in zip(exact, fused):
        assert anf is None        # fused rows carry no analyses by contract
        assert an is not None
        for a, b in ((ge.delay_s, gf.delay_s),
                     (ge.energy_j, gf.energy_j),
                     (ge.stage_time_s, gf.stage_time_s)):
            assert abs(a - b) / max(abs(a), 1e-30) < REL_TOL
        assert ge.bottleneck == gf.bottleneck
        for k in ge.energy_breakdown:
            a, b = ge.energy_breakdown[k], gf.energy_breakdown[k]
            assert abs(a - b) <= REL_TOL * max(abs(a), 1e-12)


# ---------------------------------------------------------------------------
# fused evaluator pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ZOO)
def test_fused_parity_envelope(spec):
    arch = _arch()
    g = make_workload(spec)
    reqs = _requests(g, arch, seed=1)
    ev = Evaluator(arch, g)
    _assert_parity(ev.eval_requests_batch(reqs, 8),
                   ev.eval_requests_batch(reqs, 8, backend="jax"))


@pytest.mark.parametrize("spec", ZOO)
def test_fused_program_shared_across_candidates(spec):
    """Two candidates of one core grid, with their own cut, bandwidths and
    GLB, run one traced program, each with its own constants."""
    g = make_workload(spec)
    arch_a = _arch()
    arch_b = _arch(xcut=1, ycut=3, noc_bw=4.0, d2d_bw=2.0, dram_bw=16.0,
                   glb_kb=1024)
    reqs = _requests(g, arch_a, seed=6, n=1)[:4]
    _fused_program().clear_cache()
    before = FUSED_STATS["traces"]
    delays = []
    for arch in (arch_a, arch_b):
        ev = Evaluator(arch, g)
        fused = ev.eval_requests_batch(reqs, 8, backend="jax")
        _assert_parity(ev.eval_requests_batch(reqs, 8), fused)
        delays.append([gf.delay_s for gf, _ in fused])
    assert FUSED_STATS["traces"] == before + 1
    assert delays[0] != delays[1]


def _one_bucket_batches(ev, total_batch):
    """Batches of 1-4 requests whose streams pad to one length, so that
    only the batch size tells them apart: mappings on one core a layer
    (short streams) mixed with mappings over the whole grid (long ones)."""
    g, arch, an = ev.g, ev.arch, ev.analyzer
    rng = np.random.default_rng(0)
    reqs = [(grp, random_lms(grp, g, nc, arch.n_dram, rng))
            for grp in partition_graph(g, arch, total_batch)
            for nc in (len(grp.names), arch.n_cores)]
    an._prefetch_contribs(reqs, total_batch)
    lens = [an.row_stream(grp, lms, total_batch)[0].size for grp, lms in reqs]
    cap = 1 << (max(lens) - 1).bit_length()
    order = sorted(range(len(reqs)), key=lens.__getitem__)
    return {k: [reqs[i] for i in next(
                c for c in combinations(order, k)
                if cap // 2 < sum(lens[i] for i in c) <= cap)]
            for k in (1, 2, 3, 4)}


@pytest.mark.parametrize("first", [1, 2, 3, 4])
def test_fused_batch_sizes_share_one_program(first):
    """Batches of 1-4 requests pad to one batch bucket: the size met first
    traces the program, the others reuse it, and each gets its own rows."""
    arch = _arch()
    ev = Evaluator(arch, make_workload("tf-quick"))
    batches = _one_bucket_batches(ev, 8)
    _fused_program().clear_cache()
    before = FUSED_STATS["traces"]
    for k in sorted(batches, key=lambda k: k != first):
        fused = ev.eval_requests_batch(batches[k], 8, backend="jax")
        assert FUSED_STATS["traces"] == before + 1
        _assert_parity(ev.eval_requests_batch(batches[k], 8), fused)


def test_fused_empty_requests():
    arch = _arch()
    ev = Evaluator(arch, make_workload("tf-quick"))
    assert ev.eval_requests_batch([], 8, backend="jax") == []


def test_fused_bad_backend_refused():
    arch = _arch()
    g = make_workload("tf-quick")
    ev = Evaluator(arch, g)
    reqs = _requests(g, arch, n=1)
    with pytest.raises(ValueError, match="unknown eval batch backend"):
        ev.eval_requests_batch(reqs, 8, backend="torch")
    with pytest.raises(ValueError, match="unknown analyze batch backend"):
        ev.analyzer.analyze_requests(reqs, 8, backend="torch")


def test_cached_evaluator_keeps_fused_results_separate():
    """Parity-grade fused values must never satisfy an exact-path lookup."""
    arch = _arch()
    g = make_workload("tf-quick")
    ce = CachedEvaluator(arch, g)
    reqs = _requests(g, arch, seed=2, n=2)
    fused = ce.eval_groups_batched(reqs, 8, backend="jax")
    assert len(ce._fused_cache) > 0
    # second fused call is served from the fused cache, same objects
    fused2 = ce.eval_groups_batched(reqs, 8, backend="jax")
    assert [ge for ge, _ in fused2] == [ge for ge, _ in fused]
    # the exact path must recompute from scratch and agree bit-for-bit
    # with a fresh uncached evaluator
    exact = ce.eval_groups_batched(reqs, 8)
    ref = Evaluator(arch, g).eval_requests_batch(reqs, 8)
    for (ge, _), (gr, _) in zip(exact, ref):
        assert (ge.delay_s, ge.energy_j) == (gr.delay_s, gr.energy_j)


def test_sa_fused_backend_rescores_winners_exact():
    """SAConfig(backend="jax"): proposals scored fused, best re-scored
    exactly at finalize — the reported cost must equal an independent
    exact evaluation of the returned mapping."""
    arch = _arch()
    g = make_workload("tf-quick")
    groups = partition_graph(g, arch, 8)
    cfg = SAConfig(iters=40, seed=3, n_chains=2, backend="jax")
    res = replica_exchange_sa(g, arch, groups, 8, cfg,
                              evaluator=CachedEvaluator(arch, g))
    final = Evaluator(arch, g).evaluate(res.mapping, 8)
    assert res.cost == final.cost(cfg.beta, cfg.gamma)
    assert res.energy_j == final.energy_j
    assert res.delay_s == final.delay_s


# ---------------------------------------------------------------------------
# jax REPLAY backend (analyze_requests(backend="jax"))
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ZOO)
def test_jax_replay_zoo_parity(spec):
    """The replay backend across the zoo — MoE carries non-1.0
    traffic_scale (top_k routed experts), MLA has the low-rank cubes and
    ragged CG rows; both must replay within float32 parity of the exact
    bincount."""
    arch = _arch()
    g = make_workload(spec)
    if spec == "moe-quick":
        scales = {l.traffic_scale for l in g.layers.values()}
        assert any(s != 1.0 for s in scales)     # routed experts present
    reqs = _requests(g, arch, seed=4, n=2)
    an = Evaluator(arch, g).analyzer
    ab_np = an.analyze_requests(reqs, 8)
    ab_jx = an.analyze_requests(reqs, 8, backend="jax")
    np.testing.assert_allclose(ab_jx.buf, ab_np.buf, rtol=2e-4, atol=1e-2)
    np.testing.assert_array_equal(ab_jx.weight_totals, ab_np.weight_totals)


def test_jax_replay_refuses_bad_dtypes():
    with pytest.raises(TypeError, match="int64 index stream"):
        _jax_replay(np.array([0, 1], np.int32),
                    np.array([1.0, 2.0]), 4)
    with pytest.raises(TypeError, match="float64 value stream"):
        _jax_replay(np.array([0, 1], np.int64),
                    np.array([1.0, 2.0], np.float32), 4)


def test_jax_replay_matches_bincount_exactly_shaped():
    """Direct replay check: same cells, float32-grade agreement."""
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 64, size=500)
    vals = rng.normal(size=500)
    out = _jax_replay(idx.astype(np.int64), vals.astype(np.float64), 64)
    ref = np.bincount(idx, weights=vals, minlength=64)
    assert out.shape == ref.shape and out.dtype == np.float64
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kw", [dict(n_chains=1),
                                dict(n_chains=4, lockstep=False),
                                dict(backend="torch")])
def test_sa_config_refuses_configs_that_skip_the_fused_pass(kw):
    with pytest.raises(ValueError):
        SAConfig(**{"backend": "jax", **kw})


def test_engine_refuses_workers_with_fused_backend():
    from repro.core.dse import DSEConfig
    from repro.core.explore import ExplorationEngine
    cfg = DSEConfig(batch=8, sa=SAConfig(iters=4, n_chains=4, backend="jax"))
    g = make_workload("tf-quick")
    with pytest.raises(ValueError, match="one process"):
        ExplorationEngine({"TF": g}, cfg, n_workers=2)
    ExplorationEngine({"TF": g}, cfg, n_workers=1).close()


def test_fused_stats_count_calls_and_platform():
    arch = _arch()
    g = make_workload("tf-quick")
    before = FUSED_STATS["calls"]
    Evaluator(arch, g).eval_requests_batch(_requests(g, arch), 8,
                                           backend="jax")
    assert FUSED_STATS["calls"] == before + 1
    assert FUSED_STATS["platforms"] == {"cpu"}
