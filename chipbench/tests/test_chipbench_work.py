"""Work counts and references kept with the benchmark, checked by hand and
against the program."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as harness  # noqa: E402
from chipbench.drivers import sa_pool  # noqa: E402
from chipbench.reference import realized  # noqa: E402
from chipbench.reference.costmodel import CostModel  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted(c["name"] for c in BENCH["configs"])


def _config(name):
    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    return json.loads((ROOT / entry["file"]).read_text())


def _small(name):
    """The configuration at the sizes its reference module's ``SMALL``
    gives for CPU checks."""
    cfg = _config(name)
    return dict(cfg, **harness.load_reference(cfg).SMALL)


TF = harness.load_reference(_config("table1-tf")).realized_layers(
    _config("table1-tf"))


def _metric(name):
    return harness.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py")


def test_tf_paper_macs_per_sample():
    # per block: q,k,v,o,ff1,ff2 projections 4*512^3 + 2*512^2*2048,
    # qk and av 2*512^3, two adds 2 * 512*512*2
    assert realized.pass_macs(TF, 1) == 11_280_580_608


def test_tf_paper_macs_match_the_program_graph():
    from repro.core.workloads import transformer
    assert realized.pass_macs(TF, 1) == sum(
        l.macs(1) for l in transformer().layers.values())


def test_realized_pass_flops_at_batch_unit_4():
    assert 2 * realized.pass_macs(TF, 4) == 90_244_644_864
    shapes = realized.gemm_shapes(TF, 4)
    assert len(shapes) == 48 and shapes[0] == (2048, 512, 512)
    assert 2 * sum(m * k * n for m, k, n in shapes) \
        == 2 * realized.pass_macs(TF, 4) - 2 * 12 * 4 * 512 * 512 * 2


def test_fused_pass_bytes():
    m = _metric("fused_roofline")
    assert m.call_bytes(4, 1000, 3000) == 8 * 3000 + 4 * 4 * 1000 == 40_000
    assert m.least_seconds([(4, 1000, 3000)], PEAKS) == 40_000 / 819e9


def test_tiled_matmul_flops_and_bytes():
    m = _metric("matmul_roofline")
    s = (2048, 512, 2048)
    assert m.gemm_flops(*s) == 4_294_967_296
    assert m.gemm_bytes(*s) == 4 * (2048 * 512 + 512 * 2048 + 2048 * 2048)
    assert m.bound(s, PEAKS) == "memory"
    assert m.bound((8192, 4096, 8192), PEAKS) == "compute"


def _point(arch):
    return {k: getattr(arch, k) for k in sa_pool.ARCH_FIELDS}


@pytest.mark.parametrize("which", CONFIGS)
def test_cost_model_reference_equals_the_exact_engine(which):
    """The copied reference, on the graph it builds from the configuration,
    scores mappings bit for bit like the program's exact engine
    (``Evaluator.evaluate``, which the seed oracle pins), routed experts'
    expected traffic included; its lower-precision controls do not."""
    import ml_dtypes

    from repro.core.dse import DSEConfig, grid_candidates, run_dse
    from repro.core.evaluator import Evaluator
    from repro.core.sa import SAConfig
    from repro.core.workloads import make_workload

    cfg = _small(which)
    batch = int(cfg["batch"])
    g = make_workload(harness.workload_spec(cfg))
    graph = harness.load_reference(cfg).build(cfg)
    archs = grid_candidates(72.0, mac_options=(512, 1024, 2048),
                            cut_options=(1, 3), dram_per_tops=(2.0,),
                            noc_options=(32,), d2d_ratio=(0.5,),
                            glb_options=(1024,))
    dcfg = DSEConfig(batch=batch, keep_mappings=True,
                     sa=SAConfig(iters=12, seed=5, n_chains=4))
    for p in run_dse(archs, {"W": g}, dcfg, n_workers=1):
        mapping = p.mappings["W"]
        ref = CostModel(_point(p.arch), cfg["tech"], graph)
        ev = Evaluator(p.arch, g)
        exact = ev.evaluate(mapping, batch)
        assert ref.mapping(mapping, batch) == (exact.energy_j, exact.delay_s)
        for grp, lms in mapping:
            ge, _ = ev.eval_group(grp, lms, batch)
            r = ref.group(grp, lms, batch)
            assert (r.delay_s, r.energy_j) == (ge.delay_s, ge.energy_j)
        for dt in (np.float32, ml_dtypes.bfloat16):
            assert CostModel(_point(p.arch), cfg["tech"], graph,
                             dt).mapping(mapping, batch) \
                != (exact.energy_j, exact.delay_s)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_graph_matches_the_program_graph(name):
    """The reference's graph of each configuration, at its benchmark
    sizes, has the program's layers, edges and expected-traffic scales."""
    from repro.core.workloads import make_workload

    cfg = _config(name)
    want = make_workload(harness.workload_spec(cfg))
    got = harness.load_reference(cfg).build(cfg)
    fields = ("kind", "K", "H", "W", "C", "R", "S", "stride", "groups",
              "bytes_per_elem", "n_inputs", "traffic_scale",
              "weight_traffic_scale")
    assert list(got.layers) == list(want.layers)
    for n, lyr in want.layers.items():
        assert [getattr(got.layers[n], f) for f in fields] == \
            [getattr(lyr, f) for f in fields], n
    assert got.edges == want.edges
    assert got.edge_mults == want.edge_mults


def test_window_order_is_seeded_inside_fixed_blocks():
    mix = json.loads((ROOT / "chipbench" / "traffic"
                      / "sa-pool.json").read_text())
    pool, block = mix["pool"], mix["block"]
    seed = 2 ** 33 + 5
    order = sa_pool.window_order(len(pool), block, seed)
    assert sorted(order) == list(range(len(pool)))
    assert order == sa_pool.window_order(len(pool), block, seed)
    assert order != sa_pool.window_order(len(pool), block, seed + 1)
    cores = [p["x_cores"] * p["y_cores"] for p in pool]
    for lo in range(0, len(pool), block):   # each block: the same points,
        assert sorted(order[lo:lo + block]) == list(range(lo, lo + block))
        assert sorted(cores[i] for i in order[lo:lo + block]) == \
            [18, 18, 18, 18, 35, 35, 70, 70]   # two of each stratum


def test_window_meets_each_point_once_and_none_of_set_up():
    from itertools import islice
    mix = json.loads((ROOT / "chipbench" / "traffic"
                      / "sa-pool.json").read_text())
    key = lambda p: json.dumps(p, sort_keys=True)  # noqa: E731
    n = len(mix["pool"])
    window = list(islice(sa_pool.window_tasks(mix, 2 ** 31 + 99), 2 * n))
    first = [(key(mix["pool"][i]), s) for i, s in window[:n]]
    assert len({p for p, _ in first}) == n
    setup = [(key(p), s) for p, s in sa_pool.setup_tasks(mix)]
    assert not {p for p, _ in setup} & {p for p, _ in first}
    again = [(key(mix["pool"][i]), s) for i, s in window[n:]]
    assert not set(again) & set(first)          # fresh SA seeds on a wrap
    assert 0 <= window[0][1] < 2 ** 32