"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse — the
checks interpret mode cannot make (Mosaic layouts, dot dimension numbers,
fast-memory limits).  Widths are those of the paper's Table-I deployment
(``transformer()``: d_model 512, d_ff 2048, seq 512, batch 64).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bridge import lms_to_plan
from repro.core.dse import grid_candidates
from repro.core.evaluator import Evaluator, _fused_consts, _fused_program
from repro.core.graph_partition import partition_graph
from repro.core.tangram import tangram_map
from repro.core.workloads import transformer
from repro.kernels.flash_attention import flash_attention_mha
from repro.kernels.mamba_ssd import ssd_chunk_dual
from repro.kernels.tiled_matmul import tiled_matmul
from repro.launch.hlo_analysis import analyze_hlo_text
from repro.realize.plan import validate_plan
from repro.realize.program import build_program

PRECISIONS = ("default", "highest")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def table1_graph():
    return transformer()


def _struct(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *structs, precision="default"):
    with jax.default_matmul_precision(precision):
        return jax.jit(fn).lower(*structs).compile().as_text()


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("M,K,N", [(64 * 512, 512, 2048),   # batch x seq
                                   (1000, 300, 200)])       # unaligned
def test_tiled_matmul_compiles(one_chip, M, K, N, precision):
    text = _compile_text(functools.partial(tiled_matmul, interpret=False),
                         _struct((M, K), one_chip), _struct((K, N), one_chip),
                         precision=precision)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", PRECISIONS)
def test_flash_attention_compiles(one_chip, precision):
    qkv = _struct((64, 4, 512, 128), one_chip)          # B, H, S, D
    text = _compile_text(
        functools.partial(flash_attention_mha, interpret=False),
        qkv, qkv, qkv, precision=precision)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", PRECISIONS)
def test_ssd_chunk_compiles(one_chip, precision):
    BC, Q, H, P, N = 64 * 4, 128, 4, 128, 64   # batch x chunks, d_model 512
    text = _compile_text(
        functools.partial(ssd_chunk_dual, interpret=False),
        _struct((BC, Q, H, P), one_chip), _struct((BC, Q, H), one_chip),
        _struct((BC, Q, N), one_chip), _struct((BC, Q, N), one_chip),
        precision=precision)
    assert "tpu_custom_call" in text


def test_fused_scorer_compiles(one_chip, table1_graph):
    arch = grid_candidates(72.0, mac_options=(512,), cut_options=(1,),
                           dram_per_tops=(2.0,), noc_options=(32,),
                           d2d_ratio=(0.5,), glb_options=(2048,))[0]
    ev = Evaluator(arch, table1_graph)
    an = ev.analyzer
    spans = tuple(an._layout)
    B, n = 4, 1 << 20              # the batch bucket of 4 lockstep chains
    ne, nk = ev._is_d2d.size, _fused_consts(arch).size
    compiled = _fused_program().lower(
        B, an._buf_len, spans, ev._has_d2d,
        _struct((n,), one_chip, jnp.int32), _struct((n,), one_chip),
        _struct((B,), one_chip, jnp.int32), _struct((B,), one_chip, jnp.int32),
        _struct((B,), one_chip), _struct((ne,), one_chip),
        _struct((ne,), one_chip), _struct((nk,), one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def _table1_plan(g, macs):
    arch = grid_candidates(72.0, mac_options=(macs,), cut_options=(1,),
                           dram_per_tops=(2.0,), noc_options=(32,),
                           d2d_ratio=(0.5,), glb_options=(2048,))[0]
    plan = lms_to_plan(tangram_map(partition_graph(g, arch, 64), g, arch))
    validate_plan(plan, arch.n_cores, arch)
    return arch, plan


def test_realized_stage_compiles(topo, table1_graph):
    """The d_ff GEMM stage of a 1-core Table-I candidate's realized plan."""
    g = table1_graph
    arch, plan = _table1_plan(g, 36000)
    assert arch.n_cores == 1
    prog = build_program(g, plan, devices=topo.devices[:1], interpret=False)
    (sp,) = [sp for sp in prog.stages if "l0_ff1" in sp.stage.layers]
    assert "tpu_custom_call" in sp.lower_and_compile().as_text()


def test_realized_four_chip_stage_compiles(topo, table1_graph):
    """A stage of a 2x2-core candidate spread over four described chips:
    its Pallas GEMM runs per chip under shard_map (the compiler cannot
    partition a kernel), and the HLO walker sees the stage's collectives
    through the TPU layout syntax."""
    g = table1_graph
    arch, plan = _table1_plan(g, 9000)
    assert arch.n_cores == 4
    prog = build_program(g, plan, devices=topo.devices, interpret=False)
    sp = next(sp for sp in prog.stages if sp.n_devices == 4)
    text = sp.lower_and_compile().as_text()
    assert "tpu_custom_call" in text
    assert analyze_hlo_text(text).coll_bytes > 0
