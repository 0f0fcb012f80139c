"""Pallas TPU tiled GEMM — the paper's PE-array (NVDLA) analogue.

Grid (m/bm, n/bn, k/bk) with the contraction axis innermost; a f32 VMEM
accumulator persists across k steps (output-stationary dataflow — the same
loop-order/tiling decision the paper's intra-core engine searches, here
fixed to the TPU-optimal choice: 128-aligned MXU tiles, psum in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())))

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
                 bk: int = 512, out_dtype=None,
                 interpret: bool = False) -> jax.Array:
    """a (M, K) @ b (K, N) -> (M, N) with explicit VMEM tiling."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    nm, nn, nk = -(-M // bm), -(-N // bn), -(-K // bk)
    pm, pn, pk = nm * bm - M, nn * bn - N, nk * bk - K
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    out_dtype = out_dtype or a.dtype
    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
