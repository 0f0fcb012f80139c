"""Pallas TPU flash attention: blocked online-softmax, MXU-aligned tiles.

Grid (B, H, nq, nk); the kv dim is the innermost ("arbitrary") grid axis so
the f32 accumulator/max/denominator live in VMEM scratch across kv steps and
the output tile is written once on the last step.  BlockSpecs keep one
(bq, d) query tile + one (bk, d) kv tile resident — the VMEM working set is
bq*d + 2*bk*d + bq*bk floats, tuned so bq=bk=512, d<=256 stays well under
VMEM while the (bq, bk) matmuls are 128-aligned for the MXU.

This is the TPU adaptation of the paper's intra-core dataflow search: the
BlockSpec tile choice plays exactly the role of the chosen NVDLA tiling.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int,
                  seq_k: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq,bk)

    i = pl.program_id(2)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k
    if causal:
        mask &= q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                             # (bq,)
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                  # (bq, bk)
    l_new = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] \
        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(j == nk - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, bq: int = 512, bk: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, H, Sk, D) — MHA layout (GQA is expanded
    by ops.flash_attention).  Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pad_q = nq * bq - Sq
    pad_k = nk * bk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # accumulator
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
