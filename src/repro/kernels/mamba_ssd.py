"""Pallas TPU kernel for the Mamba-2 SSD per-chunk quadratic form.

One grid step processes one (batch*chunk, head) cell: it computes the
intra-chunk dual attention ``y_intra = ((C B^T) .* L) X`` and the chunk
state ``S = B^T (decay .* X)`` in a single VMEM residency of the chunk
tensors.
The O(chunk^2) decay matrix L never leaves VMEM — that is the kernel's whole
point (the HBM-streamed version would move Q*Q*H floats per chunk).

The inter-chunk recurrence (tiny (H, N, P) state) stays in jnp/lax.scan in
ops.py — it is O(L/Q) sequential steps and bandwidth-trivial.  n_groups == 1
(our configs); grouped B/C would add a leading G index to the same layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, cum_col_ref, cum_row_ref, decay_ref, bt_ref, c_ref,
                y_ref, state_ref):
    # one (chunk, head) cell; blocks: x (1, 1, Q, P); cum_col / decay
    # (1, 1, Q, 1); cum_row (1, 1, 1, Q); bt (1, N, Q); c (1, Q, N).
    # Every contraction is a 2-D MXU matmul with a contracting dimension
    # (the chip's compiler refuses dots without one).
    x = x_ref[0, 0].astype(jnp.float32)                   # (Q, P)
    cum_col = cum_col_ref[0, 0].astype(jnp.float32)       # (Q, 1)
    cum_row = cum_row_ref[0, 0].astype(jnp.float32)       # (1, Q)
    decay = decay_ref[0, 0].astype(jnp.float32)           # (Q, 1)
    Bt = bt_ref[0].astype(jnp.float32)                    # (N, Q)
    C = c_ref[0].astype(jnp.float32)                      # (Q, N)
    Q = x.shape[0]

    mm = functools.partial(jax.lax.dot_general,
                           dimension_numbers=(((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    scores = mm(C, Bt)                                    # (Qi, Qj)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(cum_col - cum_row), 0.0)
    y_ref[0, 0] = mm(scores * L, x).astype(y_ref.dtype)   # (Q, P)
    # S = sum_j B[j]^T decay[j] x[j]: decay scales x's rows
    state_ref[0, 0] = mm(Bt, x * decay).astype(state_ref.dtype)  # (N, P)


def ssd_chunk_dual(x: jax.Array, cum: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, *, interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Per-chunk SSD quadratic form.

    x (BC, Q, H, P) discretized inputs per flattened (batch*chunk);
    cum (BC, Q, H) cumulative log-decay within the chunk;
    Bm/Cm (BC, Q, N) input/output projections (n_groups=1).
    Returns (y_intra (BC, Q, H, P), chunk_state (BC, H, N, P)).

    The grid runs one (chunk, head) cell per step; operands are laid out
    head-major here so each cell's tiles are plain 2-D matrices.
    """
    BC, Q, H, P = x.shape
    N = Bm.shape[-1]
    cum = cum.astype(jnp.float32)
    cum_hq = cum.transpose(0, 2, 1)                       # (BC, H, Q)
    decay = jnp.exp(cum_hq[:, :, -1:] - cum_hq)           # (BC, H, Q)
    y, state = pl.pallas_call(
        _ssd_kernel,
        grid=(BC, H),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, N, Q), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda i, h: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BC, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BC, H, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), cum_hq[..., None], cum_hq[:, :, None, :],
      decay[..., None], Bm.transpose(0, 2, 1), Cm)
    return y.transpose(0, 2, 1, 3), state
