"""Compressed data-parallel gradient synchronization (beyond-paper).

Instead of letting GSPMD emit fp32 all-reduces for the DP gradient sum,
``compressed_grad_sync`` runs the sync explicitly inside ``shard_map``:
each leaf is scaled by a globally-agreed power-of-two-free scale
(pmax of |g| / 127), quantized to int8, summed over the axis in int32
(hardware-exact), and dequantized.  Error feedback carries the
quantization residual into the next step, so the scheme is unbiased over
time (tests/test_compressed_dp.py).

Wire-format accounting: a ring all-reduce moves ~2·n bytes/element-width
per device; the int16 wire format halves the gradient-sync collective
bytes vs fp32 (verified from compiled HLO in the test).  On the roofline this attacks the
collective term of DP-dominated training cells.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


def _sync_leaf(g: jax.Array, e: jax.Array, axis: str
               ) -> Tuple[jax.Array, jax.Array]:
    """One leaf: error-feedback int8 quantize -> exact int32 psum -> deq."""
    gf = g.astype(jnp.float32) + e
    local_max = jnp.max(jnp.abs(gf))
    scale = jax.lax.pmax(local_max, axis) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    new_e = gf - q.astype(jnp.float32) * scale
    # int16 wire format: exact for <= 256 summands (127*256 < 2^15) — the
    # per-pod DP degree; hierarchical sync would chunk beyond that
    total = jax.lax.psum(q.astype(jnp.int16), axis)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    mean = total.astype(jnp.float32) * scale / n
    return mean, new_e


def compressed_grad_sync(grads: Pytree, error: Pytree, axis: str
                         ) -> Tuple[Pytree, Pytree]:
    """Mean-reduce ``grads`` over mesh axis ``axis`` in int8 wire format.

    Must be called inside shard_map/pmap with ``axis`` bound.  Returns
    (synced grads, new error-feedback state).
    """
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error)
    outs = [_sync_leaf(g, e, axis) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(tdef, [o[0] for o in outs]),
            jax.tree.unflatten(tdef, [o[1] for o in outs]))


def make_compressed_dp_step(loss_fn, opt_update, mesh, axis: str = "data"):
    """Build a shard_map DP train step with compressed gradient sync.

    ``loss_fn(params, batch) -> scalar``; ``opt_update(params, grads, opt)
    -> (params, opt, metrics)``.  Params/opt replicated over ``axis``;
    batch sharded on its leading dim.  Returns a jitted step:
    ``step(params, opt, err, batch) -> (params, opt, err, metrics)``.
    """
    from jax.sharding import PartitionSpec as P

    def local_step(params, opt, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, err = compressed_grad_sync(grads, err, axis)
        loss = jax.lax.pmean(loss, axis)
        params, opt, metrics = opt_update(params, grads, opt)
        return params, opt, err, {"loss": loss, **metrics}

    rep = P()
    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, rep, P(axis)),
        out_specs=(rep, rep, rep, rep),
        check_vma=False))
