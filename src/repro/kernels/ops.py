"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to auto: real TPU lowering on a TPU backend, the
Pallas interpreter on the CPU backend (tests), and an error on any other
backend — a kernel never silently runs interpreted where a device was
meant to run it.  GQA inputs are expanded to MHA layout here so the
kernels stay MXU-simple.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_mha
from .mamba_ssd import ssd_chunk_dual
from .tiled_matmul import tiled_matmul


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target TPU (or the CPU interpreter); the "
        f"default backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("causal", "n_kv", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    n_kv: Optional[int] = None, causal: bool = True,
                    bq: int = 512, bk: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q (B, Sq, H, D); k/v (B, Sk, KV, D) -> (B, Sq, H, D).

    GQA (KV < H) is expanded to MHA by repeating kv heads — transient only,
    mirrors nn.attention's repeat_kv TP layout."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention_mha(qh, kh, vh, causal=causal, bq=bq, bk=bk,
                              interpret=_auto_interpret(interpret))
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_forward(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, *, chunk: int = 128,
                interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Full chunked SSD using the Pallas per-chunk kernel + jnp recurrence.

    Same contract as nn.mamba2.ssd_chunked with n_groups=1:
    x (B,L,H,P), dt (B,L,H), A (H,), Bm/Cm (B,L,1,N)."""
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // chunk
    xb = (x * dt[..., None]).astype(jnp.float32)
    dA = dt.astype(jnp.float32) * A.astype(jnp.float32)
    cum = jnp.cumsum(dA.reshape(Bsz, nc, chunk, H), axis=2)

    flat = lambda t, s: t.reshape((Bsz * nc,) + s)
    y_intra, S = ssd_chunk_dual(
        flat(xb.reshape(Bsz, nc, chunk, H, P), (chunk, H, P)),
        flat(cum, (chunk, H)),
        flat(Bm.reshape(Bsz, nc, chunk, N), (chunk, N)),
        flat(Cm.reshape(Bsz, nc, chunk, N), (chunk, N)),
        interpret=_auto_interpret(interpret))
    y_intra = y_intra.reshape(Bsz, nc, chunk, H, P)
    S = S.reshape(Bsz, nc, H, N, P)

    tot = cum[:, :, -1]                                  # (B, nc, H)

    def step(h, inp):
        tot_c, S_c = inp
        return h * jnp.exp(tot_c)[..., None, None] + S_c, h

    h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, h_before = jax.lax.scan(step, h0,
                               (tot.transpose(1, 0, 2),
                                S.transpose(1, 0, 2, 3, 4)))
    h_before = h_before.transpose(1, 0, 2, 3, 4)         # (B,nc,H,N,P)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)
    y_inter = jnp.einsum("bcqn,bchnp,bcqh->bcqhp", Cc, h_before,
                         jnp.exp(cum))
    y = (y_intra + y_inter).reshape(Bsz, Lp, H, P)[:, :L]
    return y.astype(x.dtype), None


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           bk: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    return tiled_matmul(a, b, bm=bm, bn=bn, bk=bk,
                        interpret=_auto_interpret(interpret))
