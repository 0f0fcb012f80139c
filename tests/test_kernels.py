"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.mamba_ssd import ssd_chunk_dual
from repro.nn.mamba2 import ssd_chunked

RNG = np.random.default_rng(0)


def _randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,Sq,Sk,D", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 96, 96, 64),      # GQA + non-multiple of block
    (1, 2, 1, 128, 256, 32),    # Sq != Sk
    (2, 8, 8, 64, 64, 128),     # MHA wide head
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, H, KV, Sq, Sk, D, causal):
    if causal and Sq != Sk:
        pytest.skip("causal requires aligned q/k starts in this harness")
    q = _randn((B, Sq, H, D))
    k = _randn((B, Sk, KV, D))
    v = _randn((B, Sk, KV, D))
    out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    kr = jnp.repeat(k, H // KV, axis=2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, H // KV, axis=2).transpose(0, 2, 1, 3)
    expected = ref.attention_ref(q.transpose(0, 2, 1, 3), kr, vr,
                                 causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    B, H, S, D = 1, 2, 64, 32
    q = _randn((B, S, H, D), dtype)
    k = _randn((B, S, H, D), dtype)
    v = _randn((B, S, H, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    expected = ref.attention_ref(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=atol, rtol=atol)


def test_flash_attention_matches_nn_path():
    """Kernel vs the model's jnp flash scan (the dry-run twin)."""
    from repro.nn.attention import multihead_attention
    B, H, KV, S, D = 2, 4, 2, 128, 32
    q = _randn((B, S, H, D))
    k = _randn((B, S, KV, D))
    v = _randn((B, S, KV, D))
    a = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    b = multihead_attention(q, k, v, n_kv=KV, causal=True,
                            force_flash=True, block=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# mamba SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BC,Q,H,P,N", [
    (2, 16, 2, 8, 4),
    (4, 64, 4, 32, 16),
    (1, 128, 8, 64, 32),
])
def test_ssd_chunk_vs_ref(BC, Q, H, P, N):
    x = _randn((BC, Q, H, P))
    cum = jnp.cumsum(-jnp.abs(_randn((BC, Q, H))) * 0.1, axis=1)
    Bm = _randn((BC, Q, N))
    Cm = _randn((BC, Q, N))
    y, s = ssd_chunk_dual(x, cum, Bm, Cm, interpret=True)
    yr, sr = ref.ssd_chunk_ref(x, cum, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("L,chunk", [(64, 16), (96, 32), (70, 32)])
def test_ssd_forward_vs_model_chunked(L, chunk):
    B, H, P, N = 2, 4, 16, 8
    x = _randn((B, L, H, P))
    dt = jnp.abs(_randn((B, L, H))) * 0.1
    A = -jnp.abs(_randn((H,)))
    Bm = _randn((B, L, 1, N))
    Cm = _randn((B, L, 1, N))
    y1, _ = ops.ssd_forward(x, dt, A, Bm, Cm, chunk=chunk)
    y2, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=2e-4)


def test_ssd_chunked_matches_stepwise():
    """Chunked (train) path == token-by-token recurrence (decode path)."""
    from repro.nn.mamba2 import ssd_decode_step
    B, L, H, P, N = 1, 24, 2, 8, 4
    x = _randn((B, L, H, P))
    dt = jnp.abs(_randn((B, L, H))) * 0.1
    A = -jnp.abs(_randn((H,)))
    Bm = _randn((B, L, 1, N))
    Cm = _randn((B, L, 1, N))
    y_chunk, final_state = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    state = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(L):
        y, state = ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t, 0][:, None],
                                   Cm[:, t, 0][:, None], state)
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(final_state), np.asarray(state),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# tiled matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (64, 64, 64, 32, 32, 32),
    (100, 300, 50, 64, 64, 64),     # ragged
    (256, 128, 512, 128, 128, 128),
])
def test_tiled_matmul(M, K, N, bm, bn, bk):
    a = _randn((M, K))
    b = _randn((K, N))
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               atol=1e-3, rtol=1e-4)


def test_tiled_matmul_bf16():
    a = _randn((128, 128), jnp.bfloat16)
    b = _randn((128, 128), jnp.bfloat16)
    out = ops.matmul(a, b, bm=64, bn=64, bk=64)
    expected = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=0.5, rtol=5e-2)


def test_auto_interpret_only_on_cpu(monkeypatch):
    assert ops._auto_interpret(None) is True          # this CPU backend
    assert ops._auto_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._auto_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._auto_interpret(None)
