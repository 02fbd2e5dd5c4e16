"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ref import (
    reference_attention,
    reference_gmm,
    reference_selective_scan,
)


# multi-minute model/kernel path: runs in the full CI job only
pytestmark = pytest.mark.slow


RNG = np.random.RandomState(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "B,Lq,Lk,H,KVH,Dh,causal,window,bq,bkv",
    [
        (2, 128, 128, 4, 2, 64, True, 0, 64, 64),
        (1, 256, 256, 8, 8, 32, True, 0, 128, 64),
        (2, 200, 200, 4, 1, 64, True, 0, 64, 64),  # ragged lengths
        (1, 256, 256, 4, 2, 64, True, 96, 64, 64),  # sliding window
        (1, 64, 256, 4, 2, 64, False, 0, 64, 64),  # cross attention
        (1, 128, 128, 6, 2, 16, True, 0, 32, 32),  # small head dim
    ],
)
def test_flash_attention_sweep(B, Lq, Lk, H, KVH, Dh, causal, window, bq, bkv):
    q = jnp.asarray(RNG.randn(B, Lq, H, Dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, Lk, KVH, Dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, Lk, KVH, Dh), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, block_q=bq, block_kv=bkv)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, L, H, KVH, Dh = 1, 128, 4, 2, 64
    q = jnp.asarray(RNG.randn(B, L, H, Dh)).astype(dtype)
    k = jnp.asarray(RNG.randn(B, L, KVH, Dh)).astype(dtype)
    v = jnp.asarray(RNG.randn(B, L, KVH, Dh)).astype(dtype)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "B,L,Di,N,Lc,db,with_h0",
    [
        (2, 64, 32, 8, 16, 16, False),
        (1, 100, 48, 16, 32, 32, True),  # ragged L + seeded state
        (2, 256, 64, 16, 64, 64, False),
        (1, 32, 24, 4, 32, 8, True),  # d-blocked
    ],
)
def test_mamba_scan_sweep(B, L, Di, N, Lc, db, with_h0):
    xc = jnp.asarray(RNG.randn(B, L, Di), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(B, L, Di)) * 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.randn(B, L, N), jnp.float32)
    Cm = jnp.asarray(RNG.randn(B, L, N), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.randn(Di, N)) - 0.1, jnp.float32)
    h0 = jnp.asarray(RNG.randn(B, Di, N), jnp.float32) if with_h0 else None
    y, h = mamba_scan(xc, dt, Bm, Cm, a, h0, chunk_len=Lc, d_block=db)
    yr, hr = reference_selective_scan(xc, dt, Bm, Cm, a, h0)
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-4)


def test_mamba_scan_matches_model_chunked_scan():
    """The model's chunked associative scan and the kernel agree."""
    from repro.models.mamba import selective_scan

    B, L, Di, N = 2, 128, 32, 8
    xc = jnp.asarray(RNG.randn(B, L, Di), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(B, L, Di)) * 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.randn(B, L, N), jnp.float32)
    Cm = jnp.asarray(RNG.randn(B, L, N), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.randn(Di, N)) - 0.1, jnp.float32)
    y1, h1 = selective_scan(xc, dt, Bm, Cm, a, chunk_len=32)
    y2, h2 = mamba_scan(xc, dt, Bm, Cm, a, chunk_len=32, d_block=16)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h1, h2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "E,C,D,F,bc,bf",
    [
        (4, 32, 64, 96, 16, 32),
        (2, 100, 48, 80, 32, 32),  # ragged capacity
        (8, 16, 32, 32, 16, 16),
        (1, 64, 128, 64, 64, 64),
    ],
)
def test_moe_gmm_sweep(E, C, D, F, bc, bf):
    x = jnp.asarray(RNG.randn(E, C, D) * 0.5, jnp.float32)
    wg = jnp.asarray(RNG.randn(E, D, F) * 0.1, jnp.float32)
    wu = jnp.asarray(RNG.randn(E, D, F) * 0.1, jnp.float32)
    wd = jnp.asarray(RNG.randn(E, F, D) * 0.1, jnp.float32)
    out = moe_gmm(x, wg, wu, wd, block_c=bc, block_f=bf)
    ref = reference_gmm(x, wg, wu, wd)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_dtypes(dtype):
    E, C, D, F = 2, 32, 32, 48
    x = jnp.asarray(RNG.randn(E, C, D) * 0.5).astype(dtype)
    wg = jnp.asarray(RNG.randn(E, D, F) * 0.1).astype(dtype)
    wu = jnp.asarray(RNG.randn(E, D, F) * 0.1).astype(dtype)
    wd = jnp.asarray(RNG.randn(E, F, D) * 0.1).astype(dtype)
    out = moe_gmm(x, wg, wu, wd, block_c=16, block_f=16)
    ref = reference_gmm(x, wg, wu, wd)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), **_tol(dtype)
    )


def test_blocked_attention_matches_reference():
    """The model's scan-blocked attention == naive reference (incl. GQA+SWA)."""
    from repro.models.attention import blocked_attention

    B, L, H, KVH, Dh = 2, 160, 8, 2, 32
    q = jnp.asarray(RNG.randn(B, L, H, Dh), jnp.float32)
    k = jnp.asarray(RNG.randn(B, L, KVH, Dh), jnp.float32)
    v = jnp.asarray(RNG.randn(B, L, KVH, Dh), jnp.float32)
    for window in (0, 48):
        out = blocked_attention(q, k, v, causal=True, window=window, block_q=64, block_kv=32)
        ref = reference_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
