"""Mamba-1 selective-state-space mixer (falcon-mamba / Jamba layers).

Train/prefill path: *chunked* selective scan — time is split into chunks of
``chunk_len``; within a chunk the recurrence h_t = a_t h_{t-1} + b_t is an
``associative_scan`` over affine maps (all |a_t| <= 1, numerically tame), and
the (B, Di, N) state is carried across chunks with ``lax.scan``. The
(B, L, Di, N) discretized tensors therefore only ever exist one chunk at a
time — the same blocking the ``repro.kernels.mamba_scan`` Pallas kernel uses
to keep the working set in VMEM.

Decode path: O(1) per token — one affine state update plus a depthwise-conv
ring window.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ArchConfig, ParamBuilder, cache_layer, cache_write, rms_norm, shard


def init_mamba(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    import numpy as np

    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    r, k = cfg.dt_rank, cfg.ssm_conv
    # S4D-real init for A; dt bias init so softplus(dt) spans [1e-3, 1e-1]
    a_init = np.tile(np.arange(1, n + 1, dtype=np.float32)[None, :], (di, 1))
    dt = np.exp(
        np.random.RandomState(0).uniform(np.log(1e-3), np.log(1e-1), size=(di,))
    ).astype(np.float32)
    dt_bias = dt + np.log1p(-np.exp(-dt))  # inverse softplus
    p = {
        "in_proj": pb.dense((d, 2 * di), ("embed", "inner")),
        "conv_w": pb.dense((k, di), (None, "inner"), scale=k**-0.5),
        "conv_b": pb.zeros((di,), ("inner",)),
        "x_proj": pb.dense((di, r + 2 * n), ("inner", None)),
        "dt_proj": pb.dense((r, di), (None, "inner"), scale=r**-0.5),
        "dt_bias": pb.const(dt_bias, ("inner",), jnp.float32),
        "a_log": pb.const(np.log(a_init), ("inner", None), jnp.float32),
        "d_skip": pb.ones((di,), ("inner",)),
        "out_proj": pb.dense((di, d), ("inner", "embed")),
    }
    if cfg.ssm_bcdt_norm:
        p["dt_norm"] = pb.ones((r,), (None,))
        p["b_norm"] = pb.ones((n,), (None,))
        p["c_norm"] = pb.ones((n,), (None,))
    return p


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, L, Di), w: (K, Di) -> (B, L, Di)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # sum_k w[k] * x[t - (K-1) + k] — K static shifts (K is 4): cheap & fusable
    out = jnp.zeros_like(x)
    L = x.shape[1]
    for k in range(K):
        out = out + w[k] * jax.lax.slice_in_dim(xp, k, k + L, axis=1)
    return out + b


def _ssm_params(p: dict, cfg: ArchConfig, xc: jax.Array):
    """xc: (B, L, Di) post-conv activations -> dt (f32), Bmat, Cmat. With
    ``cfg.ssm_bcdt_norm`` the x_proj outputs dt (over dt_rank), B and C (over
    d_state) are RMS-normalised first, each with its own weight (Jamba)."""
    r, n = cfg.dt_rank, cfg.ssm_state
    proj = jnp.einsum("bld,dr->blr", xc, p["x_proj"])  # (B,L,r+2n)
    dt_in, Bm, Cm = proj[..., :r], proj[..., r : r + n], proj[..., r + n :]
    if cfg.ssm_bcdt_norm:
        dt_in = rms_norm(dt_in, p["dt_norm"], cfg.norm_eps)
        Bm = rms_norm(Bm, p["b_norm"], cfg.norm_eps)
        Cm = rms_norm(Cm, p["c_norm"], cfg.norm_eps)
    dt = jnp.einsum("blr,rd->bld", dt_in, p["dt_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B,L,Di) f32
    return dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32)


# bytes of one batch row's float32 (chunk, Di, N) tile of the chunked scan:
# 64 steps at Di 8192 and N 16, so a 2,048-token prefill of Jamba's width
# fits beside one chip's share of its weights
SCAN_TILE_BYTES = 32 << 20


def scan_chunk(cfg: ArchConfig, L: int) -> int:
    """Time steps per chunk of an L-step scan: as many as fit one batch
    row's float32 (chunk, Di, N) tile in ``SCAN_TILE_BYTES``."""
    return max(1, min(L, SCAN_TILE_BYTES // (cfg.d_inner * cfg.ssm_state * 4)))


def selective_scan(
    xc: jax.Array,  # (B, L, Di) f32/bf16 post-conv
    dt: jax.Array,  # (B, L, Di) f32
    Bm: jax.Array,  # (B, L, N) f32
    Cm: jax.Array,  # (B, L, N) f32
    a: jax.Array,  # (Di, N) f32, negative (= -exp(a_log))
    h0: Optional[jax.Array] = None,  # (B, Di, N) carry-in state
    chunk_len: int = 256,
):
    """Chunked selective scan. Returns (y: (B,L,Di) f32, h_final: (B,Di,N))."""
    with jax.named_scope("jnp_mamba_scan"):
        return _selective_scan_impl(xc, dt, Bm, Cm, a, h0, chunk_len)


def _selective_scan_impl(xc, dt, Bm, Cm, a, h0=None, chunk_len=256):
    B, L, Di = xc.shape
    N = a.shape[1]
    Lc = min(chunk_len, L)
    h0 = jnp.zeros((B, Di, N), jnp.float32) if h0 is None else h0

    pad = (-L) % Lc  # padded steps have dt=0 => a=1, b=0: state untouched
    if pad:
        xc = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = (L + pad) // Lc
    xcf = xc.astype(jnp.float32)

    def chunk_body(h, inp):
        xck, dtk, Bk, Ck = inp  # (B, Lc, ...)
        dta = dtk[..., None] * a  # (B,Lc,Di,N)  log of decay per step
        ak = jnp.exp(dta)
        bk = (dtk * xck)[..., None] * Bk[:, :, None, :]  # (B,Lc,Di,N)

        def combine(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2

        a_cum, b_cum = jax.lax.associative_scan(combine, (ak, bk), axis=1)
        hk = a_cum * h[:, None] + b_cum  # (B,Lc,Di,N)
        yk = jnp.einsum("blin,bln->bli", hk, Ck)  # (B,Lc,Di)
        return hk[:, -1], yk

    xs = tuple(
        t.reshape(B, nc, Lc, *t.shape[2:]).swapaxes(0, 1)
        for t in (xcf, dt, Bm, Cm)
    )
    h_final, ys = jax.lax.scan(chunk_body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(B, L + pad, Di)
    if pad:
        y = y[:, :L]
    return y, h_final


def mamba_block(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, D)
    positions: jax.Array,  # unused (kept for mixer-uniform signature)
    cache: Optional[dict] = None,  # {"h": (G,B,Di,N), "conv": (G,B,K-1,Di)}
    scan_impl: Optional[object] = None,  # Pallas selective scan on TPU
    layer: Optional[jax.Array] = None,  # this layer's slot in ``cache``
):
    B, L, D = x.shape
    di, n, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    xz = jnp.einsum("bld,de->ble", x, p["in_proj"])
    xr, z = jnp.split(xz, 2, axis=-1)
    xr = shard(xr, "batch", "seq", "inner")
    a = -jnp.exp(p["a_log"])  # (Di, N)

    stacked = cache
    if cache is not None:  # this layer's slot of the stacked state
        cache = {k: cache_layer(c, layer) for k, c in cache.items()}
    if cache is None:
        xc = jax.nn.silu(_causal_conv(xr, p["conv_w"], p["conv_b"]))
        dt, Bm, Cm = _ssm_params(p, cfg, xc)
        scan = scan_impl or selective_scan
        y, _ = scan(xc, dt, Bm, Cm, a, chunk_len=scan_chunk(cfg, L))
        new_cache = None
    elif L == 1:
        # decode: single-token affine update
        conv_win = jnp.concatenate([cache["conv"], xr], axis=1)  # (B, K, Di)
        xc = jax.nn.silu(
            jnp.einsum("bkd,kd->bd", conv_win, p["conv_w"]) + p["conv_b"]
        )[:, None]
        dt, Bm, Cm = _ssm_params(p, cfg, xc)
        dta = dt[:, 0, :, None] * a  # (B,Di,N)
        h = jnp.exp(dta) * cache["h"] + (dt[:, 0] * xc[:, 0].astype(jnp.float32))[
            ..., None
        ] * Bm[:, 0, None, :]
        y = jnp.einsum("bin,bn->bi", h, Cm[:, 0])[:, None]  # (B,1,Di)
        new_cache = {"h": h, "conv": conv_win[:, 1:]}
    else:
        # prefill into an existing state: conv seeded from the cached window,
        # scan seeded from the cached h
        conv_in = jnp.concatenate([cache["conv"], xr], axis=1)  # (B, K-1+L, Di)
        acc = jnp.zeros_like(xr)
        for k in range(K):
            acc = acc + p["conv_w"][k] * jax.lax.slice_in_dim(conv_in, k, k + L, axis=1)
        xc = jax.nn.silu(acc + p["conv_b"])
        dt, Bm, Cm = _ssm_params(p, cfg, xc)
        scan = scan_impl or selective_scan
        y, h_final = scan(xc, dt, Bm, Cm, a, h0=cache["h"], chunk_len=scan_chunk(cfg, L))
        new_cache = {"h": h_final, "conv": conv_in[:, -(K - 1) :]}

    if new_cache is not None:  # the layer's small state replaced in its slot
        new_cache = {k: cache_write(stacked[k], c, layer) for k, c in new_cache.items()}
    y = y + xcf_skip(xc, p["d_skip"])
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bli,id->bld", y, p["out_proj"])
    return out, new_cache


def xcf_skip(xc: jax.Array, d_skip: jax.Array) -> jax.Array:
    return xc.astype(jnp.float32) * d_skip


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype) -> dict:
    return {
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
    }
