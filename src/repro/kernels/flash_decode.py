"""Pallas TPU flash-decoding: single-token attention against a KV cache.

Decode attention is a memory-bound GEMV over the cache: the kernel's job is
to stream K/V exactly once HBM->VMEM and keep the softmax running stats in
scratch. Grid: (batch x kv_head, kv_blocks) with the kv axis innermost
(sequential); the q tile (gq rows — the GQA group of this KV head) stays
resident across all kv steps.

Masking is position-based (matches ``models.attention._cached_attention``):
a per-slot position array handles both linear caches (pos = slot index) and
SWA ring buffers (pos = stored absolute position); slots beyond the write
index are invalid and carry a position no query reaches.

The per-slot positions and the query position travel as 3-D arrays whose
last two block dims equal the array's, (1, block_kv) and (1, 1), which the
chip's (8, 128) tiling rule accepts. Runs compiled on a TPU and interpreted
elsewhere; tests compare both with ``ref.reference_decode``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -2.0e38
_NEVER = jnp.iinfo(jnp.int32).max  # position of an unwritten slot


def _decode_kernel(
    q_ref,  # (1, gq, d)
    k_ref,  # (1, bkv, d)
    v_ref,  # (1, bkv, d)
    pos_ref,  # (1, 1, bkv) s32 per-slot absolute positions (_NEVER = unwritten)
    qpos_ref,  # (1, 1, 1) s32 current query position
    o_ref,  # (1, gq, d)
    m_scr,  # (gq, 128)
    l_scr,  # (gq, 128)
    acc_scr,  # (gq, d)
    *,
    window: int,
    scale: float,
):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (gq, d)
    k = k_ref[0]  # (bkv, d)
    v = v_ref[0]
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )  # (gq, bkv)
    kpos = pos_ref[0]  # (1, bkv)
    qpos = qpos_ref[0]  # (1, 1)
    ok = kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, 0]
    l_prev = l_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-37)[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,  # (B, 1, H, Dh) the new token's queries
    k: jax.Array,  # (B, S, KVH, Dh) cache keys
    v: jax.Array,  # (B, S, KVH, Dh) cache values
    k_pos: jax.Array,  # (B, S) s32 absolute position per slot
    q_pos: jax.Array,  # (B,) s32 current position
    n_valid: jax.Array,  # (B,) s32 number of written slots
    *,
    window: int = 0,
    block_kv: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Lq, H, Dh = q.shape
    assert Lq == 1, "flash_decode is single-token"
    S, KVH = k.shape[1], k.shape[2]
    gq = H // KVH
    scale = Dh**-0.5

    block_kv = min(block_kv, S)
    nk = math.ceil(S / block_kv)
    pad = nk * block_kv - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad

    # fold: (B, 1, KVH, gq, d) -> (B*KVH, gq, d); KV -> (B*KVH, Sp, d)
    qf = q.reshape(B, KVH, gq, Dh).reshape(B * KVH, gq, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, Sp, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, Sp, Dh)
    slot = jnp.arange(S)[None, :]
    pos = jnp.where(slot < n_valid[:, None], k_pos.astype(jnp.int32), _NEVER)
    pos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=_NEVER)
    posf = jnp.repeat(pos, KVH, axis=0)[:, None, :]  # (B*KVH, 1, Sp)
    qposf = jnp.repeat(q_pos.astype(jnp.int32), KVH)[:, None, None]  # (B*KVH, 1, 1)

    kernel = functools.partial(_decode_kernel, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, nk),
        in_specs=[
            pl.BlockSpec((1, gq, Dh), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, Dh), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, Dh), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, 1), lambda b, ki: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, gq, Dh), lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, gq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf, posf, qposf)
    return out.reshape(B, KVH, gq, Dh).reshape(B, 1, H, Dh)
