"""Pallas TPU flash attention (causal / sliding-window / GQA).

TPU-native blocking: the grid is (batch x kv_head, q_blocks, kv_blocks) with
the KV axis innermost (sequential on TPU), so the online-softmax running
stats (m, l, acc) live in VMEM scratch and are carried across KV grid steps.
Q/K/V blocks are streamed HBM->VMEM by the BlockSpec index maps; the
(block_q, block_kv) score tile exists only in VMEM/VREGs — never in HBM.

GQA: the q-heads of one KV head are folded into the q-block rows (the kernel
sees q of shape (gq*block_q, d)) so KV tiles are fetched once per KV head —
no KV replication in VMEM.

Sliding-window / causal predication happens at two levels:
  1. whole-block skip via ``pl.when`` (no MXU work issued for dead tiles),
  2. elementwise masking on the boundary tiles.

Runs compiled on a TPU and interpreted elsewhere; tests compare both with
``ref.reference_attention``.
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


def _attn_kernel(
    q_ref,  # (1, gq*bq, d)
    k_ref,  # (1, bkv, d)
    v_ref,  # (1, bkv, d)
    o_ref,  # (1, gq*bq, d)
    m_scr,  # (gq*bq, 128) f32 running max
    l_scr,  # (gq*bq, 128) f32 running denom
    acc_scr,  # (gq*bq, d) f32 running numerator
    *,
    block_q: int,
    block_kv: int,
    seq_q: int,
    seq_kv: int,
    causal: bool,
    window: int,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level predication: any (q, k) pair live in this tile?
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_kv
    k_hi = k_lo + block_kv - 1
    live = k_lo < seq_kv
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # (gq*bq, d)
        k = k_ref[0]  # (bkv, d)
        v = v_ref[0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (gq*bq, bkv)
        # row r = (g, q): q position = q_lo + r % block_q; column c: k_lo + c
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_lo + jnp.remainder(r, block_q)
        k_pos = k_lo + c
        ok = (q_pos < seq_q) & (k_pos < seq_kv)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-37)[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Lq, H, Dh)
    k: jax.Array,  # (B, Lk, KVH, Dh)
    v: jax.Array,  # (B, Lk, KVH, Dh)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Lq, H, Dh = q.shape
    Lk, KVH = k.shape[1], k.shape[2]
    gq = H // KVH
    scale = Dh**-0.5

    block_q = min(block_q, Lq)
    block_kv = min(block_kv, Lk)
    nq = math.ceil(Lq / block_q)
    nk = math.ceil(Lk / block_kv)
    pad_q = nq * block_q - Lq
    pad_k = nk * block_kv - Lk

    # fold GQA: (B, L, H, D) -> (B*KVH, nq*gq*block_q, D) with row layout
    # (q_block, group, q_in_block) so one q-tile = (gq, block_q) rows and one
    # grid row owns exactly one KV head.
    qf = q.reshape(B, Lq, KVH, gq, Dh)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    qf = (
        qf.reshape(B, nq, block_q, KVH, gq, Dh)
        .transpose(0, 3, 1, 4, 2, 5)  # (B, KVH, nq, gq, bq, D)
        .reshape(B * KVH, nq * gq * block_q, Dh)
    )
    kf, vf = k, v
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kf = kf.transpose(0, 2, 1, 3).reshape(B * KVH, nk * block_kv, Dh)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * KVH, nk * block_kv, Dh)

    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q,
        block_kv=block_kv,
        seq_q=Lq,
        seq_kv=Lk,
        causal=causal,
        window=window,
        scale=scale,
    )
    qspec = pl.BlockSpec((1, gq * block_q, Dh), lambda b, qi, ki: (b, qi, 0))
    kvspec = pl.BlockSpec((1, block_kv, Dh), lambda b, qi, ki: (b, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, nq, nk),
        in_specs=[qspec, kvspec, kvspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * KVH, nq * gq * block_q, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gq * block_q, 128), jnp.float32),
            pltpu.VMEM((gq * block_q, 128), jnp.float32),
            pltpu.VMEM((gq * block_q, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)

    # unfold: (B*KVH, nq*gq*block_q, D) -> (B, Lq, H, D)
    out = out.reshape(B, KVH, nq, gq, block_q, Dh).transpose(0, 2, 4, 1, 3, 5)
    out = out.reshape(B, nq * block_q, H, Dh)
    if pad_q:
        out = out[:, :Lq]
    return out
