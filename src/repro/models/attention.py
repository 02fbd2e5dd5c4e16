"""Attention mixers: full / sliding-window / MLA, GQA-aware, blocked.

Two execution paths share one math definition:

  - ``blocked_attention`` — pure-jnp online-softmax attention, scanned over
    KV blocks (and Q blocks). This is what the multi-pod dry-run lowers: the
    compiled HLO never materializes an (Lq, Lkv) score matrix, so the memory
    analysis is honest about what a fused kernel would use.
  - ``repro.kernels.flash_attention`` — the Pallas TPU kernel with the same
    blocking scheme (HBM->VMEM streaming). Selected by ``cfg.use_pallas``.

GQA is computed grouped — KV heads are never repeated in memory: scores are
einsummed as (B, KVH, Gq, Lq, Lkv) against unexpanded KV.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import (
    ArchConfig,
    ParamBuilder,
    apply_rope,
    cache_layer,
    cache_write,
    rms_norm,
    shard,
)

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Blocked online-softmax attention (reference shared by train & prefill)
# ---------------------------------------------------------------------------


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Lq, KVH, Gq, Dh), k: (B, Lk, KVH, Dh) -> (B, KVH, Gq, Lq, Lk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """Additive bias (Lq, Lk): 0 where attendable, NEG_INF elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def blocked_attention(
    q: jax.Array,  # (B, Lq, H, Dh)
    k: jax.Array,  # (B, Lk, KVH, Dh)
    v: jax.Array,  # (B, Lk, KVH, Dh)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
    causal_skip: bool = False,  # hillclimb lever: unrolled growing-window
) -> jax.Array:
    """Online-softmax attention, O(block) memory. Returns (B, Lq, H, Dv).

    v's head dim may differ from q/k's (MLA: Dk=96, Dv=64)."""
    with jax.named_scope("jnp_attention"):
        return _blocked_attention(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_kv=block_kv, causal_skip=causal_skip,
        )


def _blocked_attention(q, k, v, *, causal, window, block_q, block_kv, causal_skip):
    B, Lq, H, Dh = q.shape
    Lk, KVH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    Gq = H // KVH
    qg = q.reshape(B, Lq, KVH, Gq, Dh)
    scale = Dh**-0.5

    if causal_skip and causal and Lq == Lk and Lq % block_q == 0:
        out = _causal_skip_attention(qg, k, v, scale, block_q, block_kv, window)
        return out.reshape(B, Lq, H, Dv).astype(q.dtype)

    block_kv = min(block_kv, Lk)
    nkv = -(-Lk // block_kv)
    pad_k = nkv * block_kv - Lk
    kv_ok = jnp.arange(nkv * block_kv) < Lk  # (nkv*bkv,) padding validity
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kb = k.reshape(B, nkv, block_kv, KVH, Dh).swapaxes(0, 1)
    vb = v.reshape(B, nkv, block_kv, KVH, Dv).swapaxes(0, 1)
    kidx = jnp.arange(nkv * block_kv).reshape(nkv, block_kv)
    okb = kv_ok.reshape(nkv, block_kv)

    def one_q_block(qblk: jax.Array, q_pos: jax.Array) -> jax.Array:
        # qblk: (B, bq, KVH, Gq, Dh); scan over kv blocks w/ running stats
        bq = qblk.shape[1]
        acc0 = jnp.zeros((B, KVH, Gq, bq, Dv), jnp.float32)
        m0 = jnp.full((B, KVH, Gq, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, Gq, bq), jnp.float32)

        def body(carry, inp):
            acc, m, l = carry
            kblk, vblk, ki, okk = inp
            s = _gqa_scores(qblk, kblk) * scale  # (B,KVH,Gq,bq,bkv) f32
            bias = _mask_bias(q_pos, ki, causal, window)
            bias = bias + jnp.where(okk, 0.0, NEG_INF)[None, :]
            s = s + bias
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk, preferred_element_type=jnp.float32
            )
            return (acc_new, m_new, l_new), None

        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, kidx, okb))
        out = acc / jnp.maximum(l, 1e-37)[..., None]  # (B,KVH,Gq,bq,Dh)
        return out.transpose(0, 3, 1, 2, 4)  # (B,bq,KVH,Gq,Dh)

    if Lq <= block_q:
        out = one_q_block(qg, jnp.arange(Lq))
    else:
        bq = block_q
        nq = -(-Lq // bq)
        pad_q = nq * bq - Lq
        qp = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0))) if pad_q else qg
        qblocks = qp.reshape(B, nq, bq, KVH, Gq, Dh).swapaxes(0, 1)
        qpos = jnp.arange(nq * bq).reshape(nq, bq)

        def qbody(_, inp):
            qblk, qpo = inp
            return None, one_q_block(qblk, qpo)

        _, outs = jax.lax.scan(qbody, None, (qblocks, qpos))
        out = outs.swapaxes(0, 1).reshape(B, nq * bq, KVH, Gq, Dv)
        if pad_q:
            out = out[:, :Lq]
    return out.reshape(B, Lq, H, Dv).astype(q.dtype)


def _causal_skip_attention(qg, k, v, scale, block_q, block_kv, window):
    """Beyond-baseline lever: unrolled Python loop over Q blocks, each slicing
    only the causally-visible KV prefix — compiled FLOPs ~ N^2/2 instead of
    N^2 (the masked-full baseline). SWA additionally drops the out-of-window
    prefix so compiled FLOPs ~ N*W."""
    B, Lq, KVH, Gq, Dh = qg.shape
    nq = Lq // block_q
    outs = []
    for i in range(nq):
        qblk = jax.lax.slice_in_dim(qg, i * block_q, (i + 1) * block_q, axis=1)
        lo = 0
        if window > 0:
            # earliest K any q-row in this block can see: q_lo - window + 1
            lo = max(0, i * block_q - window + 1)
            lo = (lo // block_kv) * block_kv  # block-align downwards
        hi = (i + 1) * block_q
        kblk = jax.lax.slice_in_dim(k, lo, hi, axis=1)
        vblk = jax.lax.slice_in_dim(v, lo, hi, axis=1)
        s = _gqa_scores(qblk, kblk) * scale
        qpos = i * block_q + jnp.arange(block_q)
        kpos = lo + jnp.arange(hi - lo)
        s = s + _mask_bias(qpos, kpos, True, window)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk, preferred_element_type=jnp.float32)
        outs.append(o.transpose(0, 3, 1, 2, 4))
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Standard (GQA / SWA / QKV-bias) attention block
# ---------------------------------------------------------------------------


def _zero_pad_rows(pair, n_real: int):
    w, axes = pair
    return w.at[n_real:].set(0), axes


def init_attention(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    d, H, KVH, Dh = cfg.d_model, cfg.n_heads_eff, cfg.n_kv_heads, cfg.head_dim
    assert H % KVH == 0, f"padded heads {H} must stay a multiple of kv={KVH}"
    # 3-D weights name their fan-in: the contracted dims, not shape[-2]
    p = {
        "wq": pb.dense((d, H, Dh), ("embed", "heads", "head_dim"), scale=d**-0.5),
        "wk": pb.dense((d, KVH, Dh), ("embed", "kv_heads", "head_dim"), scale=d**-0.5),
        "wv": pb.dense((d, KVH, Dh), ("embed", "kv_heads", "head_dim"), scale=d**-0.5),
        "wo": pb.dense(
            (H, Dh, d), ("heads", "head_dim", "embed"), scale=(cfg.n_heads * Dh) ** -0.5
        ),
    }
    if cfg.pad_heads:
        p["wo"] = _zero_pad_rows(p["wo"], cfg.n_heads)
    if cfg.qkv_bias:
        p["bq"] = pb.zeros((H, Dh), ("heads", "head_dim"))
        p["bk"] = pb.zeros((KVH, Dh), ("kv_heads", "head_dim"))
        p["bv"] = pb.zeros((KVH, Dh), ("kv_heads", "head_dim"))
    return p


def _project_qkv(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    # RoPE on q/k (positions broadcast over heads)
    q = apply_rope(q.swapaxes(1, 2), positions[:, None], cfg.rope_theta).swapaxes(1, 2)
    k = apply_rope(k.swapaxes(1, 2), positions[:, None], cfg.rope_theta).swapaxes(1, 2)
    return q, k, v


def attention_block(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, D)
    positions: jax.Array,  # (B, L) absolute positions
    cache: Optional[dict] = None,  # see init_attention_cache, stacked (G, ...)
    cross_kv: Optional[tuple] = None,  # (k, v) encoder memory for cross-attn
    layer: Optional[jax.Array] = None,  # this layer's slot in ``cache``
):
    """Self-attention with optional KV cache (decode) — returns (y, new_cache).

    ``cache`` holds every layer's cache stacked over the scan groups; this
    layer writes its new rows at ``layer`` in place and attends over its
    slot where it lies."""
    B, L, _ = x.shape
    if cross_kv is not None:
        q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
        k, v = cross_kv
        out = blocked_attention(
            q, k, v, causal=False, block_q=cfg.block_q, block_kv=cfg.block_kv
        )
        y = jnp.einsum("blhk,hkd->bld", out, p["wo"])
        return y, cache

    q, k, v = _project_qkv(p, cfg, x, positions)
    q = shard(q, "batch", "seq", "heads", None)

    if cache is None:
        out = blocked_attention(
            q,
            k,
            v,
            causal=True,
            window=cfg.window if cfg.attention == "swa" else 0,
            block_q=cfg.block_q,
            block_kv=cfg.block_kv,
            causal_skip=cfg.causal_skip,
        )
        new_cache = None
    else:
        heads_major, Dp = kv_cache_layout(cfg)
        seq = 2 if heads_major else 1  # slot axis of one layer's (B, ., ., Dp) cache
        S = cache["k"].shape[1 + seq]
        Dh = k.shape[-1]
        idx = cache_layer(cache["index"], layer)  # int32: tokens already cached
        total = idx + L

        def stored(t):  # (B, L, KVH, Dh) rows as the cache holds them
            t = jnp.pad(t, ((0, 0),) * 3 + ((0, Dp - Dh),)).astype(cache["k"].dtype)
            return jnp.moveaxis(t, 1, seq)

        if "pos" in cache:  # SWA ring buffer of size W
            wpos = jnp.mod(idx + jnp.arange(L), S)  # (L,)
            # the index arrays (layer, wpos) put the L rows first
            at = (layer,) + (slice(None),) * seq + (wpos,)
            ck = cache["k"].at[at].set(jnp.moveaxis(stored(k), seq, 0))
            cv = cache["v"].at[at].set(jnp.moveaxis(stored(v), seq, 0))
            kpos = cache["pos"].at[layer, :, wpos].set(positions.T)
            valid = jnp.arange(S)[None, :] < total  # ring: slot written yet?
            out = _cached_attention(
                q, cache_layer(ck, layer), cache_layer(cv, layer),
                cache_layer(kpos, layer), positions, valid, cfg,
            )
            new_cache = {"k": ck, "v": cv, "pos": kpos}
        else:
            at = (0,) * seq + (idx,)
            ck = cache_write(cache["k"], stored(k), layer, *at)
            cv = cache_write(cache["v"], stored(v), layer, *at)
            k_l, v_l = cache_layer(ck, layer), cache_layer(cv, layer)
            if L > 1:
                # prefill: blocked attention over the cache (slots >= L are
                # causally dead for a fresh cache; prefill starts at idx=0)
                out = blocked_attention(
                    q, jnp.moveaxis(k_l, seq, 1)[..., :Dh], jnp.moveaxis(v_l, seq, 1)[..., :Dh],
                    causal=True,
                    window=cfg.window if cfg.attention == "swa" else 0,
                    block_q=cfg.block_q, block_kv=cfg.block_kv,
                    causal_skip=cfg.causal_skip,
                )
            else:
                valid = jnp.arange(S)[None, :] < total
                kpos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
                out = _cached_attention(q, k_l, v_l, kpos, positions, valid, cfg)
            new_cache = {"k": ck, "v": cv}
        new_cache["index"] = cache_write(cache["index"], total, layer)

    out = shard(out, "batch", "seq", "heads", None)
    y = jnp.einsum("blhk,hkd->bld", out, p["wo"])
    return y, new_cache


def _cached_attention(q, k, v, k_pos, q_pos, valid, cfg: ArchConfig):
    """Decode-path attention over one layer's (possibly ring) cache with
    explicit per-slot positions. q: (B, L, H, Dh); k/v as the cache stores
    them (``kv_cache_layout``), head dim padded. The cache's seq axis may be
    sharded (flash-decoding layout) — the reductions below then lower to
    per-shard partial softmax + cross-shard combine."""
    with jax.named_scope("jnp_attention"):
        return _cached_attention_impl(q, k, v, k_pos, q_pos, valid, cfg)


def _cached_attention_impl(q, k, v, k_pos, q_pos, valid, cfg: ArchConfig):
    B, L, H, Dh = q.shape
    heads_major, Dp = kv_cache_layout(cfg)
    kv = "bhkd" if heads_major else "bkhd"
    KVH = cfg.n_kv_heads
    Gq = H // KVH
    # zero lanes past Dh add nothing to the scores, and their outputs are cut
    qg = jnp.pad(q, ((0, 0),) * 3 + ((0, Dp - Dh),)).reshape(B, L, KVH, Gq, Dp)
    s = jnp.einsum(f"bqhgd,{kv}->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * (Dh**-0.5)  # (B,KVH,Gq,L,S)
    ok = k_pos[:, None, :] <= q_pos[:, :, None]  # (B, L, S) causal
    if cfg.attention == "swa" and cfg.window > 0:
        ok &= k_pos[:, None, :] > (q_pos[:, :, None] - cfg.window)
    ok &= valid[:, None, :]
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None]  # (B,1,1,L,S)
    pw = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum(f"bhgqk,{kv}->bhgqd", pw, v, preferred_element_type=jnp.float32)
    out = out[..., :Dh].transpose(0, 3, 1, 2, 4)
    return out.reshape(B, L, H, Dh).astype(q.dtype)


LANES = 128  # a TPU vector register's lanes: the tile width of a minor dim


def kv_cache_layout(cfg: ArchConfig) -> tuple:
    """(heads_major, padded head dim) of the attention KV cache.

    Chosen so that the TPU's default layout of the stored cache is the one
    the decode step works in, so that XLA re-lays no cache-sized buffer at a
    step's entry or exit:

    - the head dim is padded with zeros to whole lanes: the layout decode
      reads pads a narrower minor dim in memory anyway, and a logical shape
      that holds the padding keeps the default layout from moving the slot
      axis minor instead;
    - with several query heads per KV head (GQA) the scores are a matmul per
      KV head, which XLA lays out heads-major, ``(B, KVH, S, Dp)``; with one
      (MHA) they are a multiply-reduce, laid out slot-major, ``(B, S, KVH, Dp)``.
    """
    heads_major = cfg.n_heads_eff // cfg.n_kv_heads > 1
    return heads_major, -(-cfg.head_dim // LANES) * LANES


def init_attention_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    S = min(max_len, cfg.window) if (cfg.attention == "swa" and cfg.window) else max_len
    heads_major, Dp = kv_cache_layout(cfg)
    KVH = cfg.n_kv_heads
    shape = (batch, KVH, S, Dp) if heads_major else (batch, S, KVH, Dp)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "index": jnp.zeros((), jnp.int32),
    }
    if cfg.attention == "swa" and cfg.window and S == cfg.window:
        cache["pos"] = jnp.full((batch, S), -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------
#
# The KV cache stores only the compressed latent c_kv (kv_lora_rank) plus the
# shared rotary key k_rope (qk_rope_dim) — the Koalja transport insight
# applied to attention state: cache the *reference* (latent), not the payload
# (full per-head KV). Scores are computed "absorbed": q is projected into
# latent space so per-head K is never reconstituted for the cache.


def init_mla(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    d, H = cfg.d_model, cfg.n_heads_eff
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p = {
        "wq_a": pb.dense((d, qr), ("embed", "q_lora")),
        "q_norm": pb.ones((qr,), ("q_lora",)),
        "wq_b": pb.dense((qr, H, dn + dr), ("q_lora", "heads", "head_dim"), scale=qr**-0.5),
        "wkv_a": pb.dense((d, kvr + dr), ("embed", "kv_lora")),
        "kv_norm": pb.ones((kvr,), ("kv_lora",)),
        "wk_b": pb.dense((kvr, H, dn), ("kv_lora", "heads", "head_dim"), scale=kvr**-0.5),
        "wv_b": pb.dense((kvr, H, dv), ("kv_lora", "heads", "head_dim"), scale=kvr**-0.5),
        "wo": pb.dense(
            (H, dv, d), ("heads", "head_dim", "embed"), scale=(cfg.n_heads * dv) ** -0.5
        ),
    }
    if cfg.pad_heads:
        p["wo"] = _zero_pad_rows(p["wo"], cfg.n_heads)
    return p


def mla_block(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[dict] = None,  # see init_mla_cache, stacked (G, ...)
    layer: Optional[jax.Array] = None,  # this layer's slot in ``cache``
):
    B, L, _ = x.shape
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    kvr = cfg.kv_lora_rank

    cq = rms_norm(jnp.einsum("bld,dr->blr", x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("blr,rhk->blhk", cq, p["wq_b"])  # (B,L,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope.swapaxes(1, 2), positions[:, None], cfg.rope_theta).swapaxes(1, 2)

    ckv_full = jnp.einsum("bld,dr->blr", x, p["wkv_a"])  # (B,L,kvr+dr)
    c_kv = rms_norm(ckv_full[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = ckv_full[..., kvr:]  # (B,L,dr) shared across heads
    k_rope = apply_rope(k_rope[:, None], positions[:, None], cfg.rope_theta)[:, 0]

    if cache is None:
        # train / prefill: reconstitute per-head K,V once and run blocked
        # attention (scores never materialized at (L, L)).
        H = cfg.n_heads_eff
        k_nope = jnp.einsum("blr,rhk->blhk", c_kv, p["wk_b"])  # (B,L,H,dn)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, L, H, dr))], axis=-1
        )
        v_full = jnp.einsum("blr,rhk->blhk", c_kv, p["wv_b"])  # (B,L,H,dv)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = blocked_attention(
            q_full, k_full, v_full, causal=True,
            block_q=cfg.block_q, block_kv=cfg.block_kv,
            causal_skip=cfg.causal_skip,
        )
        y = jnp.einsum("blhk,hkd->bld", o, p["wo"])
        return y, None

    idx = cache_layer(cache["index"], layer)
    total = idx + L
    new_cache = {
        "c_kv": cache_write(cache["c_kv"], c_kv, layer, 0, idx),
        "k_rope": cache_write(cache["k_rope"], k_rope, layer, 0, idx),
        "index": cache_write(cache["index"], total, layer),
    }
    c_kv = cache_layer(new_cache["c_kv"], layer)
    k_rope = cache_layer(new_cache["k_rope"], layer)
    S = c_kv.shape[1]

    if L > 1:
        # prefill into the latent cache: reconstitute per-head K/V from the
        # cached latents and run blocked attention (absorbed scores would
        # materialize (L, S) — fine for decode, catastrophic for prefill).
        H = cfg.n_heads_eff
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, dr))], axis=-1
        )
        v_full = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = blocked_attention(
            q_full, k_full, v_full, causal=True,
            block_q=cfg.block_q, block_kv=cfg.block_kv,
            causal_skip=cfg.causal_skip,
        )
        y = jnp.einsum("blhk,hkd->bld", o, p["wo"])
        return y, new_cache

    # decode: absorbed attention over the latent cache —
    # q_nope^T (W_kb c) = (q_nope W_kb)^T c, so the cache holds only latents.
    valid = jnp.arange(S)[None, :] < total
    kpos = jnp.arange(S)[None, :]
    ok = (kpos[:, None, :] <= positions[:, :, None]) & valid[:, None, :]

    q_lat = jnp.einsum("blhk,rhk->blhr", q_nope, p["wk_b"])  # (B,L,H,kvr)
    scale = (dn + dr) ** -0.5
    s = (
        jnp.einsum("blhr,bsr->bhls", q_lat, c_kv, preferred_element_type=jnp.float32)
        + jnp.einsum("blhk,bsk->bhls", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * scale
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None]
    pw = jax.nn.softmax(s.astype(jnp.float32), axis=-1)  # (B,H,L,S)
    o_lat = jnp.einsum("bhls,bsr->blhr", pw, c_kv, preferred_element_type=jnp.float32)
    o = jnp.einsum("blhr,rhk->blhk", o_lat.astype(x.dtype), p["wv_b"])  # (B,L,H,dv)
    y = jnp.einsum("blhk,hkd->bld", o, p["wo"])
    return y, new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "index": jnp.zeros((), jnp.int32),
    }
