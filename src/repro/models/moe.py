"""Mixture-of-Experts FFN with static sort-based dispatch.

Top-k routing with per-expert capacity bins (GShard-style drops, MegaBlocks-
style grouped matmul). Everything is static-shaped so the layer lowers under
pjit on any mesh:

  1. router: logits -> top-k (weight, expert) per token
  2. dispatch: stable-sort token-slots by expert, take the first C per expert
     (overflow dropped), scatter token vectors into an (E, C, D) buffer
  3. grouped matmul: SwiGLU per expert over its capacity bin — this einsum is
     the ``repro.kernels.moe_gmm`` Pallas hook
  4. combine: gather outputs back per token slot, weight, and sum over k

The (E, C, D) buffer is the unit the sharding rules place: experts over the
'model' axis when E % tp == 0 (expert parallelism), else tensor-parallel over
the ffn dim within replicated experts.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ArchConfig, ParamBuilder, shard


def init_moe(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": pb.dense((d, e), ("embed", "experts"), scale=d**-0.5),
        "w_gate": pb.dense((e, d, f), ("experts", "embed", "mlp")),
        "w_up": pb.dense((e, d, f), ("experts", "embed", "mlp")),
        "w_down": pb.dense((e, f, d), ("experts", "mlp", "embed")),
    }


def expert_capacity(n_tokens: int, cfg: ArchConfig) -> int:
    """Per-expert capacity bin size for an n_tokens dispatch call.

    Calls at or below ``cfg.moe_exact_tokens`` (decode steps — one token per
    sequence — and CPU smoke scale) get capacity = n_tokens: no expert can
    overflow (each token occupies an expert at most once), so the dispatch
    is *drop-free* and decode logits match the teacher-forced trunk exactly.
    Above the threshold — statistical scale, where load balancing holds —
    capacity is proportional (``capacity_factor``) and overflow tokens are
    dropped (GShard semantics, a throughput lever). The threshold is kept at
    decode scale (512) deliberately: raising it would silently change
    training numerics and grow the (E, C, D) dispatch buffers for mid-size
    batches."""
    if n_tokens <= cfg.moe_exact_tokens:
        return n_tokens
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def _dispatch(xf, gate_e, K, E, C):
    """Sort-based dispatch for one token group.

    xf: (T, D); gate_e: (T, K). Returns (xe (E, C, D), slot_by_flat (T*K,),
    keep_count) where slot E*C is the overflow dump."""
    T = xf.shape[0]
    flat_e = gate_e.reshape(-1)  # (T*K,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    token_of = sort_idx // K
    xbuf = jnp.zeros((E * C + 1, xf.shape[1]), xf.dtype).at[dest].set(xf[token_of])
    xe = xbuf[: E * C].reshape(E, C, xf.shape[1])
    slot_by_flat = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(
        jnp.where(keep, dest, E * C).astype(jnp.int32)
    )
    return xe, slot_by_flat, keep.sum()


def moe_ffn(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, D)
    gmm: Optional[object] = None,  # grouped-matmul impl (Pallas on TPU)
):
    """Returns (y, aux) where aux carries the load-balancing loss terms.

    ``cfg.moe_groups > 1`` enables GShard-style group-local dispatch: tokens
    split into G groups aligned with the data shards, each group sorted and
    capacity-binned locally, so the dispatch scatter never crosses the data
    axis and per-device gemm work is 1/G of the global capacity (the baseline
    global sort makes every device touch every token when experts cannot
    shard — e.g. mixtral's 8 experts on a 16-way model axis)."""
    B, L, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * L
    G = max(1, cfg.moe_groups)
    if T % G:
        G = 1
    Tg = T // G
    C = expert_capacity(Tg, cfg)
    xf = x.reshape(T, D)

    # 1. route (router math in f32 — routing is precision-sensitive)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_w, gate_e = jax.lax.top_k(probs, K)  # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)  # renorm

    # aux loss (Switch): E * sum_e fraction_tokens_e * mean_prob_e
    onehot = jax.nn.one_hot(gate_e[:, 0], E, dtype=jnp.float32)  # top-1 fraction
    aux_loss = E * jnp.mean(probs.mean(0) * onehot.mean(0))

    # 2. dispatch (per group, vmapped; G=1 == the global baseline)
    xg = xf.reshape(G, Tg, D)
    eg = gate_e.reshape(G, Tg, K)
    xe, slot_by_flat, kept = jax.vmap(
        lambda xx, ee: _dispatch(xx, ee, K, E, C)
    )(xg, eg)  # xe: (G, E, C, D)
    xe = shard(xe, "moe_group", "experts", None, None)

    # 3. grouped SwiGLU — the moe_gmm hook
    if gmm is not None and G == 1:
        with jax.named_scope("pallas_moe_gmm"):
            h = gmm(xe[0], p["w_gate"], p["w_up"], p["w_down"])[None]
    else:
        with jax.named_scope("jnp_moe_gmm"):
            g = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
            u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
            h = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u, p["w_down"])
    h = shard(h, "moe_group", "experts", None, None)

    # 4. combine: slot -> token, weighted sum over K (per group). The gather
    # stays group-local: constrain operand and result to the group sharding
    # so SPMD does not distribute the gather over the model axis and
    # all-reduce the (Tg*K, D) result back.
    hb = h.reshape(G, E * C, D)
    ybuf = jnp.concatenate([hb, jnp.zeros((G, 1, D), h.dtype)], axis=1)
    ybuf = shard(ybuf, "moe_group", None, None)
    y = jnp.take_along_axis(
        ybuf, slot_by_flat[..., None].astype(jnp.int32), axis=1
    )  # (G, Tg*K, D)
    y = shard(y, "moe_group", None, None)
    y = y.reshape(T, K, D)
    y = (y * gate_w[..., None].astype(y.dtype)).sum(axis=1)

    dropped = (T * K) - kept.sum()
    return y.reshape(B, L, D).astype(x.dtype), {
        "aux_loss": aux_loss,
        "dropped_frac": dropped.astype(jnp.float32) / (T * K),
    }


def init_dense_ffn(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": pb.dense((d, f), ("embed", "mlp")),
        "w_up": pb.dense((d, f), ("embed", "mlp")),
        "w_down": pb.dense((f, d), ("mlp", "embed")),
    }


def dense_ffn(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bld,df->blf", x, p["w_gate"])
    u = jnp.einsum("bld,df->blf", x, p["w_up"])
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", "seq", "mlp")
    return jnp.einsum("blf,fd->bld", h, p["w_down"])
