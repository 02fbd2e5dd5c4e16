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

Serving (``drop_free``) skips the bins: its routed rows, sorted by expert,
go through one grouped matmul (``jax.lax.ragged_dot``) and nothing drops. A
layer may hold a share of the experts (``experts_held``): the router keeps
its full width and tokens routed to absent experts add nothing here.

The (E, C, D) buffer is the unit the sharding rules place: experts over the
'model' axis when E % tp == 0 (expert parallelism), else tensor-parallel over
the ffn dim within replicated experts.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ArchConfig, ParamBuilder, cache_layer, cache_write, shard


def init_moe(pb: ParamBuilder, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.experts_here
    return {
        "router": pb.dense((d, cfg.n_experts), ("embed", "experts"), scale=d**-0.5),
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
    batches. Drop-free (serving) calls use no bins at all."""
    if n_tokens <= cfg.moe_exact_tokens:
        return n_tokens
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


# the routing counters of one MoE call, in this order (``route`` in its aux)
ROUTE_COUNTERS = ("held", "absent", "dropped", "hit_experts")


def _dispatch(xf, gate_e, K, E, C):
    """Sort-based dispatch of one call's tokens.

    xf: (T, D); gate_e: (T, K), ids >= E for experts not held here. Returns
    (xe (E, C, D), slot_by_flat (T*K,), keep_count) where slot E*C is the
    overflow dump, which absent experts' slots go to as well."""
    T = xf.shape[0]
    flat_e = gate_e.reshape(-1)  # (T*K,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K) - starts[sorted_e]
    keep = (pos_in_e < C) & (sorted_e < E)
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    token_of = sort_idx // K
    xbuf = jnp.zeros((E * C + 1, xf.shape[1]), xf.dtype).at[dest].set(xf[token_of])
    xe = xbuf[: E * C].reshape(E, C, xf.shape[1])
    slot_by_flat = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(
        jnp.where(keep, dest, E * C).astype(jnp.int32)
    )
    return xe, slot_by_flat, keep.sum()


def _grouped_swiglu(p: dict, xs: jax.Array, sizes: jax.Array) -> jax.Array:
    """SwiGLU of rows sorted by expert, ``sizes[e]`` rows for held expert e
    and rows past their sum left out (0): a grouped matmul whose work is
    the routed rows, with no capacity bins."""
    g = jax.lax.ragged_dot(xs, p["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], sizes)
    return jax.lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"], sizes)


def moe_ffn(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (B, L, D)
    gmm: Optional[object] = None,  # grouped-matmul impl (Pallas on TPU)
    drop_free: bool = False,  # serving: every routed row, no capacity bins
):
    """Returns (y, aux) where aux carries the load-balancing loss terms, the
    call's routing counters (``route``, int32 in ``ROUTE_COUNTERS`` order)
    and each token's experts (``choice``, (B*L, top_k)).

    The router scores all ``cfg.n_experts``; this layer holds
    ``cfg.experts_here`` of them, from ``cfg.experts_first``, and computes
    their part of the result: a token's slot on an absent expert adds
    nothing (expert parallelism's share on one device, without its exchange)."""
    with jax.named_scope("moe_layer"):
        return _moe_ffn(p, cfg, x, gmm, drop_free)


def _moe_ffn(p, cfg, x, gmm, drop_free):
    B, L, D = x.shape
    E, K, Eh = cfg.n_experts, cfg.top_k, cfg.experts_here
    T = B * L
    xf = x.reshape(T, D)

    # 1. route (router math in f32 — routing is precision-sensitive)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_w, gate_e = jax.lax.top_k(probs, K)  # (T, K)
    if cfg.moe_renorm:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    local_e = gate_e - cfg.experts_first
    held = (local_e >= 0) & (local_e < Eh)
    local_e = jnp.where(held, local_e, Eh)  # absent experts sort last

    # aux loss (Switch): E * sum_e fraction_tokens_e * mean_prob_e
    onehot = jax.nn.one_hot(gate_e[:, 0], E, dtype=jnp.float32)  # top-1 fraction
    aux_loss = E * jnp.mean(probs.mean(0) * onehot.mean(0))
    n_held = held.sum()
    hits = jnp.bincount(local_e.reshape(-1), length=Eh)  # held experts' rows

    if drop_free:
        # 2-4. every routed row, sorted by expert, through a grouped matmul
        order = jnp.argsort(local_e.reshape(-1), stable=True)
        with jax.named_scope("jnp_moe_gmm"):
            h = _grouped_swiglu(p, xf[order // K], hits)
        back = jnp.zeros((T * K,), jnp.int32).at[order].set(jnp.arange(T * K, dtype=jnp.int32))
        y = h[back].reshape(T, K, D)
        kept = hits.sum()
    else:
        C = expert_capacity(T, cfg)

        # 2. dispatch
        xe, slot_by_flat, kept = _dispatch(xf, local_e, K, Eh, C)  # xe: (Eh, C, D)
        xe = shard(xe, "experts", None, None)

        # 3. grouped SwiGLU — the moe_gmm hook
        if gmm is not None:
            with jax.named_scope("pallas_moe_gmm"):
                h = gmm(xe, p["w_gate"], p["w_up"], p["w_down"])
        else:
            with jax.named_scope("jnp_moe_gmm"):
                g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
                u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
                h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])
        h = shard(h, "experts", None, None)

        # 4. combine: slot -> token, weighted sum over K. Operand and result
        # of the gather are constrained replicated so SPMD does not
        # distribute the gather over the model axis and all-reduce the
        # (T*K, D) result back.
        ybuf = jnp.concatenate([h.reshape(Eh * C, D), jnp.zeros((1, D), h.dtype)])
        ybuf = shard(ybuf, None, None)
        y = shard(ybuf[slot_by_flat], None, None).reshape(T, K, D)
    y = jnp.where(held[..., None], y * gate_w[..., None].astype(y.dtype), 0).sum(axis=1)

    dropped = n_held - kept
    route = jnp.stack([n_held, T * K - n_held, dropped, (hits > 0).sum()]).astype(jnp.int32)
    return y.reshape(B, L, D).astype(x.dtype), {
        "aux_loss": aux_loss,
        "dropped_frac": dropped.astype(jnp.float32) / (T * K),
        "route": route,
        "choice": gate_e.astype(jnp.int32),
    }


def init_route_state(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    """A serve cache's routing record for one MoE layer: ``route`` sums the
    counters of its prefill calls (row 0) and decode calls (row 1); ``choice``
    holds each cached position's experts (-1 where none came yet)."""
    return {
        "route": jnp.zeros((2, len(ROUTE_COUNTERS)), jnp.int32),
        "choice": jnp.full((batch, max_len, cfg.top_k), -1, jnp.int32),
    }


def record_route(stacked: dict, aux: dict, layer, positions: jax.Array) -> dict:
    """``stacked`` (every layer's routing record, see ``init_route_state``)
    with one call's counters added and its tokens' experts written, in place
    at ``layer``."""
    B, L = positions.shape
    row = int(L == 1)  # static: a prefill or a decode call
    route = cache_layer(stacked["route"], layer).at[row].add(aux["route"])
    choice = aux["choice"].reshape(B, L, -1)
    return {
        "route": cache_write(stacked["route"], route, layer),
        "choice": cache_write(stacked["choice"], choice, layer, 0, positions[0, 0]),
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
