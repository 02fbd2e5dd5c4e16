"""Layout-driven transformer assembly.

A model is ``embed -> scan over G groups of layout positions -> norm -> head``
where the layout is a repeating tuple of (mixer, ffn) specs — dense GQA
(``internlm2``), MoE (``mixtral``), hybrid Mamba+attention (``jamba``),
attention-free SSM (``falcon-mamba``), MLA (``minicpm3``) and enc-dec
(``seamless``) are all the same assembly with different layouts.

Parameters for each layout position are stacked over the G groups and the
forward pass is a single ``lax.scan`` (per-group remat policy applies to the
scan body), so the compiled HLO is O(1) in depth.

The LM head / loss is computed in sequence chunks with the vocab dimension
shardable over the model axis — full (B, L, V) logits never materialize.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba as mb
from . import moe as moe_mod
from .common import (
    ArchConfig,
    LayerSpec,
    ParamBuilder,
    shard,
    split_tree,
    stack_groups,
)


# ---------------------------------------------------------------------------
# Layer init / apply
# ---------------------------------------------------------------------------


def init_layer(pb: ParamBuilder, cfg: ArchConfig, spec: LayerSpec, cross: bool) -> dict:
    p: dict = {"ln1": pb.ones((cfg.d_model,), ("embed",))}
    if spec.mixer == "attention":
        p["mixer"] = (
            attn.init_mla(pb, cfg) if cfg.attention == "mla" else attn.init_attention(pb, cfg)
        )
    elif spec.mixer == "mamba":
        p["mixer"] = mb.init_mamba(pb, cfg)
    else:
        raise ValueError(f"unknown mixer {spec.mixer}")
    if cross:
        p["ln_cross"] = pb.ones((cfg.d_model,), ("embed",))
        p["cross"] = attn.init_attention(pb, cfg)
    if spec.ffn == "dense":
        p["ln2"] = pb.ones((cfg.d_model,), ("embed",))
        p["ffn"] = moe_mod.init_dense_ffn(pb, cfg)
    elif spec.ffn == "moe":
        p["ln2"] = pb.ones((cfg.d_model,), ("embed",))
        p["ffn"] = moe_mod.init_moe(pb, cfg)
    elif spec.ffn != "none":
        raise ValueError(f"unknown ffn {spec.ffn}")
    return p


def _rms(x, w, eps):
    from .common import grad_cast, rms_norm

    # grad_cast keeps the backward cotangent in the activation dtype so the
    # tensor-parallel dx all-reduces move bf16 payloads (see common.grad_cast)
    return grad_cast(rms_norm(x, w, eps))


def apply_layer(
    p: dict,
    cfg: ArchConfig,
    spec: LayerSpec,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[dict],  # every layer's cache, stacked (G, ...)
    memory: Optional[jax.Array],  # encoder output for cross-attention
    kernels: Optional[dict] = None,
    layer: Optional[jax.Array] = None,  # this layer's slot in ``cache``
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    kernels = kernels or {}
    route = None  # an MoE layer's routing record rides in its layer's cache
    if cache is not None and "route" in cache:
        route = {k: cache[k] for k in ("route", "choice")}
        cache = {k: v for k, v in cache.items() if k not in route}
    h = _rms(x, p["ln1"], cfg.norm_eps)
    if spec.mixer == "attention":
        if cfg.attention == "mla":
            y, new_cache = attn.mla_block(p["mixer"], cfg, h, positions, cache, layer=layer)
        else:
            y, new_cache = attn.attention_block(
                p["mixer"], cfg, h, positions, cache, layer=layer
            )
    else:
        y, new_cache = mb.mamba_block(
            p["mixer"], cfg, h, positions, cache,
            scan_impl=kernels.get("mamba_scan"), layer=layer,
        )
    x = x + y

    if "cross" in p and memory is not None:
        h = _rms(x, p["ln_cross"], cfg.norm_eps)
        mk = jnp.einsum("btd,dhk->bthk", memory, p["cross"]["wk"])
        mv = jnp.einsum("btd,dhk->bthk", memory, p["cross"]["wv"])
        y, _ = attn.attention_block(
            p["cross"], cfg, h, positions, cache=None, cross_kv=(mk, mv)
        )
        x = x + y

    if "ffn" in p:
        h = _rms(x, p["ln2"], cfg.norm_eps)
        if spec.ffn == "moe":
            # serving (a cache) drops no routed row; training keeps capacity bins
            y, mo = moe_mod.moe_ffn(
                p["ffn"], cfg, h, gmm=kernels.get("moe_gmm"), drop_free=cache is not None
            )
            aux = aux + mo["aux_loss"]
            if route is not None:
                new_cache = {**new_cache, **moe_mod.record_route(route, mo, layer, positions)}
        else:
            y = moe_mod.dense_ffn(p["ffn"], h)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    """Functional model container: init + forward paths for one ArchConfig."""

    cfg: ArchConfig

    # -- init ---------------------------------------------------------------
    def init(self, key: jax.Array):
        """Returns (params, logical_axes) pytrees (same treedef)."""
        cfg = self.cfg
        pb = ParamBuilder(key, cfg.compute_dtype())
        tree: dict = {
            "embed": pb.dense((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=1.0),
            "final_norm": pb.ones((cfg.d_model,), ("embed",)),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = pb.dense((cfg.d_model, cfg.vocab), ("embed", "vocab"))
        cross = cfg.cross_attention
        tree["blocks"] = [
            stack_groups(
                [init_layer(pb, cfg, spec, cross) for _ in range(cfg.n_groups)]
            )
            for spec in cfg.layout
        ]
        if cfg.encoder_layers:
            enc_spec = LayerSpec(mixer="attention", ffn="dense")
            enc_cfg = dataclasses.replace(cfg, attention="full", cross_attention=False)
            tree["encoder"] = {
                "blocks": stack_groups(
                    [
                        init_layer(pb, enc_cfg, enc_spec, cross=False)
                        for _ in range(cfg.encoder_layers)
                    ]
                ),
                "norm": pb.ones((cfg.d_model,), ("embed",)),
            }
        return split_tree(tree)

    # -- encoder --------------------------------------------------------------
    def encode(self, params: dict, frames: jax.Array) -> jax.Array:
        """frames: (B, T, D) stub frontend embeddings -> (B, T, D) memory."""
        cfg = self.cfg
        enc_cfg = dataclasses.replace(cfg, attention="full", cross_attention=False)
        spec = LayerSpec(mixer="attention", ffn="dense")
        x = frames.astype(cfg.compute_dtype())
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

        def body(carry, p_g):
            h = _rms(carry, p_g["ln1"], cfg.norm_eps)
            q = jnp.einsum("bld,dhk->blhk", h, p_g["mixer"]["wq"])
            k = jnp.einsum("bld,dhk->blhk", h, p_g["mixer"]["wk"])
            v = jnp.einsum("bld,dhk->blhk", h, p_g["mixer"]["wv"])
            from .common import apply_rope

            q = apply_rope(q.swapaxes(1, 2), positions[:, None], cfg.rope_theta).swapaxes(1, 2)
            k = apply_rope(k.swapaxes(1, 2), positions[:, None], cfg.rope_theta).swapaxes(1, 2)
            o = attn.blocked_attention(
                q, k, v, causal=False, block_q=cfg.block_q, block_kv=cfg.block_kv
            )
            carry = carry + jnp.einsum("blhk,hkd->bld", o, p_g["mixer"]["wo"])
            h = _rms(carry, p_g["ln2"], cfg.norm_eps)
            carry = carry + moe_mod.dense_ffn(p_g["ffn"], h)
            return carry, None

        body = _maybe_remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return _rms(x, params["encoder"]["norm"], cfg.norm_eps)

    # -- decoder trunk ----------------------------------------------------------
    def trunk(
        self,
        params: dict,
        x: jax.Array,  # (B, L, D) embedded inputs
        positions: jax.Array,  # (B, L)
        caches: Optional[list] = None,  # per layout position, stacked (G,...)
        memory: Optional[jax.Array] = None,
        kernels: Optional[dict] = None,
    ):
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)

        if caches is None:

            def body(carry, p_gs):
                x, aux = carry
                for spec, p_g in zip(cfg.layout, p_gs):
                    x, _, a = apply_layer(p_g, cfg, spec, x, positions, None, memory, kernels)
                    aux = aux + a
                return (x, aux), None

            # remat only matters under autodiff: the serve path skips it
            (x, aux), _ = jax.lax.scan(
                _maybe_remat(body, cfg), (x, aux0), list(params["blocks"])
            )
            return x, aux, None

        # serve: the stacked caches ride in the carry and each layer writes
        # its new rows into them in place, at its index
        def serve_body(carry, xs):
            x, aux, cs = carry
            p_gs, g = xs
            new_cs = []
            for spec, p_g, c in zip(cfg.layout, p_gs, cs):
                x, c, a = apply_layer(p_g, cfg, spec, x, positions, c, memory, kernels, g)
                aux = aux + a
                new_cs.append(c)
            return (x, aux, new_cs), None

        (x, aux, caches), _ = jax.lax.scan(
            serve_body,
            (x, aux0, list(caches)),
            (list(params["blocks"]), jnp.arange(cfg.n_groups, dtype=jnp.int32)),
        )
        return x, aux, caches

    # -- heads --------------------------------------------------------------
    def embed(self, params: dict, tokens: jax.Array) -> jax.Array:
        x = params["embed"][tokens]  # (B, L, D)
        return shard(x, "batch", "seq", None)

    def logits(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = _rms(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        out = jnp.einsum("bld,dv->blv", x, w)
        return shard(out, "batch", "seq", "vocab")

    def chunked_loss(
        self,
        params: dict,
        x: jax.Array,  # (B, L, D) trunk output
        labels: jax.Array,  # (B, L) next-token ids, -1 = ignore
        chunk: int = 512,
    ) -> jax.Array:
        """Token-mean CE without materializing (B, L, V): scan over L-chunks;
        the V dim of each chunk's logits is shardable over 'model'."""
        cfg = self.cfg
        x = _rms(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        B, L, D = x.shape
        chunk = min(chunk, L)
        pad = (-L) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        nc = (L + pad) // chunk
        xb = x.reshape(B, nc, chunk, D).swapaxes(0, 1)
        lb = labels.reshape(B, nc, chunk).swapaxes(0, 1)

        def body(carry, inp):
            tot, cnt = carry
            xc, lc = inp
            logits = jnp.einsum("bld,dv->blv", xc, w).astype(jnp.float32)
            logits = shard(logits, "batch", "seq", "vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None].clip(0), axis=-1)[..., 0]
            mask = (lc != -1).astype(jnp.float32)
            return (tot + ((lse - gold) * mask).sum(), cnt + mask.sum()), None

        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xb, lb)
        )
        return tot / jnp.maximum(cnt, 1.0)

    # -- cache --------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> list:
        """Per layout position: stacked (G, ...) cache trees."""
        cfg = self.cfg
        dt = cfg.compute_dtype()

        def one(spec: LayerSpec):
            if spec.mixer == "mamba":
                c = mb.init_mamba_cache(cfg, batch, dt)
            elif cfg.attention == "mla":
                c = attn.init_mla_cache(cfg, batch, max_len, dt)
            else:
                c = attn.init_attention_cache(cfg, batch, max_len, dt)
            if spec.ffn == "moe":
                c = {**c, **moe_mod.init_route_state(cfg, batch, max_len)}
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_groups,) + a.shape), c
            )

        return [one(spec) for spec in cfg.layout]


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    # "block": save only big matmul outputs entering the block boundary
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
