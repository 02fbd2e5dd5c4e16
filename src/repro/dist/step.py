"""Jitted, sharded train/serve step builders.

``make_train_step`` and ``make_serve_fns`` take a Model plus a mesh and
return donated jitted functions together with the shape/shard trees the
callers need for checkpointing, dry-run lowering (``jit(...).lower(ghost
shapes).compile()``), and per-device memory accounting. The model code never
sees the mesh — logical axis rules are installed around the traced call
(``axis_rules``) so the ``shard()`` hints inside the model bind here.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.common import axis_rules
from repro.models.registry import (
    decode_step as _decode_step,
    init_serve_state,
    prefill as _prefill,
    train_loss,
)
from repro.optim import adamw_init, adamw_update, ef_compress

from .sharding import cache_logical_axes, make_rules, pspec_for_axes, shardings_for


# ---------------------------------------------------------------------------
# Shape / spec trees
# ---------------------------------------------------------------------------


def param_specs(model):
    """(ShapeDtypeStruct tree, logical-axes tree) for the model's params —
    derived abstractly (no parameter is ever allocated)."""
    captured = {}

    def _init(key):
        params, axes = model.init(key)
        captured["axes"] = axes
        return params

    shapes = jax.eval_shape(_init, jax.random.key(0))
    return shapes, captured["axes"]


def make_train_state_specs(model):
    """(state shapes, state logical axes) for {params, opt, step}.

    AdamW moments mirror the param tree, so they inherit the param axes —
    FSDP shards optimizer state exactly like the weights (ZeRO posture)."""
    pshapes, paxes = param_specs(model)
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    state_shapes = {
        "params": pshapes,
        "opt": {
            "m": jax.tree.map(f32, pshapes),
            "v": jax.tree.map(f32, pshapes),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_axes = {
        "params": paxes,
        "opt": {"m": paxes, "v": paxes, "count": ()},
        "step": (),
    }
    return state_shapes, state_axes


def init_train_state(model, key) -> dict:
    """Fresh {params, opt, step} from ``key``. Jit it with the state
    shardings as ``out_shardings`` so the state is made where it lives,
    never whole on one device."""
    params, _ = model.init(key)
    return {
        "params": params,
        "opt": adamw_init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_batch_specs(cfg, kind: str, global_batch: int, seq_len: int) -> dict:
    """Ghost batch (ShapeDtypeStructs) for one input shape cell."""
    sds = jax.ShapeDtypeStruct
    batch = {"tokens": sds((global_batch, seq_len), jnp.int32)}
    if kind == "train":
        batch["labels"] = sds((global_batch, seq_len), jnp.int32)
    if cfg.encoder_layers:
        batch["frames"] = sds(
            (global_batch, cfg.frontend_len, cfg.d_model), cfg.compute_dtype()
        )
    if cfg.frontend == "vision":
        batch["prefix"] = sds(
            (global_batch, cfg.frontend_len, cfg.d_model), cfg.compute_dtype()
        )
    return batch


def _batch_shardings(cfg, kind: str, rules: dict, mesh) -> dict:
    tok = NamedSharding(mesh, P(rules.get("batch"), None))
    three = NamedSharding(mesh, P(rules.get("batch"), None, None))
    out = {"tokens": tok}
    if kind == "train":
        out["labels"] = tok
    if cfg.encoder_layers:
        out["frames"] = three
    if cfg.frontend == "vision":
        out["prefix"] = three
    return out


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(
    model,
    mesh,
    schedule: Callable,
    *,
    rules: Optional[dict] = None,
    global_batch: int,
    microbatches: int = 1,
    compress_pods: bool = False,
):
    """Build the donated, sharded train step.

    Returns (jitted, state_shapes, state_shard, batch_shard) where
    ``jitted(state, batch) -> (state, metrics)`` donates its state argument.

    microbatches > 1 accumulates gradients over equal batch splits (mean of
    per-microbatch means == full-batch mean when splits are equal).
    compress_pods applies int8 error-feedback compression to the gradient
    payload crossing the ``pod`` axis (adds a ``compress`` residual tree to
    the state).
    """
    cfg = model.cfg
    rules = dict(rules) if rules is not None else make_rules(cfg, mesh, "train", global_batch)
    state_shapes, state_axes = make_train_state_specs(model)

    compress = bool(compress_pods) and dict(mesh.shape).get("pod", 1) > 1
    if compress:
        f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
        state_shapes["compress"] = {
            "residual": jax.tree.map(f32, state_shapes["params"])
        }
        state_axes["compress"] = {"residual": state_axes["params"]}
    n_pods = dict(mesh.shape).get("pod", 1)

    state_shard = shardings_for(state_axes, state_shapes, rules, mesh)
    batch_shard = _batch_shardings(cfg, "train", rules, mesh)

    if microbatches > 1 and global_batch % microbatches != 0:
        raise ValueError(
            f"global_batch {global_batch} not divisible by microbatches {microbatches}"
        )

    def train_step(state, batch):
        with axis_rules(rules, mesh):
            lr = schedule(state["step"]).astype(jnp.float32)
            grad_fn = jax.value_and_grad(
                lambda p, b: train_loss(model, p, b), has_aux=True
            )

            if microbatches > 1:
                mb = jax.tree.map(
                    lambda x: x.reshape(
                        (microbatches, x.shape[0] // microbatches) + x.shape[1:]
                    ),
                    batch,
                )

                def acc(carry, b):
                    gsum, lsum = carry
                    (l, _), g = grad_fn(state["params"], b)
                    gsum = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), gsum, g
                    )
                    return (gsum, lsum + l), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
                )
                (gsum, lsum), _ = jax.lax.scan(acc, (zeros, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / microbatches, gsum)
                loss = lsum / microbatches
            else:
                (loss, _), grads = grad_fn(state["params"], batch)

            new_state = {}
            if compress:
                # gradients crossing the slow pod links go int8 + error
                # feedback; in-pod reductions stay f32 (XLA native)
                gspecs = jax.tree.map(lambda s: s.spec, state_shard["params"])
                cspecs = {"residual": gspecs}
                grads, cstate, _ = jax.shard_map(
                    functools.partial(ef_compress, axis_name="pod", n_pods=n_pods),
                    mesh=mesh,
                    in_specs=(gspecs, cspecs),
                    out_specs=(gspecs, cspecs, P()),
                    check_vma=False,
                )(grads, state["compress"])
                new_state["compress"] = cstate

            new_params, new_opt, om = adamw_update(
                state["params"], grads, state["opt"], lr
            )
            new_state.update(
                {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
            )
            metrics = {
                "loss": loss,
                "lr": lr,
                "grad_norm": om["grad_norm"],
                "clip_scale": om["clip_scale"],
            }
        return new_state, metrics

    jitted = jax.jit(
        train_step,
        in_shardings=(state_shard, batch_shard),
        out_shardings=(state_shard, None),
        donate_argnums=0,
    )
    return jitted, state_shapes, state_shard, batch_shard


# ---------------------------------------------------------------------------
# Serve fns (prefill + decode)
# ---------------------------------------------------------------------------


def make_serve_fns(
    model,
    mesh,
    *,
    max_len: int,
    global_batch: int,
    rules: Optional[dict] = None,
):
    """Build jitted (prefill, decode) against sharded KV/SSM caches.

    Returns (prefill_jit, decode_jit, st_shapes, shards):
      prefill_jit(params, tokens, state, frames=None, prefix=None)
      decode_jit(params, tokens, state)
    both donate their state argument. ``shards`` = {"params": ...,
    "state": {"caches": ..., "t": ...}} (NamedShardings for accounting).

    Shardings are applied as in-function constraints (not ``in_shardings``)
    so callers may thread extra state entries (e.g. encoder "memory")
    through untouched.
    """
    cfg = model.cfg
    rules = dict(rules) if rules is not None else make_rules(cfg, mesh, "serve", global_batch)
    pshapes, paxes = param_specs(model)
    param_shard = shardings_for(paxes, pshapes, rules, mesh)

    st_shapes = jax.eval_shape(lambda: init_serve_state(model, global_batch, max_len))
    cache_axes = cache_logical_axes(cfg, max_len)
    cache_shard = shardings_for(cache_axes, st_shapes["caches"], rules, mesh)
    state_shard = {"caches": cache_shard, "t": NamedSharding(mesh, P())}
    shards = {"params": param_shard, "state": state_shard}
    logits_shard = NamedSharding(
        mesh, pspec_for_axes(("batch", "vocab"), (global_batch, cfg.vocab), rules, mesh)
    )

    def _constrain(tree_, shard_tree):
        return jax.tree.map(jax.lax.with_sharding_constraint, tree_, shard_tree)

    def prefill_fn(params, tokens, state, frames=None, prefix=None):
        params = _constrain(params, param_shard)
        state = {**state, "caches": _constrain(state["caches"], cache_shard)}
        with axis_rules(rules, mesh):
            logits, new_state = _prefill(
                model, params, tokens, state, frames=frames, prefix=prefix
            )
        new_state = {**new_state, "caches": _constrain(new_state["caches"], cache_shard)}
        return jax.lax.with_sharding_constraint(logits, logits_shard), new_state

    def decode_fn(params, tokens, state):
        params = _constrain(params, param_shard)
        state = {**state, "caches": _constrain(state["caches"], cache_shard)}
        with axis_rules(rules, mesh):
            logits, new_state = _decode_step(model, params, tokens, state)
        new_state = {**new_state, "caches": _constrain(new_state["caches"], cache_shard)}
        return jax.lax.with_sharding_constraint(logits, logits_shard), new_state

    prefill_jit = jax.jit(prefill_fn, donate_argnums=(2,))
    decode_jit = jax.jit(decode_fn, donate_argnums=(2,))
    return prefill_jit, decode_jit, st_shapes, shards
