"""Distribution layer: sharding rules (divisibility fallbacks), elastic
resharding, train-step numerics on the host mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import (
    cache_logical_axes,
    make_rules,
    pspec_for_axes,
)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH_POD = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_pspec_divisible_dims_shard():
    rules = {"embed": "data", "heads": "model"}
    spec = pspec_for_axes(("embed", "heads", None), (4096, 32, 128), rules, MESH)
    assert spec == P("data", "model", None)


def test_pspec_indivisible_falls_back():
    rules = {"heads": "model", "embed": "data"}
    # 40 heads % 16 -> replicate that dim only
    spec = pspec_for_axes(("embed", "heads", None), (5120, 40, 128), rules, MESH)
    assert spec == P("data", None, None)


def test_pspec_no_axis_reuse():
    rules = {"a": "model", "b": "model"}
    spec = pspec_for_axes(("a", "b"), (32, 32), rules, MESH)
    assert spec == P("model", None)  # second use of 'model' dropped


def test_pspec_tuple_axes():
    rules = {"batch": ("pod", "data")}
    spec = pspec_for_axes(("batch", None), (256, 128), rules, MESH_POD)
    assert spec == P(("pod", "data"), None)
    # batch=1 cannot shard
    spec = pspec_for_axes(("batch", None), (1, 128), rules, MESH_POD)
    assert spec == P(None, None)


def test_serve_rules_flash_decoding_fallback():
    cfg = get_config("qwen2.5-32b")  # kv=8 % 16 != 0
    rules = make_rules(cfg, MESH, "serve", global_batch=128)
    assert rules["kv_seq"] == ("model",)
    cfg2 = get_config("stablelm-1.6b")  # kv=32 divides
    rules2 = make_rules(cfg2, MESH, "serve", global_batch=128)
    assert rules2["kv_seq"] is None
    # batch=1 long-context: seq gets the batch axes too
    rules3 = make_rules(cfg, MESH, "serve", global_batch=1)
    assert set(rules3["kv_seq"]) == {"data", "model"}


def test_train_rules_fsdp_only_in_train():
    cfg = get_config("internlm2-20b")
    assert make_rules(cfg, MESH, "train")["embed"] == "data"
    assert make_rules(cfg, MESH, "serve")["embed"] is None


def test_cache_axes_match_cache_structure():
    from repro.models.registry import build_model, init_serve_state

    for arch in ("mixtral-8x7b", "minicpm3-4b", "falcon-mamba-7b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        state = init_serve_state(model, 2, 16)
        axes = cache_logical_axes(cfg, max_len=16)
        # same tree structure (axes leaves are tuples)
        jax.tree.map(
            lambda a, c: None,
            axes,
            state["caches"],
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )


# ---------------------------------------------------------------------------
# Elastic resharding (numeric identity on the host mesh)
# ---------------------------------------------------------------------------


def test_elastic_reshard_identity():
    from repro.dist.elastic import reshard_state
    from repro.dist.step import param_specs
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.optim import adamw_init

    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params, axes = model.init(jax.random.key(0))
    state = {
        "params": params,
        "opt": adamw_init(params),
        "step": jnp.int32(3),
    }
    mesh_a = make_host_mesh()
    mesh_b = make_host_mesh()  # same devices; exercises the machinery
    new_state, shardings = reshard_state(state, axes, mesh_a, mesh_b, cfg, "train")
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(new_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Train-step builder numerics (host mesh)
# ---------------------------------------------------------------------------


def test_train_step_builder_runs_and_descends():
    from repro.dist.step import make_train_step
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.optim import adamw_init, constant_lr

    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()
    jitted, state_shapes, state_shard, batch_shard = make_train_step(
        model, mesh, constant_lr(1e-3), global_batch=4
    )
    params, _ = model.init(jax.random.key(0))
    state = {"params": params, "opt": adamw_init(params), "step": jnp.int32(0)}
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for _ in range(8):
        state, metrics = jitted(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]  # memorizes the fixed batch
    assert int(state["step"]) == 8


def test_train_step_microbatched_matches_full():
    from repro.dist.step import make_train_step
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.optim import adamw_init, constant_lr

    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()
    params, _ = model.init(jax.random.key(0))

    def run(microbatches):
        jitted, *_ = make_train_step(
            model, mesh, constant_lr(1e-3), global_batch=4, microbatches=microbatches
        )
        p = jax.tree.map(jnp.copy, params)  # the step donates its state
        state = {"params": p, "opt": adamw_init(p), "step": jnp.int32(0)}
        tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)
        state, metrics = jitted(state, {"tokens": tokens, "labels": tokens})
        return state, float(metrics["loss"])

    s1, l1 = run(1)
    s2, l2 = run(2)
    assert l1 == pytest.approx(l2, rel=2e-3)
    for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=2e-4
        )
