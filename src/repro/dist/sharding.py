"""Logical-axis sharding rules and PartitionSpec derivation.

Models annotate every parameter and activation with *logical* axis names
("embed", "heads", "kv_seq", ...). This module maps logical names to mesh
axes per (arch config, mesh, mode) and derives concrete PartitionSpecs with
two safety properties:

  - **divisibility fallback** — a dimension that does not divide evenly over
    its assigned mesh axes is replicated (that dim only), so an arch with 40
    heads on a 16-way model axis lowers instead of crashing;
  - **no mesh-axis reuse** — a mesh axis consumed by an earlier dimension of
    the same tensor is dropped from later dimensions (XLA requires each mesh
    axis to appear at most once per spec).

The rules encode the placement policy:

  train — Megatron tensor parallelism over ``model`` (heads / mlp / vocab,
  or experts when the expert count divides), FSDP over ``data`` (the
  ``embed`` dim of every weight, optimizer state included for free because
  AdamW state mirrors the param tree), batch over ``(pod, data)``.

  serve — no FSDP (weights stay whole per model shard: decode is latency
  bound and all-gathering weights every token would dominate), batch over
  the data axes, and the KV cache placed by the *flash-decoding fallback*:
  when the KV head count does not divide the model axis, the cache shards
  over its sequence axis instead (``kv_seq``), turning decode attention into
  per-shard partial softmax + cross-shard combine. A batch too small to
  occupy the data axes (long-context ``global_batch=1``) donates those axes
  to ``kv_seq`` as well.

Only ``mesh.shape`` (a name->size mapping) is consulted, so rules can be
computed for meshes that do not exist yet (capacity planning).
"""

from __future__ import annotations

import math
from typing import Optional

from jax.sharding import NamedSharding, PartitionSpec

from repro.models.attention import kv_cache_layout
from repro.models.common import ArchConfig


def _axis_size(mesh_shape: dict, entry) -> int:
    axes = entry if isinstance(entry, (tuple, list)) else (entry,)
    return math.prod(mesh_shape.get(a, 1) for a in axes)


def pspec_for_axes(axes: tuple, shape: tuple, rules: dict, mesh) -> PartitionSpec:
    """Derive a PartitionSpec for one tensor.

    axes: logical axis name (or None) per dimension.
    shape: concrete dimension sizes (for divisibility checks).
    rules: logical name -> mesh axis (str), mesh axes (tuple), or None.

    A tuple assignment is reduced greedily from the right until the dimension
    divides (e.g. batch=8 over ("pod", "data")=(2, 16) falls back to "pod").
    """
    mesh_shape = dict(mesh.shape)
    used: set = set()
    entries = []
    for ax, dim in zip(axes, shape):
        assign = rules.get(ax) if ax is not None else None
        if assign is None:
            entries.append(None)
            continue
        cand = tuple(assign) if isinstance(assign, (tuple, list)) else (assign,)
        cand = tuple(a for a in cand if a not in used and mesh_shape.get(a, 1) > 1)
        while cand and dim % _axis_size(mesh_shape, cand) != 0:
            cand = cand[:-1]  # greedy fallback: drop trailing axes
        if not cand:
            entries.append(None)
            continue
        used.update(cand)
        entries.append(cand if len(cand) > 1 else cand[0])
    return PartitionSpec(*entries)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def shardings_for(axes_tree, shapes_tree, rules: dict, mesh):
    """NamedSharding tree from parallel (logical axes, shapes) trees."""
    import jax

    return jax.tree.map(
        lambda ax, s: NamedSharding(mesh, pspec_for_axes(ax, s.shape, rules, mesh)),
        axes_tree,
        shapes_tree,
        is_leaf=_is_axes_leaf,
    )


def make_rules(
    cfg: ArchConfig,
    mesh,
    mode: str,
    global_batch: Optional[int] = None,
) -> dict:
    """Logical axis name -> mesh axis assignment for one (arch, mesh, mode).

    mode: "train" | "serve". global_batch=None assumes a batch large enough
    to occupy the data axes (capacity-planning default).
    """
    if mode not in ("train", "serve"):
        raise ValueError(f"unknown mode {mode!r} (want 'train' or 'serve')")
    mesh_shape = dict(mesh.shape)
    model = "model" if mesh_shape.get("model", 1) > 1 else None
    tp = mesh_shape.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if mesh_shape.get(a, 1) > 1)
    dp = _axis_size(mesh_shape, data_axes)
    batch_ok = bool(data_axes) and (
        global_batch is None or (global_batch >= dp and global_batch % dp == 0)
    )

    rules: dict = {
        "layers": None,
        "seq": None,
        "head_dim": None,
        "q_lora": None,
        "kv_lora": None,
        "vocab": model if cfg.vocab % tp == 0 else None,
        "heads": model if cfg.n_heads_eff % tp == 0 else None,
        "kv_heads": model if cfg.n_kv_heads % tp == 0 else None,
        "inner": model if cfg.d_inner % tp == 0 else None,
        "batch": (
            (data_axes if len(data_axes) > 1 else data_axes[0]) if batch_ok else None
        ),
        "moe_group": None,
    }

    # MoE FFN: expert parallelism when the expert count divides the model
    # axis; otherwise replicate experts and tensor-shard the ffn dim.
    if cfg.n_experts and cfg.n_experts % tp == 0:
        rules["experts"], rules["mlp"] = model, None
    else:
        rules["experts"] = None
        rules["mlp"] = model if (cfg.d_ff and cfg.d_ff % tp == 0) else None
    if cfg.moe_groups and "data" in mesh_shape:
        rules["moe_group"] = "data"

    # FSDP (ZeRO-3 posture) is a throughput lever: train only.
    rules["embed"] = "data" if (mode == "train" and "data" in mesh_shape) else None

    # serve: KV-cache placement (flash-decoding fallback on the seq axis)
    kv_seq: list = []
    if mode == "serve":
        if model and cfg.n_kv_heads % tp != 0:
            kv_seq.append("model")
        if data_axes and not batch_ok:
            kv_seq.extend(data_axes)
    rules["kv_seq"] = tuple(kv_seq) if kv_seq else None
    return rules


def cache_logical_axes(cfg: ArchConfig, max_len: int) -> list:
    """Logical-axes tree mirroring ``Model.init_cache(batch, max_len)``.

    Per layout position: a dict whose leaves are tuples of logical axis
    names, one entry per array dimension (the leading entry is "layers" —
    caches are stacked over the scan groups exactly like the params).
    """

    def attention_axes() -> dict:
        if cfg.attention == "mla":
            return {
                "c_kv": ("batch", "kv_seq", "kv_lora"),
                "k_rope": ("batch", "kv_seq", None),
                "index": (),
            }
        heads_major, _ = kv_cache_layout(cfg)
        kv = (
            ("batch", "kv_heads", "kv_seq", "head_dim")
            if heads_major
            else ("batch", "kv_seq", "kv_heads", "head_dim")
        )
        c = {"k": kv, "v": kv, "index": ()}
        S = (
            min(max_len, cfg.window)
            if (cfg.attention == "swa" and cfg.window)
            else max_len
        )
        if cfg.attention == "swa" and cfg.window and S == cfg.window:
            c["pos"] = ("batch", "kv_seq")  # ring-buffer slot positions
        return c

    def mamba_axes() -> dict:
        return {
            "h": ("batch", "inner", None),
            "conv": ("batch", None, "inner"),
        }

    out = []
    for spec in cfg.layout:
        tree = mamba_axes() if spec.mixer == "mamba" else attention_axes()
        out.append({k: ("layers",) + v for k, v in tree.items()})
    return out
