"""Shared model substrate: config schema, logical-axis sharding hooks,
parameter init, RMSNorm, RoPE.

Sharding is expressed with *logical axis names* on params and activations;
``repro.dist.sharding`` maps logical names -> mesh axes per (arch, shape).
On CPU (no mesh context) all sharding hooks are no-ops so smoke tests and
kernels run unmodified.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Config schema
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer position inside a repeating group."""

    mixer: str = "attention"  # "attention" | "mamba"
    ffn: str = "dense"  # "dense" | "moe" | "none"


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 -> d_model // n_heads
    # repeating layout (len(layout) must divide n_layers)
    layout: tuple = (LayerSpec(),)
    # attention
    attention: str = "full"  # full | swa | mla
    window: int = 0  # SWA window (0 = unlimited)
    qkv_bias: bool = False
    rope_theta: float = 1e4  # 0: no positional encoding (Jamba's attention)
    # MoE
    n_experts: int = 0  # the router's outputs
    top_k: int = 0
    capacity_factor: float = 1.25
    experts_held: int = 0  # experts whose weights this device holds (0: all)
    experts_first: int = 0  # id of the first of them; tokens routed elsewhere add nothing here
    moe_renorm: bool = True  # top-k weights renormalised to sum to 1 (Mixtral); Jamba keeps softmax's
    # MLA (minicpm3-style)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # Mamba
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_bcdt_norm: bool = False  # RMSNorm on dt, B and C after x_proj (Jamba's mixer)
    # encoder-decoder
    encoder_layers: int = 0  # 0 -> decoder-only
    cross_attention: bool = False
    # modality frontend (stub per assignment): "none" | "vision" | "audio"
    frontend: str = "none"
    frontend_len: int = 0  # patches / frames provided by input_specs()
    # numerics & structure
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # runtime knobs (not architecture)
    remat: str = "block"  # none | block | full
    block_q: int = 512
    block_kv: int = 512
    moe_exact_tokens: int = 512  # decode/smoke-scale calls dispatch drop-free

    # -- derived -----------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, self.d_model // 16)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % len(self.layout) == 0, (
            f"{self.name}: layout len {len(self.layout)} !| n_layers {self.n_layers}"
        )
        return self.n_layers // len(self.layout)

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k: SSM-dominated (pure or hybrid) or bounded
        attention window. Pure full-attention archs are skipped per the
        assignment."""
        if any(s.mixer == "mamba" for s in self.layout):
            return True  # ssm / hybrid
        return self.attention == "swa" and self.window > 0

    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def n_params(self) -> int:
        """Analytic parameter count (for 6ND MODEL_FLOPS)."""
        d, dh = self.d_model, self.head_dim
        total = self.vocab * d  # embed
        if not self.tie_embeddings:
            total += self.vocab * d
        for spec in self.layout:
            p = 0
            if spec.mixer == "attention":
                if self.attention == "mla":
                    qr = self.q_lora_rank or d
                    p += d * qr + qr * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                    p += d * (self.kv_lora_rank + self.qk_rope_dim)
                    p += self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                    p += self.n_heads * self.v_head_dim * d
                else:
                    p += d * self.n_heads * dh + 2 * d * self.n_kv_heads * dh
                    p += self.n_heads * dh * d
            elif spec.mixer == "mamba":
                di, N = self.d_inner, self.ssm_state
                p += d * 2 * di + di * self.ssm_conv
                p += di * (self.dt_rank + 2 * N) + self.dt_rank * di
                p += di * N + di + di * d
                if self.ssm_bcdt_norm:
                    p += self.dt_rank + 2 * N
            if spec.ffn == "dense":
                p += 3 * d * self.d_ff  # SwiGLU
            elif spec.ffn == "moe":
                p += d * self.n_experts  # router
                p += self.experts_here * 3 * d * self.d_ff
            p += 2 * d  # two norms
            total += p * self.n_groups
        if self.encoder_layers:
            enc = self.encoder_layers * (
                d * self.n_heads * dh + 2 * d * self.n_kv_heads * dh
                + self.n_heads * dh * d + 3 * d * self.d_ff + 2 * d
            )
            # decoder cross-attention adds one attention block per layer
            cross = self.n_layers * (
                d * self.n_heads * dh + 2 * d * self.n_kv_heads * dh
                + self.n_heads * dh * d + d
            )
            total += enc + cross
        return int(total)

    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if self.n_experts == 0:
            return self.n_params()
        d = self.d_model
        moe_layers = sum(1 for s in self.layout if s.ffn == "moe") * self.n_groups
        inactive = moe_layers * (self.experts_here - self.top_k) * 3 * d * self.d_ff
        return int(self.n_params() - inactive)

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=len(self.layout) * 2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)) if self.n_kv_heads < self.n_heads else 4,
            d_head=16,
            d_ff=128 if self.d_ff else 0,
            vocab=256,
            n_experts=min(self.n_experts, 4),
            experts_held=min(self.experts_held, 2),
            top_k=min(self.top_k, 2),
            q_lora_rank=32 if self.q_lora_rank else 0,
            kv_lora_rank=16 if self.kv_lora_rank else 0,
            qk_nope_dim=8 if self.qk_nope_dim else 0,
            qk_rope_dim=8 if self.qk_rope_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            ssm_state=8,
            encoder_layers=2 if self.encoder_layers else 0,
            frontend_len=8 if self.frontend_len else 0,
            window=min(self.window, 64) if self.window else 0,
            block_q=16,
            block_kv=16,
            dtype="float32",
            remat="none",
        )


# ---------------------------------------------------------------------------
# Logical-axis sharding hooks
# ---------------------------------------------------------------------------

_AXIS_RULES = threading.local()


def set_axis_rules(rules: Optional[dict], mesh=None) -> None:
    """rules: logical axis name -> mesh axis (str/tuple/None)."""
    _AXIS_RULES.ctx = None if rules is None else (rules, mesh)


def get_axis_rules():
    return getattr(_AXIS_RULES, "ctx", None)


class axis_rules:
    """Context manager for logical->mesh axis rules (+ the mesh itself)."""

    def __init__(self, rules: Optional[dict], mesh=None):
        self.rules, self.mesh = rules, mesh

    def __enter__(self):
        self.prev = get_axis_rules()
        set_axis_rules(self.rules, self.mesh)
        return self

    def __exit__(self, *exc):
        _AXIS_RULES.ctx = self.prev


def logical_to_pspec(axes: tuple):
    from jax.sharding import PartitionSpec

    ctx = get_axis_rules()
    if ctx is None:
        return None
    rules, _ = ctx
    return PartitionSpec(*[rules.get(a) for a in axes])


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain activation sharding by logical axes. No-op without rules."""
    ctx = get_axis_rules()
    if ctx is None:
        return x
    rules, mesh = ctx
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(*[rules.get(a) for a in axes])
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Param init: params pytree + parallel logical-axes pytree
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Accumulates (params, logical axes) pytrees with a split key stream."""

    def __init__(self, key: jax.Array, dtype: Any):
        self._key = key
        self.dtype = dtype

    def next_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def dense(self, shape: tuple, axes: tuple, scale: Optional[float] = None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        s = scale if scale is not None else fan_in ** -0.5
        w = (jax.random.normal(self.next_key(), shape, jnp.float32) * s).astype(self.dtype)
        return w, axes

    def zeros(self, shape: tuple, axes: tuple, dtype: Any = None):
        return jnp.zeros(shape, dtype or self.dtype), axes

    def ones(self, shape: tuple, axes: tuple, dtype: Any = None):
        return jnp.ones(shape, dtype or self.dtype), axes

    def const(self, value: np.ndarray, axes: tuple, dtype: Any = None):
        return jnp.asarray(value, dtype or self.dtype), axes


def split_tree(tree_of_pairs):
    """Split a pytree whose leaves are (param, axes) into two pytrees."""
    params = jax.tree.map(lambda p: p[0], tree_of_pairs, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "dtype"))
    axes = jax.tree.map(lambda p: p[1], tree_of_pairs, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "dtype"))
    return params, axes


def stack_groups(pairs_list):
    """Stack a list of identical (param, axes) trees along a new leading
    'layers' axis (for scan-over-groups)."""
    is_pair = lambda x: isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "dtype")

    def _stack(*leaves):
        ps = jnp.stack([l[0] for l in leaves])
        return (ps, ("layers",) + leaves[0][1])

    return jax.tree.map(_stack, *pairs_list, is_leaf=is_pair)


# ---------------------------------------------------------------------------
# Serve caches stacked over the scan groups
# ---------------------------------------------------------------------------


def cache_layer(a: jax.Array, layer: jax.Array) -> jax.Array:
    """Layer ``layer`` of a cache leaf stacked over the scan groups, read
    where it lies (XLA fuses the slice into its reader)."""
    return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)


def cache_write(a: jax.Array, rows: jax.Array, layer: jax.Array, *at) -> jax.Array:
    """``a`` with one layer's new ``rows`` written at ``(layer, *at)``, the
    dims ``at`` leaves out at 0: the stacked cache updated in place, nothing
    else of it touched."""
    start = (layer, *at) + (0,) * (a.ndim - 1 - len(at))
    return jax.lax.dynamic_update_slice(a, rows[None].astype(a.dtype), start)


# ---------------------------------------------------------------------------
# Norms & RoPE
# ---------------------------------------------------------------------------


@jax.custom_vjp
def grad_cast(x: jax.Array) -> jax.Array:
    """Identity whose cotangent is cast to the primal dtype. Placed at layer
    boundaries so tensor-parallel backward all-reduces move bf16, not the f32
    that norm/loss chains would otherwise propagate (halves those payloads)."""
    return x


def _grad_cast_fwd(x):
    return x, jnp.zeros((0,), x.dtype)  # zero-size dtype token


def _grad_cast_bwd(token, g):
    return (g.astype(token.dtype),)


grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with a hand-written backward (the fused-layernorm pattern):
    residuals are (x in its own dtype, w, rstd) instead of autodiff's chain
    of f32 (B, L, D) intermediates — backward HBM traffic drops ~2x and the
    dx cotangent leaves in the activation dtype (bf16 TP all-reduces)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rstd * weight.astype(jnp.float32)).astype(dt)


def _rms_norm_fwd(x, weight, eps):
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = (xf * rstd * weight.astype(jnp.float32)).astype(x.dtype)
    return y, (x, weight, rstd)


def _rms_norm_bwd(eps, res, g):
    x, weight, rstd = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = xf * rstd
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    dxhat = gf * weight.astype(jnp.float32)
    dx = rstd * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def rope_frequencies(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., L, Dh) rotated pairwise-half style. positions: (..., L)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)  # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, dh/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array, ignore_id: int = -1):
    """Mean token CE in f32; logits (..., V), labels (...,) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    loss = (lse - gold) * mask
    return loss.sum() / jnp.maximum(mask.sum(), 1.0)
