"""Random weights of the hybrid decoder from the seed, made on the device in
one jitted call.

The layout: ``embed`` (V, D), ``lm_head`` (D, V), ``final_norm`` (D,), and
``layers``, one dict per layer of the period (``work_hybrid.layout``), each
array stacked over the periods the configuration holds (P = layers / 8):

  ln1, ln2 (P, D)
  mamba mixer:  in_proj (P, D, 2Di)  conv_w (P, Kc, Di)  conv_b (P, Di)
                x_proj (P, Di, R+2N)  dt_norm (P, R)  b_norm, c_norm (P, N)
                dt_proj (P, R, Di)  dt_bias (P, Di) f32  a_log (P, Di, N) f32
                d_skip (P, Di)  out_proj (P, Di, D)
  attention:    wq (P, D, H, Dh)  wk, wv (P, D, KVH, Dh)  wo (P, H, Dh, D)
  dense ffn:    w_gate, w_up (P, D, F)  w_down (P, F, D)
  moe ffn:      router (P, D, E)  w_gate, w_up (P, Eh, D, F)  w_down (P, Eh, F, D)

bf16 but for the two float32 Mamba arrays. Each matrix, and the conv bias,
is normal with standard deviation 1/sqrt(its contracted size); the
embedding is unit normal; norms and ``d_skip`` are ones. ``a_log`` is
log(1..N) in every channel and ``dt_bias`` the inverse softplus of a dt
log-uniform on [1e-3, 1e-1], as Mamba initialises them. Every array is drawn
in blocks of at most 2**25 values, so the draw needs little float32 on top
of the weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from work_hybrid import SIZE_KEYS, dims, layout

DTYPE = jnp.bfloat16
BLOCK = 1 << 25
NORMAL, ONES, A_LOG, DT_BIAS = "normal", "ones", "a_log", "dt_bias"


def period(c: dict) -> int:
    p = math.lcm(c["attn_layer_period"], c["expert_layer_period"])
    if c["num_hidden_layers"] % p:
        raise ValueError(f"{c['num_hidden_layers']} layers are not whole periods of {p}")
    return p


def shapes(c: dict) -> dict:
    """Per leaf (shape, kind, scale)."""
    k = dims(c)
    D, Di, N, R, Kc, F = k["D"], k["Di"], k["N"], k["R"], k["Kc"], k["F"]
    P = c["num_hidden_layers"] // period(c)

    def normal(*s, fan):
        return ((P, *s), NORMAL, fan**-0.5)

    def ones(*s):
        return ((P, *s), ONES, None)

    layers = []
    for mixer, ffn in layout(c)[: period(c)]:
        if mixer == "mamba":
            m = {
                "in_proj": normal(D, 2 * Di, fan=D),
                "conv_w": normal(Kc, Di, fan=Kc),
                "conv_b": normal(Di, fan=Kc),
                "x_proj": normal(Di, R + 2 * N, fan=Di),
                "dt_norm": ones(R),
                "b_norm": ones(N),
                "c_norm": ones(N),
                "dt_proj": normal(R, Di, fan=R),
                "dt_bias": ((P, Di), DT_BIAS, None),
                "a_log": ((P, Di, N), A_LOG, None),
                "d_skip": ones(Di),
                "out_proj": normal(Di, D, fan=Di),
            }
        else:
            m = {
                "wq": normal(D, k["H"], k["Dh"], fan=D),
                "wk": normal(D, k["KVH"], k["Dh"], fan=D),
                "wv": normal(D, k["KVH"], k["Dh"], fan=D),
                "wo": normal(k["H"], k["Dh"], D, fan=k["H"] * k["Dh"]),
            }
        if ffn == "moe":
            f = {
                "router": normal(D, k["E"], fan=D),
                "w_gate": normal(k["Eh"], D, F, fan=D),
                "w_up": normal(k["Eh"], D, F, fan=D),
                "w_down": normal(k["Eh"], F, D, fan=F),
            }
        else:
            f = {
                "w_gate": normal(D, F, fan=D),
                "w_up": normal(D, F, fan=D),
                "w_down": normal(F, D, fan=F),
            }
        layers.append({"ln1": ones(D), "mixer": m, "ln2": ones(D), "ffn": f})
    return {
        "embed": ((k["V"], D), NORMAL, 1.0),
        "lm_head": ((D, k["V"]), NORMAL, D**-0.5),
        "final_norm": ((D,), ONES, None),
        "layers": layers,
    }


def _blocks(shape) -> int:
    """Blocks of at most BLOCK values along the leading rows of ``shape``."""
    rows, size = math.prod(shape[:-1]), math.prod(shape)
    b = 1
    while size // b > BLOCK and rows % (2 * b) == 0:
        b *= 2
    return b


def _draw(key, shape, kind, scale):
    if kind == ONES:
        return jnp.ones(shape, DTYPE)
    if kind == A_LOG:
        a = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape)
    if kind == DT_BIAS:
        lo, hi = jnp.log(1e-3), jnp.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    b = _blocks(shape)
    inner = (math.prod(shape[:-1]) // b, shape[-1])

    def one(k):
        return (jax.random.normal(k, inner, jnp.float32) * scale).astype(DTYPE)

    return jax.lax.map(one, jax.random.split(key, b)).reshape(shape)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


@functools.cache
def _builder(dims_key: tuple):
    spec = shapes(dict(dims_key))
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([_draw(k, *leaf) for k, leaf in zip(keys, leaves)])

    return build


def make(c: dict, seed_word: int) -> dict:
    """The weights of configuration ``c`` for one seed, on the default device."""
    key = tuple(sorted((k, c[k]) for k in SIZE_KEYS if k in c))
    return _builder(key)(jax.random.key(seed_word))
