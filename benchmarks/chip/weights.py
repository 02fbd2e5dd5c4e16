"""Random bf16 weights from the seed, made on the device in one jitted call.

The layout is the benchmark's own, one stacked array per weight kind:

  embed (V, D)  lm_head (D, V)  final_norm (D,)
  layers/ ln1, ln2 (L, D)  wq (L, D, H, Dh)  wk, wv (L, D, KVH, Dh)
          wo (L, H, Dh, D)  w_gate, w_up (L, D, F)  w_down (L, F, D)

Each matrix is normal with standard deviation 1/sqrt(its contracted size),
so activations keep their scale through any depth; the embedding is unit
normal and the norms are ones. Each array is drawn in blocks along its first
axis (a few layers, or a sixteenth of a table's rows, at a time), so the draw
needs little float32 on top of the weights themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from work import SIZE_KEYS, dims

DTYPE = jnp.bfloat16


def shapes(c: dict) -> dict:
    k = dims(c)
    L, D, H, KVH, Dh, F, V = (k[n] for n in ("L", "D", "H", "KVH", "Dh", "F", "V"))
    return {
        "embed": ((V, D), 1.0),
        "lm_head": ((D, V), D**-0.5),
        "final_norm": ((D,), None),
        "layers": {
            "ln1": ((L, D), None),
            "ln2": ((L, D), None),
            "wq": ((L, D, H, Dh), D**-0.5),
            "wk": ((L, D, KVH, Dh), D**-0.5),
            "wv": ((L, D, KVH, Dh), D**-0.5),
            "wo": ((L, H, Dh, D), (H * Dh) ** -0.5),
            "w_gate": ((L, D, F), D**-0.5),
            "w_up": ((L, D, F), D**-0.5),
            "w_down": ((L, F, D), F**-0.5),
        },
    }


def _rows(n: int) -> int:
    """Blocks to draw an n-row table in."""
    for b in (16, 8, 4, 2):
        if n % b == 0:
            return b
    return 1


def _draw(key, shape, scale):
    if scale is None:
        return jnp.ones(shape, DTYPE)
    blocks = _rows(shape[0])
    inner = (shape[0] // blocks,) + tuple(shape[1:])

    def one(k):
        return (jax.random.normal(k, inner, jnp.float32) * scale).astype(DTYPE)

    out = jax.lax.map(one, jax.random.split(key, blocks))
    return out.reshape(shape)


@functools.cache
def _builder(dims_key: tuple):
    spec = shapes(dict(dims_key))
    leaves, treedef = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([_draw(k, s, sc) for k, (s, sc) in zip(keys, leaves)])

    return build


def make(c: dict, seed_word: int) -> dict:
    """The weights of configuration ``c`` for one seed, on the default device."""
    key = tuple(sorted((k, c[k]) for k in SIZE_KEYS if k in c))
    return _builder(key)(jax.random.key(seed_word))
