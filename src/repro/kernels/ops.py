"""Jit'd kernel wrappers + the kernel registry handed to the models.

``kernel_set(use_pallas)`` returns the dict that ``repro.models`` threads
through the layers. Each kernel derives its mode from the platform
(``resolve_interpret``): compiled on a TPU, interpreted elsewhere. Without
``use_pallas`` the models take their pure-jnp paths.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import flash_attention
from .flash_decode import flash_decode
from .hash_tree import hash_tree_state
from .mamba_scan import mamba_scan
from .moe_gmm import moe_gmm


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv"))
def flash_attention_op(q, k, v, *, causal=True, window=0, block_q=128, block_kv=128):
    return flash_attention(
        q, k, v, causal=causal, window=window, block_q=block_q, block_kv=block_kv
    )


def kernel_set(use_pallas: bool) -> Optional[dict]:
    """The dict the model trunk consumes (keys: moe_gmm, mamba_scan,
    flash_decode, hash_tree)."""
    if not use_pallas:
        return None
    return {
        "moe_gmm": moe_gmm,
        "mamba_scan": mamba_scan,
        "flash_decode": flash_decode,
        "hash_tree": hash_tree_state,
    }
