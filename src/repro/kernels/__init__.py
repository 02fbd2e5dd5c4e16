"""Pallas kernels (``flash_attention``, ``flash_decode``, ``hash_tree``,
``moe_gmm``, ``mamba_scan``) with their pure-jnp oracles in ``ref``."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel runs in Pallas interpret mode. ``None`` derives it
    from the platform: compiled on a TPU, interpreted on any other backend.
    An explicit bool is for compiling against a described TPU from a
    process whose backend is the CPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
