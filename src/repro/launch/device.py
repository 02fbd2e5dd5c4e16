"""Process-level set-up shared by the drivers and ``chip_smoke.py``: JAX's
persistent compilation cache and the device a run reports.

Call :func:`enable_compile_cache` first thing in a ``main()``, never at
import: it must run before the first compilation, and importing a module
must not change how another program compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of the cache
# key and a directory that moves never hits. Listed in .gitignore.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is: JAX reads it
    itself, and no other directory is set in code. Otherwise the cache lives
    in the checkout at :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def device_info() -> dict:
    """The device this process runs on, as JAX reports it."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
