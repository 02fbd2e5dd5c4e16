"""Pallas blockwise tree-hash for large array payloads (the data plane's
content-hash kernel — see ``repro.core.hashing`` for the digest contract).

One grid cell = one chunk of ``CHUNK_BLOCKS`` level-0 blocks (128 uint32
words each). Per block ``j`` the kernel folds the words to a wraparound
uint32 blocksum ``s_j``, mixes it with a per-block odd constant
(``c_j = (j*0x9E3779B1 + 0x85EBCA77) | 1``; golden-ratio / murmur fmix
constants) into ``m_j = (s_j ^ c_j) * c_j``, and tree-combines the chunk
into a 3-word running state ``(sum m, xor m, sum s)`` held in SMEM scratch
across the sequential grid — the same init/accumulate/finish shape as
``moe_gmm``. All arithmetic wraps mod 2**32, so the result is bit-identical
to ``ref.reference_hash_tree`` (pure jnp) and to the numpy definition in
``repro.core.hashing.tree_state_np``.

Roofline audit (analytic, like the other kernels): the kernel reads
``4 * n_words`` bytes once and writes a 12-byte state — arithmetic
intensity ~= 3 ops / 4 bytes, i.e. firmly **memory-bound**; the ceiling is
DRAM bandwidth, not compute.

Contract: input is a 1-D uint32 word array whose length is a multiple of
``TREE_BLOCK_WORDS * CHUNK_BLOCKS`` (callers slice the chunk-aligned bulk
through the kernel and finish the ragged remainder on the host — see
``repro.core.hashing._tree_state``). Runs compiled on a TPU and interpreted
elsewhere; both are checked bit for bit against the reference.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import _TREE_GOLD, _TREE_SALT, TREE_BLOCK_WORDS

from . import resolve_interpret

CHUNK_BLOCKS = 64  # level-0 blocks per grid cell (64 * 512 B = 32 KiB/chunk); a power of two


# The kernel computes in int32: the chip's compiler has no unsigned
# reductions, and two's-complement sums, products and XOR agree with uint32
# bit for bit mod 2**32. The constants are the same words, read as signed.
_GOLD_S32 = _TREE_GOLD - (1 << 32)
_SALT_S32 = _TREE_SALT - (1 << 32)


def _hash_tree_kernel(w_ref, o_ref, acc_ref):
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        for i in range(3):
            acc_ref[i] = 0

    w = w_ref[...]  # (CHUNK_BLOCKS, TREE_BLOCK_WORDS) int32
    s = jnp.sum(w, axis=1, keepdims=True)  # (CHUNK_BLOCKS, 1) blocksums
    j = ci * CHUNK_BLOCKS + jax.lax.broadcasted_iota(jnp.int32, (CHUNK_BLOCKS, 1), 0)
    c = (j * jnp.int32(_GOLD_S32) + jnp.int32(_SALT_S32)) | 1
    m = (s ^ c) * c
    x, n = m, CHUNK_BLOCKS  # XOR-fold halves (no XOR reduction on chip)
    while n > 1:
        n //= 2
        x = x[:n] ^ x[n : 2 * n]
    acc_ref[0] = acc_ref[0] + jnp.sum(m)
    acc_ref[1] = acc_ref[1] ^ x[0, 0]
    acc_ref[2] = acc_ref[2] + jnp.sum(s)

    @pl.when(ci == pl.num_programs(0) - 1)
    def _finish():
        for i in range(3):
            o_ref[i] = acc_ref[i]


def hash_tree_state(
    words: jax.Array,  # (n,) uint32, n % (TREE_BLOCK_WORDS * CHUNK_BLOCKS) == 0
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tree state ``(h1, h2, h3)`` as a (3,) uint32 array."""
    n = words.shape[0]
    chunk_words = TREE_BLOCK_WORDS * CHUNK_BLOCKS
    if n == 0 or n % chunk_words:
        raise ValueError(
            f"hash_tree_state needs len(words) a non-zero multiple of "
            f"{chunk_words}, got {n}"
        )
    w2 = jax.lax.bitcast_convert_type(
        jnp.asarray(words, dtype=jnp.uint32), jnp.int32
    ).reshape(-1, TREE_BLOCK_WORDS)
    state = pl.pallas_call(
        _hash_tree_kernel,
        grid=(n // chunk_words,),
        in_specs=[pl.BlockSpec((CHUNK_BLOCKS, TREE_BLOCK_WORDS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((3,), jnp.int32),
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(w2)
    return jax.lax.bitcast_convert_type(state, jnp.uint32)
