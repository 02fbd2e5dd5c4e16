"""Pallas TPU grouped matmul for MoE expert FFNs (SwiGLU).

One grid cell = (expert, token-block, ffn-block); the ffn axis is innermost
(sequential) so the (c_blk, D) output accumulator lives in VMEM scratch and
each w_down tile is applied as soon as its h tile is formed — gate, up, silu,
elementwise product and down-projection are fused in one VMEM residency
(MegaBlocks adapted to the MXU: dense tiles over static capacity bins instead
of CUDA block-sparse indices; the token->bin gather happens outside in the
dispatch einsum where XLA can overlap it with the previous layer).

Tile sizes default to MXU-aligned (128 rows, up to 256 ffn cols); the
contraction dim D stays whole per tile (weights stream (D, f_blk) slabs
HBM->VMEM), so the ffn block shrinks as D grows until the double-buffered
tiles fit the scoped VMEM a kernel gets (16 MiB on v5e): at mixtral's
D = 4096 in bf16 that is 128 columns.

Runs compiled on a TPU and interpreted elsewhere; tests compare both with
``ref.reference_gmm``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

_VMEM_BUDGET = 14 << 20  # of the 16 MiB of scoped VMEM, with room to spare


def _vmem_bytes(bc: int, bf: int, D: int, itemsize: int) -> int:
    """Double-buffered x/out tiles and three weight tiles, plus the f32
    accumulator."""
    return 2 * (2 * bc * D + 3 * D * bf) * itemsize + 4 * bc * D


def _fit_block_f(bc: int, D: int, itemsize: int, block_f: int = 256) -> int:
    while block_f > 128 and _vmem_bytes(bc, block_f, D, itemsize) > _VMEM_BUDGET:
        block_f //= 2
    return block_f


def _gmm_kernel(
    x_ref,  # (1, c_blk, D)
    wg_ref,  # (1, D, f_blk)
    wu_ref,  # (1, D, f_blk)
    wd_ref,  # (1, f_blk, D)
    o_ref,  # (1, c_blk, D)
    acc_scr,  # (c_blk, D) f32
):
    fi = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]
    g = jax.lax.dot_general(
        x, wg_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    u = jax.lax.dot_general(
        x, wu_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_scr[...] += jax.lax.dot_general(
        h, wd_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(fi == nf - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_gmm(
    x: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    *,
    block_c: int = 128,
    block_f: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    E, C, D = x.shape
    F = w_gate.shape[-1]
    bc = min(block_c, C)
    if block_f is None:
        block_f = _fit_block_f(bc, D, jnp.dtype(w_gate.dtype).itemsize)
    bf = min(block_f, F)
    nc = -(-C // bc)
    nf = -(-F // bf)
    pad_c = nc * bc - C
    pad_f = nf * bf - F
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c), (0, 0)))
    if pad_f:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pad_f)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pad_f)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pad_f), (0, 0)))

    out = pl.pallas_call(
        _gmm_kernel,
        grid=(E, nc, nf),
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, ci, fi: (e, ci, 0)),
            pl.BlockSpec((1, D, bf), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, D, bf), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, bf, D), lambda e, ci, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, D), lambda e, ci, fi: (e, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((E, nc * bc, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_gate, w_up, w_down)
    return out[:, :C] if pad_c else out
