"""Pallas TPU selective-scan kernel (Mamba-1).

Grid: (batch, d_inner blocks, time chunks) with the *chunk* axis innermost
(sequential on TPU). The recurrent state lives in VMEM scratch and is
carried across chunk grid steps — the (B, L, Di, N) discretized tensors
never exist anywhere: each timestep's slab is formed in VREGs, folded into
the state, contracted against C_t, and dropped.

This is the TPU adaptation of the CUDA selective-scan: instead of one thread
block per (batch, d-slice) staging into SRAM and syncing warps, one grid cell
owns a (d_blk) stripe, streams its x/dt/B/C chunk HBM->VMEM via BlockSpecs,
and runs the recurrence on the VPU (there is no MXU work in Mamba-1's scan —
the matmuls live in the surrounding projections).

Layout for the chip: the state is held as (N, d_blk), d on the lanes. The
time loop walks groups of 8 steps: it loads each group's x/dt/B/C rows from
the refs at a dynamic, 8-aligned sublane offset (``pl.ds``), turns the B/C
rows into columns, and unrolls the 8 steps with static slices, so no value
is ever sliced at a traced index. Padded steps have dt = 0, which leaves the
state untouched.

Runs compiled on a TPU and interpreted elsewhere; tests compare both with
``ref.reference_selective_scan``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


_GROUP = 8  # steps per aligned load: one f32 sublane tile


def _scan_kernel(
    x_ref,  # (1, Lc, d_blk)
    dt_ref,  # (1, Lc, d_blk) f32
    b_ref,  # (1, Lc, N) f32
    c_ref,  # (1, Lc, N) f32
    a_ref,  # (N, d_blk) f32
    h0_ref,  # (1, N, d_blk) f32
    y_ref,  # (1, Lc, d_blk) f32
    hout_ref,  # (1, N, d_blk) f32 final state (revisited; last write wins)
    h_scr,  # (N, d_blk) f32 carry across chunks
    *,
    chunk_len: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    a = a_ref[...]

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        x = x_ref[0, rows, :].astype(jnp.float32)  # (8, d_blk)
        dt = dt_ref[0, rows, :]
        b = b_ref[0, rows, :].T  # (N, 8)
        c = c_ref[0, rows, :].T
        ys = []
        for t in range(_GROUP):
            dt_t = dt[t : t + 1]  # (1, d_blk)
            h = jnp.exp(dt_t * a) * h + (dt_t * x[t : t + 1]) * b[:, t : t + 1]
            ys.append(jnp.sum(h * c[:, t : t + 1], axis=0, keepdims=True))
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, chunk_len // _GROUP, group, h_scr[...])
    h_scr[...] = h
    hout_ref[0] = h


def mamba_scan(
    xc: jax.Array,  # (B, L, Di)
    dt: jax.Array,  # (B, L, Di) f32
    Bm: jax.Array,  # (B, L, N) f32
    Cm: jax.Array,  # (B, L, N) f32
    a: jax.Array,  # (Di, N) f32
    h0: jax.Array | None = None,  # (B, Di, N)
    chunk_len: int = 128,
    d_block: int = 512,
    interpret: Optional[bool] = None,
):
    """Pallas selective scan. Returns (y (B, L, Di) f32, h_final (B, Di, N))."""
    B, L, Di = xc.shape
    N = a.shape[1]
    up = lambda n: -(-n // _GROUP) * _GROUP
    Lc = min(up(chunk_len), up(L))
    db = min(d_block, Di)
    nc = -(-L // Lc)
    nd = -(-Di // db)
    pad_l = nc * Lc - L
    pad_d = nd * db - Di
    if pad_l or pad_d:
        xc = jnp.pad(xc, ((0, 0), (0, pad_l), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_l), (0, pad_d)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_l), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_l), (0, 0)))
        a = jnp.pad(a, ((0, pad_d), (0, 0)))
    Dp = Di + pad_d
    h0 = jnp.zeros((B, Dp, N), jnp.float32) if h0 is None else (
        jnp.pad(h0, ((0, 0), (0, pad_d), (0, 0))) if pad_d else h0
    )

    y, h_out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk_len=Lc),
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, Lc, db), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, Lc, db), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, Lc, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((1, Lc, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((N, db), lambda b, di, ci: (0, di)),
            pl.BlockSpec((1, N, db), lambda b, di, ci: (b, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, Lc, db), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, N, db), lambda b, di, ci: (b, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc * Lc, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, N, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, db), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(
        xc,
        dt,
        Bm.astype(jnp.float32),
        Cm.astype(jnp.float32),
        a.T,
        h0.transpose(0, 2, 1),
    )
    return y[:, :L, :Di], h_out.transpose(0, 2, 1)[:, :Di]
