"""Pallas TPU RG-LRU scan: time-blocked sequential linear recurrence.

Grid (B, nR, nT): nT innermost/arbitrary; the hidden state h [1, bR] persists
in VMEM scratch across time blocks (channels are independent → the R axis is
embarrassingly parallel and tiles the lane dimension). Inside a block the
recurrence is an unrolled loop of vector ops over [bT, bR] in VMEM — the TPU
replacement for the GPU per-timestep kernel (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(a_ref, bx_ref, y_ref, h_scr, *, block_t: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(i, h):                                   # h [1, bR]
        row = pl.ds(i, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + bx_ref[0, row, :].astype(jnp.float32))
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_t, step, h_scr[...])


def rglru_scan(a, bx, *, block_t: int = 128, block_r: int = 128,
               interpret: Optional[bool] = None):
    """h_t = a_t ⊙ h_{t-1} + bx_t over [B, S, R]. Returns (y, h_last)."""
    B, S, R = a.shape
    block_t = min(block_t, S)
    block_r = min(block_r, R)
    Sp = -(-S // block_t) * block_t
    Rp = -(-R // block_r) * block_r
    ap = jnp.pad(a, ((0, 0), (0, Sp - S), (0, Rp - R)))
    bp = jnp.pad(bx, ((0, 0), (0, Sp - S), (0, Rp - R)))
    nt, nr = Sp // block_t, Rp // block_r

    y = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, nt=nt),
        grid=(B, nr, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_r), lambda b, ri, ti: (b, ti, ri)),
            pl.BlockSpec((1, block_t, block_r), lambda b, ri, ti: (b, ti, ri)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_r), lambda b, ri, ti: (b, ti, ri)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Rp), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(ap, bp)
    y = y[:, :S, :R]
    return y, y[:, -1].astype(jnp.float32)
