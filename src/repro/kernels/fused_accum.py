"""Pallas TPU kernel: fused staleness-weighted accumulate.

``acc = sum_i w_i * (1 + s_i)^(-a) * d_i`` over the slot dim of one leaf in
a SINGLE HBM pass — replacing the unfused weight-then-sum tree maps in
core/pipeline.py (one elementwise multiply materializing a full [K, ...]
intermediate, then a reduction reading it back).  The discount formula is
the FedBuff polynomial from pipeline.staleness_weights, computed in-kernel
from the raw weights so the weighted stack never touches HBM.

The slot count K rides along in the block (commit buffers are small — the
VMEM budget is K * rows * block * 4 bytes, comfortably inside 16 MB for any
realistic buffer); the grid tiles rows.  Interpret mode (CPU) evaluates the
whole stack as one grid step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_TILE = 8


def _kernel(x_ref, w_ref, s_ref, a_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)               # [K, rows, block]
    w = w_ref[...].astype(jnp.float32)               # [K, 1]
    s = s_ref[...].astype(jnp.float32)               # [K, 1]
    a = a_ref[0, 0].astype(jnp.float32)
    w_eff = w * (1.0 + s) ** (-a)                    # FedBuff discount
    o_ref[...] = (x * w_eff[:, :, None]).sum(0).astype(o_ref.dtype)


def fused_accum_blocks(xb, w, s, alpha, interpret: bool):
    """xb: [K, R, block] f32; w, s: [K, 1] f32; alpha: [1, 1] f32.
    Returns the [R, block] f32 discounted weighted sum over slots."""
    K, R, block = xb.shape
    rows = R if interpret else min(ROWS_TILE, R)
    rows_pad = (-R) % rows
    if rows_pad:
        # zero rows fill the last tile: part of packing the slot stack, and
        # XLA merges this concatenate with the bucket's own (ops.pack_blocks)
        with jax.named_scope("fl.commit.pack"):
            xb = jnp.concatenate(
                [xb, jnp.zeros((K, rows_pad, block), xb.dtype)], axis=1)
    Rp = R + rows_pad
    y = pl.pallas_call(
        _kernel,
        name="fl_accum",
        grid=(Rp // rows,),
        in_specs=[
            pl.BlockSpec((K, rows, block), lambda i: (0, i, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, block), jnp.float32),
        interpret=interpret,
    )(xb, w, s, alpha)
    return y[:R] if rows_pad else y
