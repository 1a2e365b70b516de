"""Pallas TPU kernel: blockwise symmetric quantize->dequantize.

The FL round applies this to every leaf of a model-sized update pytree each
round (paper §4.3 "gradient quantization") — an elementwise+rowreduce op that
is purely HBM-bandwidth-bound, so the kernel's job is one pass: read a VMEM
tile, compute per-block scales, round, dequantize, write back.  Straight-
through semantics (returns dequantized values; wire format is int{bits} +
one f32 scale per block, accounted in core.compression.payload_bytes).

Layout: input flattened to [R, block]; grid tiles R.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_TILE = 8


def _kernel(x_ref, o_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)              # [rows, block]
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    y = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    o_ref[...] = y.astype(o_ref.dtype)


def quantize_dequant_blocks(xb, bits: int, interpret: bool):
    """xb: [R, block] float; returns same shape/dtype.

    Arbitrary R: the row dim is padded here to a tile multiple (zero rows
    quantize to zero — scale falls back to 1.0 — so the pad is inert) and
    sliced back off, so odd leaf sizes route to the kernel instead of
    tripping a shape assert."""
    R, block = xb.shape
    rows = min(ROWS_TILE, R)
    rows_pad = (-R) % rows
    if rows_pad:
        xb = jnp.concatenate([xb, jnp.zeros((rows_pad, block), xb.dtype)])
    y = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        name="fl_quantize",
        grid=((R + rows_pad) // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R + rows_pad, block), xb.dtype),
        interpret=interpret,
    )(xb)
    return y[:R] if rows_pad else y
