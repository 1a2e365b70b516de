"""Pallas TPU kernel: per-block magnitude top-k sparsification.

Semantics (shared with the jnp oracle): keep every entry whose |magnitude|
is >= the k-th largest magnitude in its block, zero the rest.  Instead of a
sort (unsupported/slow on the TPU vector unit), the threshold is found by
fixed-iteration bisection on [0, max|x|] — 32 iterations reach f32-epsilon
resolution, and every iteration is a vectorised compare+popcount, which maps
cleanly onto the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_TILE = 8
N_ITERS = 32


def _kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)              # [rows, block]
    mag = jnp.abs(x)
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(mag >= mid, axis=-1, keepdims=True)
        gt = cnt > k
        # keep invariant: count(>=lo) > k >= count(>=hi)... converge lo -> m_k
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, N_ITERS, body, (lo, hi))
    # lo converges to just below the k-th magnitude; keep mag >= lo while
    # breaking the ">k" overshoot by comparing against hi when exact.
    cnt_lo = jnp.sum(mag >= lo, axis=-1, keepdims=True)
    thresh = jnp.where(cnt_lo <= k, lo, hi)
    o_ref[...] = jnp.where(mag >= thresh, x, 0.0).astype(o_ref.dtype)


def topk_sparsify_blocks(xb, k: int, interpret: bool):
    """Arbitrary R: rows are padded to a tile multiple (all-zero rows keep a
    threshold of 0 and stay zero) and sliced back, so odd leaf sizes route
    to the kernel instead of tripping a shape assert."""
    R, block = xb.shape
    rows = min(ROWS_TILE, R)
    rows_pad = (-R) % rows
    if rows_pad:
        xb = jnp.concatenate([xb, jnp.zeros((rows_pad, block), xb.dtype)])
    y = pl.pallas_call(
        functools.partial(_kernel, k=k),
        name="fl_topk",
        grid=((R + rows_pad) // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R + rows_pad, block), xb.dtype),
        interpret=interpret,
    )(xb)
    return y[:R] if rows_pad else y
