"""Pallas TPU kernel: Mamba chunked selective-scan inner chunk.

Computes the diagonal linear recurrence  h_t = a_t * h_{t-1} + b_t  for one
chunk of L timesteps, emitting all intermediate states (needed for y = C.h)
plus the chunk-final state that the outer lax.scan carries.

TPU adaptation (DESIGN.md §7): the CUDA Mamba kernel streams the whole
sequence through SRAM with a warp-level scan; on TPU we instead tile
(batch x d_inner) across the grid, keep an L x d_tile x N working set in
VMEM, and run the time loop sequentially *inside* the kernel — the
recurrence is elementwise over [d_tile, N] lanes, so the VPU stays full
while HBM sees exactly one read of (a, b) and one write of hs per element.
d_inner is `model`-sharded outside the kernel (recurrent-scan sharding), so
no cross-chip traffic occurs inside a chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

D_TILE = 128


def _kernel(a_ref, b_ref, h0_ref, hs_ref, hl_ref):
    L = a_ref.shape[1]

    def body(t, h):
        h = a_ref[0, t] * h + b_ref[0, t]          # [d_tile, N]
        hs_ref[0, t] = h
        return h

    h = jax.lax.fori_loop(0, L, body, h0_ref[0])
    hl_ref[0] = h


def selective_scan_chunk_kernel(a, b, h0, interpret: bool):
    """a, b: [B, L, D, N] f32;  h0: [B, D, N] f32.
    Returns (hs [B, L, D, N], h_last [B, D, N])."""
    B, L, D, N = a.shape
    dt = min(D_TILE, D)
    assert D % dt == 0
    hs, hl = pl.pallas_call(
        _kernel,
        name="fl_selective_scan",
        grid=(B, D // dt),
        in_specs=[
            pl.BlockSpec((1, L, dt, N), lambda bi, di: (bi, 0, di, 0)),
            pl.BlockSpec((1, L, dt, N), lambda bi, di: (bi, 0, di, 0)),
            pl.BlockSpec((1, dt, N), lambda bi, di: (bi, di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, dt, N), lambda bi, di: (bi, 0, di, 0)),
            pl.BlockSpec((1, dt, N), lambda bi, di: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, D, N), a.dtype),
            jax.ShapeDtypeStruct((B, D, N), a.dtype),
        ],
        interpret=interpret,
    )(a, b, h0)
    return hs, hl
