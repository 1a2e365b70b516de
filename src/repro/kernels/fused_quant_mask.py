"""Pallas TPU kernels: the fused commit path — compress + mask + accumulate
in ONE pass over the slot stack.

The unfused pipeline (core/pipeline.py) materializes a full model-sized
[K, ...] intermediate between every stage of
compress -> weight -> secure_mask -> aggregate.  Each stage is elementwise
or a slot reduction, i.e. pure HBM bandwidth, so fusing them into a single
kernel that reads each slot once and writes the reduced leaf once is the
whole win.  Two kernel variants over one blocked [K, rows, block] tile:

  * ``_plain_kernel`` — per-slot top-k, per-slot per-block symmetric
    quantize (identical algebra to the unfused core.compression stages),
    then the staleness-discounted weighted sum over slots.
  * ``_secure_kernel`` — per-slot top-k, ONE commit-common per-block scale,
    integer quantize, pairwise masking in the quantized INTEGER domain
    (uint32 modular arithmetic on the wire words, as in standard
    finite-ring SecAgg), sum, dequantize.  Mask words cancel EXACTLY under
    wraparound — no float cancellation error — so the output equals the
    unmasked quantized sum bit for bit while each slot's wire word stays
    uniformly masked.  This is also what lets the wire accounting charge
    quantized ring words instead of dense f32 masks (secure_agg.
    masked_payload_bytes).

The mask PRF is a portable integer avalanche hash ("lowbias32"-style) over
(pair seed, element index) — pure vector uint32 ops, so the Pallas body,
interpret mode on CPU, and the jnp oracle in kernels/ref.py share one
implementation with identical bits.  Pair seeds arrive as a symmetric
[K, K] uint32 matrix derived outside the kernel from the commit key
(secure_agg.pair_seeds); the signed coefficients sgn(id_j - id_i)*p_i*p_j
arrive as int32 in {-1, 0, +1} and are applied as two's-complement
multiplies, exact under wraparound.

Shard invariance (what makes these kernels shard_map-safe): every
per-block quantity — the plain kernel's per-slot per-block scale, the
secure kernel's commit-common per-row scale, the top-k threshold — is a
function of ONE row (one whole last-dim block), so sharding the row dim
across devices changes nothing bitwise.  The only position-dependent
quantity is the secure kernel's element index stream: ``base`` must be
the GLOBAL element index of the shard's row 0 (callers under shard_map
offset it by flat_shard_index * local_rows * block, kernels/ops.py), so
PRF mask words are derived from global positions and cancel bitwise
across any mesh shape.  ``base`` may be a traced uint32 — it is a kernel
operand, not a compile-time constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

ROWS_TILE = 8
N_ITERS = 32                      # top-k threshold bisection iterations
_GOLDEN = np.uint32(0x9E3779B9)   # element-index mixing constant


def hash_u32(x):
    """"lowbias32"-style avalanche hash, uint32 -> uint32."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def mask_total_u32(seeds_row, coef_row, idx):
    """Slot i's summed pairwise masks over its K peers, uint32 modular:
    ``sum_j coef[j] * PRF(seed[j], idx)``.  ``idx`` is the [rows, block]
    global element index; coefficients enter as two's-complement uint32 so
    the signed combination is exact under wraparound."""
    cu = jax.lax.bitcast_convert_type(coef_row.astype(jnp.int32), jnp.uint32)
    bits = hash_u32(idx[None] * _GOLDEN + seeds_row[:, None, None])
    return (cu[:, None, None] * bits).sum(0, dtype=jnp.uint32)


def topk_threshold_mask(mag, k: int):
    """Boolean keep-mask for per-block magnitude top-k over the last dim:
    keep |x| >= the k-th largest magnitude, ties kept.  Fixed-iteration
    bisection on [0, max] (compare+popcount per iteration — VPU-friendly,
    no sort), same scheme as kernels/topk_sparsify."""
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        gt = jnp.sum(mag >= mid, axis=-1, keepdims=True) > k
        return jnp.where(gt, mid, lo), jnp.where(gt, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_ITERS, body, (lo, hi))
    cnt_lo = jnp.sum(mag >= lo, axis=-1, keepdims=True)
    thresh = jnp.where(cnt_lo <= k, lo, hi)
    return mag >= thresh


def _plain_kernel(x_ref, w_ref, s_ref, a_ref, o_ref, *, bits: int, k: int):
    """top-k -> per-slot per-block quantize -> discounted weighted sum."""
    x = x_ref[...].astype(jnp.float32)               # [K, rows, block]
    if k:
        x = jnp.where(topk_threshold_mask(jnp.abs(x), k), x, 0.0)
    if bits:
        qmax = 2.0 ** (bits - 1) - 1
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
        scale = jnp.where(scale == 0, 1.0, scale)
        x = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    w = w_ref[...].astype(jnp.float32)               # [K, 1]
    s = s_ref[...].astype(jnp.float32)               # [K, 1]
    a = a_ref[0, 0].astype(jnp.float32)
    w_eff = w * (1.0 + s) ** (-a)
    o_ref[...] = (x * w_eff[:, :, None]).sum(0).astype(o_ref.dtype)


def _secure_kernel(x_ref, w_ref, seeds_ref, coef_ref, base_ref, o_ref,
                   *, bits: int, k: int):
    """top-k -> commit-common scale -> integer quantize -> integer-domain
    pairwise mask -> sum -> dequantize.  Every slot must quantize onto ONE
    grid (the commit-common per-block scale) or the integer masks could
    not cancel in the sum."""
    x = x_ref[...].astype(jnp.float32)               # [K, rows, block]
    K, rows, block = x.shape
    if k:
        x = jnp.where(topk_threshold_mask(jnp.abs(x), k), x, 0.0)
    w = w_ref[...].astype(jnp.float32)               # [K, 1] eff. weights
    y = x * w[:, :, None]
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(y), axis=(0, 2), keepdims=True) / qmax  # [1,r,1]
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(y / scale), -qmax - 1, qmax).astype(jnp.int32)
    qu = jax.lax.bitcast_convert_type(q, jnp.uint32)
    off = (pl.program_id(0) * (rows * block)).astype(jnp.uint32)
    idx = (off + base_ref[0, 0]
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, block), 0)
           * np.uint32(block)
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, block), 1))
    # Each slot's WIRE word: its quantized word plus its masks
    # coef[i,j] * PRF(seed[i,j]), summed mod 2^32 — the sum mask_total_u32
    # forms for the oracle, so the bits agree.  Peers loop with scalar
    # reads: Mosaic has no unsigned reduction over a peer axis.
    total = jnp.zeros((rows, block), jnp.uint32)
    for i in range(K):
        total = total + qu[i]
        for j in range(K):
            cu = coef_ref[i, j].astype(jnp.uint32)   # two's complement
            total = total + cu * hash_u32(idx * _GOLDEN + seeds_ref[i, j])
    summed = jax.lax.bitcast_convert_type(total, jnp.int32).astype(jnp.float32)
    o_ref[...] = (summed * scale[0]).astype(o_ref.dtype)


def _rows_tiling(R: int, interpret: bool):
    """Interpret mode runs the whole stack as one grid step (a vectorised
    jnp evaluation — a Python grid loop over hundreds of tiles would crawl
    on CPU); the TPU path tiles rows for VMEM."""
    rows = R if interpret else min(ROWS_TILE, R)
    return rows, (-R) % rows


def _fill_last_tile(xb, rows_pad: int):
    """Zero rows that fill the last tile.  They are part of packing the slot
    stack: XLA merges this concatenate with the bucket's own
    (``ops.pack_blocks``), so it carries the same scope."""
    K, _, block = xb.shape
    with jax.named_scope("fl.commit.pack"):
        return jnp.concatenate(
            [xb, jnp.zeros((K, rows_pad, block), xb.dtype)], axis=1)


def plain_commit_blocks(xb, w, s, alpha, *, bits: int, k: int,
                        interpret: bool):
    """xb: [K, R, block] f32 -> [R, block] f32 reduced leaf."""
    K, R, block = xb.shape
    rows, rows_pad = _rows_tiling(R, interpret)
    if rows_pad:
        xb = _fill_last_tile(xb, rows_pad)
    Rp = R + rows_pad
    y = pl.pallas_call(
        functools.partial(_plain_kernel, bits=bits, k=k),
        name="fl_plain_commit",
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


def secure_commit_blocks(xb, w_eff, seeds, coef, base, *, bits: int, k: int,
                         interpret: bool):
    """xb: [K, R, block] f32; seeds: [K, K] uint32 (symmetric pair seeds);
    coef: [K, K] int32 in {-1, 0, +1}; base: [1, 1] uint32 leaf offset into
    the commit-wide element index space.  Returns [R, block] f32."""
    K, R, block = xb.shape
    rows, rows_pad = _rows_tiling(R, interpret)
    if rows_pad:
        xb = _fill_last_tile(xb, rows_pad)
    Rp = R + rows_pad
    y = pl.pallas_call(
        functools.partial(_secure_kernel, bits=bits, k=k),
        name="fl_secure_commit",
        grid=(Rp // rows,),
        in_specs=[
            pl.BlockSpec((K, rows, block), lambda i: (0, i, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, block), jnp.float32),
        interpret=interpret,
    )(xb, w_eff, seeds, coef, base)
    return y[:R] if rows_pad else y
