"""Pallas TPU kernel: fused FedProx local SGD update.

    w <- w - lr * (g + mu * (w - w0))

Three-operand elementwise fusion: the unfused jnp version reads w twice and
materialises (w - w0) and the corrected gradient in HBM; the kernel does one
read of each operand and one write per VMEM tile (HBM traffic 4 arrays vs 6+).
This is the inner-loop op of every client's every local step, across every
parameter of the model — the FL analogue of a fused optimizer kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024 * 8


def _kernel(w_ref, g_ref, w0_ref, o_ref, *, lr: float, mu: float):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    o_ref[...] = (w - lr * (g + mu * (w - w0))).astype(o_ref.dtype)


def fedprox_update_flat(w, g, w0, lr: float, mu: float, interpret: bool):
    """w,g,w0: flat [N] arrays padded to a TILE multiple."""
    n = w.shape[0]
    tile = min(TILE, n)
    assert n % tile == 0
    spec = pl.BlockSpec((tile,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_kernel, lr=lr, mu=mu),
        name="fl_fedprox_update",
        grid=(n // tile,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), w.dtype),
        interpret=interpret,
    )(w, g, w0)
