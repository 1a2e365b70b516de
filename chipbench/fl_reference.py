"""Plain references of what the timed federated paths compute, and the
comparison that decides a run's ``correct``.

Independent of the code under test: nothing here imports ``repro``.  The
model itself comes from ``chipbench/references/<name>.py`` (named by the
configuration file).  Parameters are held in the configuration's dtype
between steps, as the configuration states; every step is computed in
float32, and every matrix product at ``highest`` precision.

* ``sync_round``: one synchronous FedAvg/FedProx round with sequential
  clients: H local SGD steps per client (gradients accumulated over row
  blocks of the batch, which is exact for a mean loss), the client delta
  quantized per 256-wide block of each leaf's last dim with deterministic
  rounding, the data-size weighted mean, and the FedAvg apply.  Pairwise
  secure masks cancel in the sum, so the reference has none: masks that
  failed to cancel would show as a gap.
* ``buffer_commit``: one buffered asynchronous commit of K slot deltas
  with the staleness discount ``(1 + s)^-a``, quantized onto one
  commit-common grid per block (the integer-domain secure scheme, whose
  masks cancel exactly), normalised by the undiscounted weight mass.
* ``leaf_change_norms``, ``leaf_gaps``, ``rel_gap``: the arithmetic of the
  numbers a run is judged by (``lm.step_checks``); a value that is not
  finite reads as an infinite gap.

``matmul(operands, result)`` makes the ``mm`` that every reference uses: a
product accumulated in float32 at ``highest``, of float32 operands (the
reference), of operands rounded to float8 (e4m3) in the forward pass (the
control), or of bfloat16 operands with the product rounded to bfloat16 (the
arithmetic of a bfloat16 model, its cotangents rounded alike: a second
reference that ``calibrate.py --references`` holds the first against).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 256


def reference_module(name: str):
    return importlib.import_module(f"chipbench.references.{name}")


def _float8(x):
    """Round to float8 e4m3 straight through: the backward pass sees the
    float32 cotangent."""
    x8 = x.astype(jnp.float8_e4m3fn).astype(F32)
    return x + jax.lax.stop_gradient(x8 - x)


OPERANDS = {"float32": lambda x: x,
            "bfloat16": lambda x: x.astype(jnp.bfloat16),
            "float8": _float8}
RESULTS = {"float32": lambda y: y,
           "bfloat16": lambda y: y.astype(jnp.bfloat16).astype(F32)}


def matmul(operands: str = "float32", result: str = "float32"):
    """``mm(spec, a, b)``: an einsum accumulated in float32 at highest
    precision, its operands first rounded to ``operands`` and its product
    to ``result`` (see the module's docstring)."""
    if operands not in OPERANDS or result not in RESULTS:
        raise ValueError(f"unknown reference precision {operands!r}, "
                         f"{result!r}")
    cast, rnd = OPERANDS[operands], RESULTS[result]

    def mm(spec, a, b):
        return rnd(jnp.einsum(spec, cast(a.astype(F32)), cast(b.astype(F32)),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=F32))
    return mm


def quantize_dequant(x, bits: int):
    """Symmetric per-block quantize round trip along the last dim (blocks of
    256, zero padded), round half to even, clipped to the signed range."""
    shape = x.shape
    L = shape[-1] if x.ndim else 1
    xb = x.reshape(shape or (1,)).astype(F32)
    pad = (-L) % BLOCK
    xb = jnp.pad(xb, [(0, 0)] * (xb.ndim - 1) + [(0, pad)])
    xb = xb.reshape(*xb.shape[:-1], -1, BLOCK)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(xb), -1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    y = jnp.clip(jnp.round(xb / scale), -qmax - 1, qmax) * scale
    y = y.reshape(*y.shape[:-2], -1)[..., :L]
    return y.reshape(shape)


def common_grid_sum(x, w_eff, bits: int):
    """sum_k Q(w_k x_k) on ONE grid per block shared by the K slots: x is
    [K, ...]; the scale is the largest weighted magnitude of the block over
    all slots."""
    K, shape = x.shape[0], x.shape[1:]
    L = shape[-1] if shape else 1
    xb = x.reshape((K,) + (shape or (1,))).astype(F32)
    pad = (-L) % BLOCK
    xb = jnp.pad(xb, [(0, 0)] * (xb.ndim - 1) + [(0, pad)])
    xb = xb.reshape(K, -1, BLOCK) * w_eff.reshape(K, 1, 1)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(xb), axis=(0, 2), keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xb / scale), -qmax - 1, qmax)
    y = q.sum(0) * scale[0]
    y = y.reshape(*(shape or (1,))[:-1], -1)[..., :L]
    return y.reshape(shape)


# ------------------------------------------------------------- sync round

def make_client_step(ref, model: dict, mm, rows: int, lr: float, mu: float):
    """jit ``(w, w0, tokens[b,S], targets) -> (w', loss)``: one local SGD
    step (FedProx term ``mu (w - w0)``), the gradient of the batch mean
    accumulated over blocks of ``rows`` rows."""
    def block_loss(w32, tok, tgt):
        return ref.loss(w32, tok, tgt, model, mm)

    @jax.jit
    def step(w, w0, tokens, targets):
        b = tokens.shape[0]
        nb = b // rows
        w32 = jax.tree.map(lambda a: a.astype(F32), w)
        if nb == 1:
            l, g = jax.value_and_grad(block_loss)(w32, tokens, targets)
        else:
            def body(carry, xs):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(block_loss)(w32, *xs)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

            zero = jax.tree.map(jnp.zeros_like, w32)
            (g, l), _ = jax.lax.scan(body, (zero, jnp.float32(0.0)), (
                tokens.reshape(nb, rows, -1), targets.reshape(nb, rows, -1)))
            g = jax.tree.map(lambda a: a / nb, g)
            l = l / nb
        new = jax.tree.map(
            lambda p, gp, p0: (p.astype(F32) - lr * (gp + mu * (
                p.astype(F32) - p0.astype(F32)))).astype(p.dtype),
            w, g, w0)
        return new, l
    return step


@functools.partial(jax.jit, static_argnames="bits")
def _fold_client(acc, w, w0, weight, bits):
    return jax.tree.map(
        lambda a, p, p0: a + weight * (quantize_dequant(
            p.astype(F32) - p0.astype(F32), bits) if bits else
            p.astype(F32) - p0.astype(F32)), acc, w, w0)


def sync_round(step, params, client_batches, weights, bits: int):
    """One round from ``params`` (the configuration's dtype).
    ``client_batches``: per client, a list of H (tokens, targets).  Returns
    (new params, mean client loss), the loss being each client's mean over
    its H steps, averaged over clients."""
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses = []
    for batches, wt in zip(client_batches, weights):
        w, ls = params, []
        for tokens, targets in batches:
            w, l = step(w, params, tokens, targets)
            ls.append(float(l))
        acc = _fold_client(acc, w, params, jnp.float32(wt), bits)
        losses.append(float(np.mean(ls)))
        del w
    total = float(np.sum(weights))
    new = jax.tree.map(lambda p, a: (p.astype(F32) + a / total).astype(p.dtype),
                       params, acc)
    return new, float(np.mean(losses))


# ---------------------------------------------------------- buffer commit

@functools.partial(jax.jit, static_argnames="bits")
def _commit_leaf(p, x, w_raw, w_eff, bits):
    d = common_grid_sum(x, w_eff, bits) / jnp.maximum(w_raw.sum(), 1e-12)
    return (p.astype(F32) + d).astype(p.dtype), jnp.sum(d * d)


def buffer_commit(params, deltas, weights, staleness, mask, exponent: float,
                  bits: int):
    """One commit, leaf by leaf.  Returns (new params, delta norm)."""
    w_raw = jnp.asarray(weights, F32) * jnp.asarray(mask, F32)
    w_eff = w_raw * (1.0 + jnp.asarray(staleness, F32)) ** (-exponent)
    leaves, treedef = jax.tree.flatten(params)
    new, sq = [], 0.0
    for p, x in zip(leaves, jax.tree.leaves(deltas)):
        n, s = _commit_leaf(p, x, w_raw, w_eff, bits)
        new.append(n)
        sq += float(s)
    return jax.tree.unflatten(treedef, new), float(np.sqrt(sq))


# ------------------------------------------------------------- comparison

@jax.jit
def leaf_change_norms(a, b):
    """Per-leaf ||a - b||, in float32, as one vector."""
    return jnp.stack([jnp.linalg.norm((x.astype(F32) - y.astype(F32)).ravel())
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_gaps(prog, ref, grad, floor_frac: float = 1e-3):
    """Per leaf |prog_l - ref_l| / max(ref_l, median_l ref), NaN for the
    leaves whose reference gradient norm ``grad`` is under ``floor_frac``
    of the median leaf's (a leaf below it moves by round-off alone).
    Returns the gaps and the indices left out."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    grad = np.asarray(grad, np.float64)
    keep = grad >= floor_frac * float(np.median(grad))
    gaps = np.abs(prog - ref) / np.maximum(ref, float(np.median(ref)))
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    return np.where(keep, gaps, np.nan), [int(i) for i in
                                          np.flatnonzero(~keep)]


def rel_gap(a: float, b: float) -> float:
    g = abs(a - b) / max(abs(b), 1e-30)
    return g if np.isfinite(g) else float("inf")
