"""Weights and token streams made from ``--seed``.

Weights are made by the benchmark, not by the program, so that the
reference can make the same ones: one jitted call draws every leaf of a
reference layout (``references/<name>.layout``) on the device, in the
configuration's dtype.  Token batches are drawn on the host with numpy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the high 32 bits are
    folded in, not dropped)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def shapes(layout, dtype):
    """The layout as a tree of ShapeDtypeStructs."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], dtype), layout,
                        is_leaf=_is_spec)


@functools.lru_cache(maxsize=None)
def _init_fn(treedef, specs, dtype):
    def init(key):
        leaves = []
        for i, (shape, (kind, arg)) in enumerate(specs):
            if kind == "normal":
                x = arg * jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)
            else:
                x = jnp.full(shape, arg, jnp.float32)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)
    return jax.jit(init)


def init_params(layout, seed: int, dtype):
    """Every leaf in one jitted call: normal leaves as ``std * N(0, 1)``,
    the rest constant, each drawn from its own fold of the seed's key."""
    specs, treedef = jax.tree.flatten(layout, is_leaf=_is_spec)
    return _init_fn(treedef, tuple(specs), jnp.dtype(dtype))(seed_key(seed))


def token_batch(seed: int, client: int, step: int, shape, lo: int, hi: int):
    """Token ids in [lo, hi) of ``shape`` + one position (inputs and
    next-token targets), the same for the same (seed, client, step)."""
    rng = np.random.default_rng([seed, client, step])
    return rng.integers(lo, hi, tuple(shape[:-1]) + (shape[-1] + 1,),
                        dtype=np.int32)


def vocab_share(client: int, n_clients: int, vocab: int):
    """Non-IID split: client c draws from its own half of the vocabulary,
    the halves of neighbouring clients overlapping."""
    lo = (client * vocab) // (2 * n_clients)
    return lo, lo + vocab // 2
