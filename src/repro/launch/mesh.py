"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because dryrun.py must set
XLA_FLAGS before any jax initialisation.

Mesh semantics (DESIGN.md §2): `pod` = site (HPC cluster / cloud region),
`data` = federated-client / batch axis inside a site, `model` = tensor /
expert / sequence parallel axis inside a client.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto.  The model, round and
    commit code shard through GSPMD propagation (``with_sharding_constraint``,
    ``vmap(spmd_axis_name=...)``), which only Auto axes accept; jax's own
    default is Explicit.  Every mesh in the repo is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    n = n_devices or len(jax.devices())
    if n >= 8:
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    if n >= 4:
        return make_mesh((2, 2), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))
