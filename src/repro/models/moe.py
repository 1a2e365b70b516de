"""Mixture-of-Experts layer with TPU-native sharding.

Design (DESIGN.md §6):
  * Expert weights at rest: E sharded over `model`, expert d_ff over `data`
    (full 2-D sharding; kimi-k2's 1T params -> ~8 GB/chip).
  * Dispatch is LOCAL per model-shard: every shard routes its data-shard's
    tokens against the full router, then sort-based capacity-gathers only the
    tokens assigned to its E/|model| local experts.  No global [T, E, C]
    one-hot dispatch tensor is ever built (GShard-style dispatch would be
    ~4e13 elements at kimi scale).
  * Train/prefill ("gather_weights"): expert weights are all-gathered over
    `data` per layer (transient ZeRO-3 gather) because tokens are big.
  * Decode ("gather_tokens"): the (tiny) token batch is all-gathered over the
    batch axes instead and weights stay fully sharded.
  * Outputs are psum-combined over `model` (each shard contributes its local
    experts' outputs) — the expert-parallel analogue of TP.

Implemented with shard_map when a mesh is active; the same inner function
runs directly (world size 1) in unit tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models import sharding as sh
from repro.models.common import act_fn


def init_moe(builder, path, d_model: int, cfg: MoEConfig, n_groups: int):
    E, F = cfg.num_experts, cfg.d_expert
    g = (n_groups,) if n_groups else ()
    pre = (None,) if n_groups else ()
    # router is tiny ([D, E]) -> replicated so routing needs no weight gather
    builder.add({}, path + ["router"], g + (d_model, E), pre + (None, None))
    builder.add({}, path + ["w1"], g + (E, d_model, F), pre + (sh.MODEL, None, sh.DATA))
    builder.add({}, path + ["w3"], g + (E, d_model, F), pre + (sh.MODEL, None, sh.DATA))
    builder.add({}, path + ["w2"], g + (E, F, d_model), pre + (sh.MODEL, sh.DATA, None))


def _route(x2d, router, cfg: MoEConfig):
    """x2d [T, D] -> (expert ids [T,K], gate weights [T,K], aux loss)."""
    logits = (x2d.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    gate, eid = jax.lax.top_k(probs, cfg.top_k)                   # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    E = cfg.num_experts
    hard = jax.nn.one_hot(eid[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(hard.mean(0) * probs.mean(0))
    return eid, gate.astype(x2d.dtype), aux


def _dispatch_indices(eid, gate, e_lo: int, e_n: int, capacity: int):
    """Sort-based capacity dispatch for local experts [e_lo, e_lo+e_n).

    Returns tok_idx [e_n, C] (into the flat token dim; slot 0 used for
    dropped/empty with gate 0) and gates [e_n, C]."""
    T, K = eid.shape
    flat_e = eid.reshape(-1)                                       # [T*K]
    flat_g = gate.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    local = flat_e - e_lo
    in_range = (local >= 0) & (local < e_n)
    key = jnp.where(in_range, local, e_n)                          # out-of-range last
    order = jnp.argsort(key, stable=True)
    k_sorted = key[order]
    # rank within each expert segment
    seg_start = jnp.searchsorted(k_sorted, jnp.arange(e_n + 1))
    rank = jnp.arange(T * K) - seg_start[jnp.clip(k_sorted, 0, e_n)]
    keep = (k_sorted < e_n) & (rank < capacity)
    e_slot = jnp.where(keep, k_sorted, e_n)                        # drop -> row e_n
    c_slot = jnp.where(keep, rank, 0)
    tok_idx = jnp.zeros((e_n + 1, capacity), jnp.int32).at[e_slot, c_slot].set(
        flat_t[order].astype(jnp.int32), mode="drop")
    gates = jnp.zeros((e_n + 1, capacity), flat_g.dtype).at[e_slot, c_slot].set(
        jnp.where(keep, flat_g[order], 0), mode="drop")
    return tok_idx[:e_n], gates[:e_n]


def _expert_ffn(xs, w1, w3, w2, act: str):
    """xs [E, C, D] through per-expert gated FFN."""
    h1 = jnp.einsum("ecd,edf->ecf", xs, w1)
    if act in ("swiglu", "geglu"):
        inner = act_fn({"swiglu": "silu", "geglu": "gelu"}[act])
        h = inner(h1) * jnp.einsum("ecd,edf->ecf", xs, w3)
    else:
        h = act_fn(act)(h1)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _moe_local(x, router, w1, w3, w2, *, cfg: MoEConfig, act: str,
               model_axis, f_axes, token_axes, mode: str):
    """Per-shard MoE body.  x [B_loc, S, D] (tokens local to this data shard,
    replicated over `model`).  w* local: [E_loc, D, F_loc] etc.

    f_axes:     mesh axes the expert F dim is sharded over at rest.
    token_axes: mesh axes the token batch is sharded over (may be () for
                batch-1 decode)."""
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    T = x2d.shape[0]
    E_loc = w1.shape[0]
    midx = jax.lax.axis_index(model_axis) if model_axis else 0
    e_lo = midx * E_loc

    if mode == "gather_weights":
        # ZeRO-3 style transient gather of the expert F dim.
        if f_axes:
            w1 = jax.lax.all_gather(w1, f_axes, axis=2, tiled=True)
            w3 = jax.lax.all_gather(w3, f_axes, axis=2, tiled=True)
            w2 = jax.lax.all_gather(w2, f_axes, axis=1, tiled=True)
        eid, gate, aux = _route(x2d, router, cfg)
        cap = max(int(T * cfg.top_k * cfg.capacity_factor / cfg.num_experts), 4)
        tok_idx, gates = _dispatch_indices(eid, gate, e_lo, E_loc, cap)
        xs = x2d[tok_idx.reshape(-1)].reshape(E_loc, cap, D)
        ys = _expert_ffn(xs, w1, w3, w2, act)
        out = jnp.zeros_like(x2d).at[tok_idx.reshape(-1)].add(
            (gates[..., None] * ys).reshape(-1, D))
        if model_axis:
            out = jax.lax.psum(out, model_axis)
            aux = jax.lax.pmean(aux, model_axis)
    else:  # gather_tokens (decode): replicate the tiny batch, keep F sharded
        if token_axes:
            x2d = jax.lax.all_gather(x2d, token_axes, axis=0, tiled=True)
        Tg = x2d.shape[0]
        eid, gate, aux = _route(x2d, router, cfg)
        cap = max(int(Tg * cfg.top_k * cfg.capacity_factor / cfg.num_experts), 4)
        tok_idx, gates = _dispatch_indices(eid, gate, e_lo, E_loc, cap)
        xs = x2d[tok_idx.reshape(-1)].reshape(E_loc, cap, D)
        ys = _expert_ffn(xs, w1, w3, w2, act)        # partial over F_loc
        out = jnp.zeros_like(x2d).at[tok_idx.reshape(-1)].add(
            (gates[..., None] * ys).reshape(-1, D))
        if model_axis:
            out = jax.lax.psum(out, model_axis)
        if f_axes:
            out = jax.lax.psum(out, f_axes)          # sum F partials
            aux = jax.lax.pmean(aux, f_axes)
        if token_axes:
            didx = jax.lax.axis_index(token_axes)
            out = jax.lax.dynamic_slice_in_dim(out, didx * T, T, axis=0)
    return out.reshape(B, S, D), aux


def moe_apply(p, x, *, cfg: MoEConfig, act: str, mode: str = "gather_weights"):
    """x [B, S, D]; p has router/w1/w3/w2 (already sliced to this layer)."""
    mesh = sh.get_mesh()
    if mesh is None:
        out, aux = _moe_local(x, p["router"], p["w1"], p["w3"], p["w2"],
                              cfg=cfg, act=act, model_axis=None, f_axes=(),
                              token_axes=(), mode="gather_weights")
        return out, aux

    batch = sh.batch_axes(mesh)
    model_axis = sh.MODEL if sh.MODEL in mesh.axis_names else None
    data_ax = sh.DATA if sh.DATA in mesh.axis_names else None
    # shard the token batch only over axes its size divides by
    tok_axes = []
    rem = x.shape[0]
    for a in batch:
        if rem % mesh.shape[a] == 0:
            tok_axes.append(a)
            rem //= mesh.shape[a]
    tok_axes = tuple(tok_axes)
    x_spec = P(tok_axes if len(tok_axes) != 1 else tok_axes[0], None, None) \
        if tok_axes else P(None, None, None)
    f_axes = (data_ax,) if data_ax else ()
    specs = dict(
        router=P(None, None),
        w1=P(model_axis, None, data_ax),
        w3=P(model_axis, None, data_ax),
        w2=P(model_axis, data_ax, None),
    )
    fn = partial(_moe_local, cfg=cfg, act=act, model_axis=model_axis,
                 f_axes=f_axes,
                 token_axes=tok_axes if mode == "gather_tokens" else (),
                 mode=mode)
    out, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, specs["router"], specs["w1"], specs["w3"], specs["w2"]),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    return out, aux
