"""Public wrappers around the Pallas kernels.

Handles flattening/padding to tile multiples, dtype plumbing, interpret-mode
selection (interpret=True on CPU — the container validates kernel *bodies*;
TPU is the deployment target), mesh-native execution, and the custom VJP for
the selective scan (the only kernel that sits under autodiff:
compression/update kernels run on post-gradient values).

Mesh-native fused commit (the GSPMD story)
------------------------------------------
``pallas_call`` carries no GSPMD sharding rules, so a bare kernel call under
an active mesh would force XLA to all-gather its operands.  Every fused
entry point here therefore wraps its kernel in ``shard_map`` over the active
mesh (``models.sharding.get_mesh()``/``fusion_axes()``) whenever one is
active, sharding the ROW dim of the blocked ``[K, rows, block]`` commit
stack: rows are whole last-dim blocks — the same block membership rule as
``core.compression._to_blocks`` — so per-block quantize scales and top-k
thresholds are device-local and bitwise identical to the unsharded
blocking.  The slot-dim (K) weighted sum is a purely local reduce (K is
replicated), so no collective runs inside the kernel wrapper at all.  The
one shard-dependent quantity is the secure kernel's element-index stream:
mask PRF words are derived from GLOBAL block indices
(``sharding.flat_shard_index`` offsets each shard's base), keeping uint32
mask cancellation bitwise across any mesh shape.

The mesh is read at CALL time, which is why the fused/compress entry
points are NOT wrapped in module-level ``jax.jit``: a shared jit cache
keyed only on shapes would silently replay a no-mesh trace after a mesh
became active (or vice versa).  Instead each entry point looks up a
jitted closure from an ``lru_cache`` keyed on (mesh, shard axes, static
params) — same compiled numerics as a plain ``@jax.jit``, one compiled
program per mesh configuration, no staleness.

Leaf bucketing
--------------
``fused_*_tree`` take the FLATTENED leaf list of a slot-stacked update tree
and concatenate every leaf's blocked rows into one ``[K, R_total, block]``
bucket before the kernel call: a 100+-leaf model costs one kernel launch
(and one jit cache entry) per bucket instead of one per leaf shape.  Row
concatenation preserves block membership exactly — each row is one block of
one leaf — and the row-major element index of the bucket equals the old
per-leaf ``base`` accumulation, so per-block scales, top-k thresholds and
the secure mask stream are unchanged.  ``KERNEL_LAUNCHES`` counts launches
at call time so benchmarks can report the collapse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import fedprox_update as _fp
from repro.kernels import fused_accum as _fa
from repro.kernels import fused_quant_mask as _fqm
from repro.kernels import quantize as _q
from repro.kernels import ref as _ref
from repro.kernels import selective_scan as _ss
from repro.kernels import topk_sparsify as _tk

KERNEL_LAUNCHES = 0   # call-time pallas-launch counter (benchmarks read
#                       and reset it around a commit to see launches/call)


@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    # cached module-level lookup: the backend registry walk behind
    # jax.default_backend() is not free, and the backend cannot change
    # within a process
    return jax.default_backend() != "tpu"


def _count_launch():
    global KERNEL_LAUNCHES
    KERNEL_LAUNCHES += 1


def _mesh_axes():
    """(mesh, row-shard axes) for the active mesh, or (None, ()) when no
    mesh is active or no multi-device axis is usable.  Imported lazily:
    repro.models' package import pulls model modules that consume these
    kernels."""
    from repro.models import sharding as sh
    mesh = sh.get_mesh()
    if mesh is None:
        return None, ()
    axes = sh.fusion_axes()
    return (mesh, axes) if axes else (None, ())


def _pad_rows(xb, mult, axis):
    R = xb.shape[axis]
    pad = (-R) % mult
    if pad:
        widths = [(0, 0)] * xb.ndim
        widths[axis] = (0, pad)
        xb = jnp.pad(xb, widths)
    return xb, pad


def _shard_rows_map(mesh, axes, fn, xb):
    """Run an elementwise-by-block rows op ([R, block] -> [R, block]) with
    rows sharded over ``axes``.  Zero row padding to the shard multiple is
    a fixed point of every block kernel (scale-0 guard -> zeros stay
    zeros), so it is sliced off untouched."""
    n = math.prod(mesh.shape[a] for a in axes)
    xb, pad = _pad_rows(xb, n, 0)
    y = jax.shard_map(fn, mesh=mesh, in_specs=(P(axes, None),),
                      out_specs=P(axes, None), check_vma=False)(xb)
    return y[:-pad] if pad else y


def _shard_rows_reduce(mesh, axes, fn, xb, *consts):
    """Run a slot-reducing rows kernel ([K, R, block] -> [R, block]) with
    rows sharded over ``axes``; scalars/seed matrices replicate.  ``fn``
    receives (xb_local, flat_shard_index, *consts) — the shard index lets
    the secure kernel derive its GLOBAL element-index base.  The slot-dim
    sum is shard-local (K replicates), so no collective is emitted."""
    from repro.models import sharding as sh
    n = math.prod(mesh.shape[a] for a in axes)
    xb, pad = _pad_rows(xb, n, 1)

    def body(xb_l, *cs):
        return fn(xb_l, sh.flat_shard_index(axes, mesh), *cs)

    y = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, axes, None),) + (P(),) * len(consts),
                      out_specs=P(axes, None), check_vma=False)(xb, *consts)
    return y[:-pad] if pad else y


def _as_blocks(x, block):
    """Blocks along the LAST dim (matches core.compression's shard-local
    grouping), then collapse leading dims to rows for the kernel grid.
    Row padding to the kernels' tile multiple happens INSIDE the block
    wrappers (quantize/topk), so any leaf size routes to the kernels."""
    L = x.shape[-1] if x.ndim else 1
    xx = x.reshape(x.shape or (1,)).astype(jnp.float32)
    pad = (-L) % block
    if pad:
        xx = jnp.pad(xx, [(0, 0)] * (xx.ndim - 1) + [(0, pad)])
    rows_shape = xx.shape[:-1] + ((L + pad) // block,)
    return xx.reshape(-1, block), (pad, rows_shape)


def _from_blocks(b, meta, shape, dtype):
    pad, rows_shape = meta
    y = b.reshape(*rows_shape, -1).reshape(*rows_shape[:-1], -1)
    if pad:
        y = y[..., :-pad]
    return y.reshape(shape).astype(dtype)


def quantize_dequant(x, *, bits: int = 8, block: int = 256):
    _count_launch()
    mesh, axes = _mesh_axes()
    return _quantize_dequant_c(mesh, axes, bits, block)(x)


@functools.lru_cache(maxsize=None)
def _quantize_dequant_c(mesh, axes, bits, block):
    def quantize_dequant(x):
        xb, meta = _as_blocks(x, block)
        run = lambda b: _q.quantize_dequant_blocks(b, bits, _interpret())
        y = run(xb) if mesh is None else _shard_rows_map(mesh, axes, run, xb)
        return _from_blocks(y, meta, x.shape, x.dtype)
    return jax.jit(quantize_dequant)


def topk_sparsify(x, *, k: int, block: int = 256):
    _count_launch()
    mesh, axes = _mesh_axes()
    return _topk_sparsify_c(mesh, axes, k, block)(x)


@functools.lru_cache(maxsize=None)
def _topk_sparsify_c(mesh, axes, k, block):
    def topk_sparsify(x):
        xb, meta = _as_blocks(x, block)
        # padded zero blocks: threshold 0 keeps everything -> zeros stay
        # zero.  OK.
        run = lambda b: _tk.topk_sparsify_blocks(b, k, _interpret())
        y = run(xb) if mesh is None else _shard_rows_map(mesh, axes, run, xb)
        return _from_blocks(y, meta, x.shape, x.dtype)
    return jax.jit(topk_sparsify)


@functools.partial(jax.jit, static_argnames=("lr", "mu"))
def fedprox_update(w, g, w0, *, lr: float, mu: float = 0.0):
    shape, dtype = w.shape, w.dtype
    flat = lambda t: t.reshape(-1).astype(jnp.float32)
    wf, gf, w0f = flat(w), flat(g), flat(w0)
    tile = min(_fp.TILE, max(wf.shape[0], 1))
    pad = (-wf.shape[0]) % tile
    if pad:
        z = jnp.zeros((pad,), jnp.float32)
        wf, gf, w0f = (jnp.concatenate([a, z]) for a in (wf, gf, w0f))
    y = _fp.fedprox_update_flat(wf, gf, w0f, lr, mu, _interpret())
    if pad:
        y = y[:-pad]
    return y.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# fused commit path (kernels/fused_accum, kernels/fused_quant_mask): the
# per-update hot loop — compress + mask + accumulate in one pass over a
# slot-stacked [K, ...] leaf.  core/pipeline.py dispatches here through the
# bucketed fused_*_tree entry points; the per-leaf forms below serve tests
# and microbenchmarks.
# ---------------------------------------------------------------------------

def _stack_blocks(x, block):
    """[K, ...] slot-stacked leaf -> ([K, R, block] f32, meta).  Blocks
    along the leaf's LAST dim per slot — identical block membership to
    core.compression._to_blocks, so per-block scales agree with the
    unfused stages — with leading dims collapsed into rows."""
    K = x.shape[0]
    lead = x.shape[1:]
    xx = x.reshape((K,) + (lead or (1,))).astype(jnp.float32)
    L = xx.shape[-1]
    pad = (-L) % block
    if pad:
        xx = jnp.pad(xx, [(0, 0)] * (xx.ndim - 1) + [(0, pad)])
    return xx.reshape(K, -1, block), (pad, xx.shape[1:], lead)


def _unstack_sum(y, meta, dtype):
    """[R, block] summed blocks -> the un-padded summed leaf."""
    pad, padded_shape, lead = meta
    y = y.reshape(*padded_shape[:-1], -1)
    if pad:
        y = y[..., :-pad]
    return y.reshape(lead or ()).astype(dtype)


def pack_blocks(leaves, block):
    """Slot-stacked [K, ...] leaves -> ONE [K, R_total, block] bucket.

    Rows are whole blocks of one leaf each (identical membership to the
    per-leaf ``_stack_blocks``), so per-block scales, top-k thresholds and
    — through the bucket's row-major element index — the secure mask
    stream are unchanged vs. per-leaf kernel calls; only the launch count
    collapses from O(#leaves) to one.  Returns (bucket, metas, row
    counts)."""
    blocked, metas, rows = [], [], []
    with jax.named_scope("fl.commit.pack"):
        for leaf in leaves:
            xb, meta = _stack_blocks(leaf, block)
            blocked.append(xb)
            metas.append(meta)
            rows.append(xb.shape[1])
        return jnp.concatenate(blocked, axis=1), metas, rows


def unpack_sums(y, metas, rows, dtype=jnp.float32):
    """[R_total, block] summed bucket -> the per-leaf summed leaves."""
    out, r0 = [], 0
    with jax.named_scope("fl.commit.unpack"):
        for meta, r in zip(metas, rows):
            out.append(_unstack_sum(y[r0:r0 + r], meta, dtype))
            r0 += r
    return out


def _slot_vectors(w, staleness, exponent, K):
    wv = jnp.asarray(w, jnp.float32).reshape(K, 1)
    sv = jnp.asarray(staleness, jnp.float32).reshape(K, 1)
    av = jnp.asarray(exponent, jnp.float32).reshape(1, 1)
    return wv, sv, av


def _accum_rows(mesh, axes, xb, wv, sv, av):
    if mesh is None:
        return _fa.fused_accum_blocks(xb, wv, sv, av, _interpret())
    return _shard_rows_reduce(
        mesh, axes,
        lambda xl, _, w, s, a: _fa.fused_accum_blocks(xl, w, s, a,
                                                      _interpret()),
        xb, wv, sv, av)


def _plain_rows(mesh, axes, xb, wv, sv, av, bits, k):
    if mesh is None:
        return _fqm.plain_commit_blocks(xb, wv, sv, av, bits=bits, k=k,
                                        interpret=_interpret())
    return _shard_rows_reduce(
        mesh, axes,
        lambda xl, _, w, s, a: _fqm.plain_commit_blocks(
            xl, w, s, a, bits=bits, k=k, interpret=_interpret()),
        xb, wv, sv, av)


def _secure_rows(mesh, axes, xb, wv, seeds, coef, base, bits, k):
    bv = jnp.asarray(base, jnp.uint32).reshape(1, 1)
    if mesh is None:
        return _fqm.secure_commit_blocks(xb, wv, seeds, coef, bv, bits=bits,
                                         k=k, interpret=_interpret())
    block = xb.shape[2]

    def body(xl, shard, w, sd, cf, b):
        # GLOBAL element index of this shard's row 0: each shard owns
        # local_rows whole blocks, row-major over the flat shard order
        b_l = b + shard * np.uint32(xl.shape[1] * block)
        return _fqm.secure_commit_blocks(xl, w, sd, cf, b_l, bits=bits,
                                         k=k, interpret=_interpret())

    return _shard_rows_reduce(mesh, axes, body, xb, wv, seeds, coef, bv)


def _secure_body(mesh, axes, use_pallas, bits, k, xb, wv, seeds, coef, base,
                 noise_rng):
    """Shared secure-commit core over a blocked stack: kernel vs the
    bit-identical jnp oracle (stochastic rounding or use_pallas=False)."""
    if noise_rng is not None or not use_pallas:
        noise = (jax.random.uniform(noise_rng, xb.shape)
                 if noise_rng is not None else None)
        return _ref.fused_secure_commit_ref(xb, wv, seeds, coef, base, bits,
                                            k=k, noise=noise)
    return _secure_rows(mesh, axes, xb, wv, seeds, coef, base, bits, k)


# ------------------------------------------------- bucketed tree entry points

def fused_accum_tree(leaves, w, staleness, exponent, *, block: int = 256):
    """Bucketed fused accumulate over a flattened leaf list: ONE kernel
    launch for the whole tree.  Returns the per-leaf f32 sums."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_accum_tree_c(mesh, axes, block)(
        list(leaves), w, staleness, exponent)


@functools.lru_cache(maxsize=None)
def _fused_accum_tree_c(mesh, axes, block):
    def fused_accum_tree(leaves, w, s, a):
        xb, metas, rows = pack_blocks(leaves, block)
        wv, sv, av = _slot_vectors(w, s, a, xb.shape[0])
        return unpack_sums(_accum_rows(mesh, axes, xb, wv, sv, av),
                           metas, rows)
    return jax.jit(fused_accum_tree)


def fused_plain_commit_tree(leaves, w, staleness, exponent, *, bits: int,
                            k: int, block: int = 256):
    """Bucketed one-pass plain commit (top-k + quantize + discounted sum)
    over a flattened leaf list: ONE kernel launch for the whole tree."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_plain_tree_c(mesh, axes, bits, k, block)(
        list(leaves), w, staleness, exponent)


@functools.lru_cache(maxsize=None)
def _fused_plain_tree_c(mesh, axes, bits, k, block):
    def fused_plain_commit_tree(leaves, w, s, a):
        xb, metas, rows = pack_blocks(leaves, block)
        wv, sv, av = _slot_vectors(w, s, a, xb.shape[0])
        return unpack_sums(_plain_rows(mesh, axes, xb, wv, sv, av, bits, k),
                           metas, rows)
    return jax.jit(fused_plain_commit_tree)


def fused_secure_commit_tree(leaves, w_eff, seeds, coef, *, bits: int,
                             k: int = 0, block: int = 256,
                             use_pallas: bool = True, noise_rng=None):
    """Bucketed integer-domain secure commit over a flattened leaf list.
    The bucket's row-major element index equals the old per-leaf ``base``
    accumulation (base advanced by each leaf's padded blocked size), so
    the mask stream is bitwise-identical to per-leaf calls from base 0."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_secure_tree_c(mesh, axes, bits, k, block, use_pallas)(
        list(leaves), w_eff, seeds, coef, noise_rng)


@functools.lru_cache(maxsize=None)
def _fused_secure_tree_c(mesh, axes, bits, k, block, use_pallas):
    def fused_secure_commit_tree(leaves, w_eff, seeds, coef, noise_rng):
        xb, metas, rows = pack_blocks(leaves, block)
        wv = w_eff.astype(jnp.float32).reshape(xb.shape[0], 1)
        y = _secure_body(mesh, axes, use_pallas, bits, k, xb, wv, seeds,
                         coef, jnp.uint32(0), noise_rng)
        return unpack_sums(y, metas, rows)
    return jax.jit(fused_secure_commit_tree)


# ---------------------------------------------------- per-leaf entry points

def fused_accum(x, w, staleness, exponent, *, block: int = 256):
    """``sum_i w_i * (1+s_i)^(-exponent) * x_i`` over the slot dim of one
    leaf in a single pass (kernels/fused_accum); mesh-native."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_accum_c(mesh, axes, block)(x, w, staleness, exponent)


@functools.lru_cache(maxsize=None)
def _fused_accum_c(mesh, axes, block):
    def fused_accum(x, w, s, a):
        xb, meta = _stack_blocks(x, block)
        wv, sv, av = _slot_vectors(w, s, a, xb.shape[0])
        return _unstack_sum(_accum_rows(mesh, axes, xb, wv, sv, av), meta,
                            jnp.float32)
    return jax.jit(fused_accum)


def fused_plain_commit(x, w, staleness, exponent, *, bits: int, k: int,
                       block: int = 256):
    """Per-slot top-k + deterministic quantize + discounted weighted sum
    over the slot dim of one leaf, one pass (kernels/fused_quant_mask);
    mesh-native — every per-block quantity is row-local, so sharded ==
    unsharded bitwise."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_plain_c(mesh, axes, bits, k, block)(x, w, staleness,
                                                      exponent)


@functools.lru_cache(maxsize=None)
def _fused_plain_c(mesh, axes, bits, k, block):
    def fused_plain_commit(x, w, s, a):
        xb, meta = _stack_blocks(x, block)
        wv, sv, av = _slot_vectors(w, s, a, xb.shape[0])
        return _unstack_sum(_plain_rows(mesh, axes, xb, wv, sv, av, bits, k),
                            meta, jnp.float32)
    return jax.jit(fused_plain_commit)


def fused_secure_commit(x, w_eff, seeds, coef, base, *, bits: int, k: int = 0,
                        block: int = 256, use_pallas: bool = True,
                        noise_rng=None):
    """Integer-domain secure aggregation of one slot-stacked leaf: top-k,
    commit-common-scale integer quantize, uint32 modular pairwise masks,
    sum, dequantize.  ``base`` is the leaf's global element-index offset
    into the commit-wide mask stream.  ``use_pallas=False`` (or a
    ``noise_rng`` for stochastic rounding) routes to the bit-identical jnp
    oracle — the SCHEME is the same either way; only the executor
    differs."""
    _count_launch()
    mesh, axes = _mesh_axes()
    return _fused_secure_c(mesh, axes, bits, k, block, use_pallas)(
        x, w_eff, seeds, coef, jnp.asarray(base, jnp.uint32), noise_rng)


@functools.lru_cache(maxsize=None)
def _fused_secure_c(mesh, axes, bits, k, block, use_pallas):
    def fused_secure_commit(x, w_eff, seeds, coef, base, noise_rng):
        xb, meta = _stack_blocks(x, block)
        wv = w_eff.astype(jnp.float32).reshape(xb.shape[0], 1)
        y = _secure_body(mesh, axes, use_pallas, bits, k, xb, wv, seeds,
                         coef, base, noise_rng)
        return _unstack_sum(y, meta, jnp.float32)
    return jax.jit(fused_secure_commit)


# ---------------------------------------------------------------------------
# selective scan with custom VJP (forward = Pallas kernel; backward = the
# reverse-time linear recurrence via associative scan)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def selective_scan_chunk(a, b, h0):
    hs, hl = _ss.selective_scan_chunk_kernel(
        a.astype(jnp.float32), b.astype(jnp.float32),
        h0.astype(jnp.float32), _interpret())
    return hs, hl


def _ss_fwd(a, b, h0):
    hs, hl = selective_scan_chunk(a, b, h0)
    return (hs, hl), (a, hs, h0)


def _ss_bwd(res, cot):
    a, hs, h0 = res
    g_hs, g_hl = cot
    # total gradient at each t: G_t = g_hs_t + a_{t+1} G_{t+1}; G_L += g_hl
    g = g_hs.at[:, -1].add(g_hl)
    a_next = jnp.concatenate([a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)

    def combine(c1, c2):  # reverse-time scan
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    ar = jnp.flip(a_next, axis=1)
    gr = jnp.flip(g, axis=1)
    aa, bb = jax.lax.associative_scan(combine, (ar, gr), axis=1)
    G = jnp.flip(bb, axis=1)                       # [B,L,D,N]
    h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    ga = G * h_prev
    gb = G
    gh0 = a[:, 0] * G[:, 0]
    return ga, gb, gh0


selective_scan_chunk.defvjp(_ss_fwd, _ss_bwd)
