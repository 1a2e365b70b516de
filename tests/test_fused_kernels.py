"""Always-on parity suite for the fused commit path (ISSUE 7).

Three layers of pinning:

  * kernel vs jnp oracle (kernels/ref.py) on odd, padding-exercising
    shapes, bits {4, 8} — the Pallas kernels compute the same numbers.
  * the integer-domain SecAgg algebra: uint32 modular pairwise masks
    cancel EXACTLY in the summed wire words (bitwise, not allclose), with
    non-participating slots unwound.
  * fused vs unfused ``use_fused`` across all four execution regimes
    (sync parallel / sequential / pod_sequential via build_fl_round_step,
    async buffered commit via build_buffer_commit_step): <= 1e-5 on the
    committed params — the acceptance criterion of the ISSUE.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (AsyncConfig, CompressionConfig, FLConfig,
                        build_buffer_commit_step, build_client_update_step,
                        build_fl_round_step, build_update_pipeline)
from repro.core import secure_agg as sec
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import build_model
from repro.optim import get_client_optimizer, get_server_optimizer

K = 4
ODD_SHAPES = [(17,), (2, 5, 9), (3, 300), (1,), (2049,)]


def _slots(shape, seed=0, scale=0.01):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(K,) + shape).astype(np.float32) * scale)
    w = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 5, K).astype(np.float32))
    return x, w, s


def _close(t1, t2, tol=1e-5):
    for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


def _block(x, block=256):
    """The ops._stack_blocks layout: last dim padded/blocked per slot row,
    leading dims collapsed -> [K, R, block]."""
    shp = x.shape[1:] or (1,)
    xx = x.reshape((x.shape[0], -1, shp[-1])).astype(jnp.float32)
    pad = (-shp[-1]) % block
    if pad:
        xx = jnp.pad(xx, ((0, 0), (0, 0), (0, pad)))
    return xx.reshape(x.shape[0], -1, block), pad, shp


def _unblock(y, pad, shp):
    return np.asarray(y).reshape(-1, shp[-1] + pad)[:, :shp[-1]].reshape(shp)


# ------------------------------------------------------ kernels vs oracles
@pytest.mark.parametrize("shape", ODD_SHAPES)
def test_fused_accum_matches_oracle(shape):
    x, w, s = _slots(shape)
    got = kops.fused_accum(x, w, s, 0.5)
    xb, pad, shp = _block(x)
    want = _unblock(kref.fused_accum_ref(xb, w[:, None], s[:, None], 0.5),
                    pad, shp)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    assert got.shape == shape


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", [(515,), (3, 130)])
def test_fused_plain_commit_matches_oracle(bits, shape):
    x, w, s = _slots(shape, seed=bits)
    comp = CompressionConfig(quantize_bits=bits, topk_frac=0.1)
    got = kops.fused_plain_commit(x, w, s, 0.5, bits=bits, k=comp.topk_k)
    xb, pad, shp = _block(x, comp.block)
    want = _unblock(kref.fused_plain_commit_ref(
        xb, w[:, None], s[:, None], 0.5, bits, k=comp.topk_k), pad, shp)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [4, 8])
def test_fused_secure_commit_matches_oracle(bits):
    shape = (3, 300)
    x, w, _ = _slots(shape, seed=bits + 10)
    ids = jnp.arange(1, K + 1, dtype=jnp.uint32)
    part = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
    seeds = sec.pair_seeds(jax.random.PRNGKey(3), ids)
    coef = sec.pair_coef_int(ids, part)
    got = kops.fused_secure_commit(x, w, seeds, coef, 7, bits=bits)
    xb, pad, shp = _block(x)
    want = _unblock(kref.fused_secure_commit_ref(
        xb, w[:, None], seeds, coef, 7, bits), pad, shp)
    # ulp-level only: the hand-called eager ref and the jitted wrapper may
    # reassociate the scale division differently; exactness is asserted on
    # same-executor properties (mask cancellation, executor swap below)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-8)
    swap = kops.fused_secure_commit(x, w, seeds, coef, 7, bits=bits,
                                    use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(swap),
                               rtol=1e-7, atol=1e-9)


def test_integer_masks_cancel_exactly():
    """uint32 modular masks cancel bitwise in the sum: the masked commit
    equals the coef-zeroed (unmasked) commit EXACTLY, including with a
    non-participating slot whose pair masks are unwound."""
    x, w, _ = _slots((4, 257), seed=5)
    ids = jnp.arange(1, K + 1, dtype=jnp.uint32)
    part = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
    w = w * part                     # padded slot contributes nothing
    seeds = sec.pair_seeds(jax.random.PRNGKey(9), ids)
    coef = sec.pair_coef_int(ids, part)
    masked = kops.fused_secure_commit(x, w, seeds, coef, 0, bits=8)
    unmasked = kops.fused_secure_commit(x, w, seeds,
                                        jnp.zeros_like(coef), 0, bits=8)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(unmasked))


# ------------------------------------------------- leaf bucketing (PR 10)
def test_bucketed_tree_matches_per_leaf_bitwise():
    """The bucketed tree entry points (what core/pipeline dispatches) must
    equal per-leaf kernel calls BITWISE: rows are whole blocks of one leaf
    each, so block membership, per-block scales, top-k thresholds and the
    secure mask stream (bucket row-major index == per-leaf ``base``
    accumulation) are all unchanged — only the launch count collapses."""
    rng = np.random.default_rng(11)
    shapes = [(7,), (33, 9), (256,), (2, 5, 3), (515,)]
    leaves = [jnp.asarray(rng.normal(size=(K,) + s).astype(np.float32) * 0.01)
              for s in shapes]
    w = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 5, K).astype(np.float32))

    for got, want in zip(kops.fused_accum_tree(leaves, w, s, 0.5),
                         [kops.fused_accum(l, w, s, 0.5) for l in leaves]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    for got, want in zip(
            kops.fused_plain_commit_tree(leaves, w, s, 0.5, bits=8, k=26),
            [kops.fused_plain_commit(l, w, s, 0.5, bits=8, k=26)
             for l in leaves]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    ids = jnp.arange(1, K + 1, dtype=jnp.uint32)
    seeds = sec.pair_seeds(jax.random.PRNGKey(3), ids)
    coef = sec.pair_coef_int(ids, jnp.ones((K,), jnp.float32))
    got_tree = kops.fused_secure_commit_tree(leaves, w, seeds, coef, bits=8)
    base = 0
    for got, leaf in zip(got_tree, leaves):
        want = kops.fused_secure_commit(leaf, w, seeds, coef, base, bits=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        base += _block(leaf)[0].shape[1] * 256   # padded elements of leaf


def test_bucketed_tree_single_launch():
    rng = np.random.default_rng(12)
    leaves = [jnp.asarray(rng.normal(size=(K, 100 + 7 * i))
                          .astype(np.float32)) for i in range(8)]
    w = jnp.ones((K,), jnp.float32)
    s = jnp.zeros((K,), jnp.float32)
    kops.KERNEL_LAUNCHES = 0
    kops.fused_plain_commit_tree(leaves, w, s, 0.5, bits=8, k=26)
    assert kops.KERNEL_LAUNCHES == 1
    kops.KERNEL_LAUNCHES = 0
    [kops.fused_plain_commit(l, w, s, 0.5, bits=8, k=26) for l in leaves]
    assert kops.KERNEL_LAUNCHES == len(leaves)


# ------------------------------------- sharded == unsharded, bitwise (PR 10)
_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FLConfig, build_update_pipeline
from repro.core import secure_agg as sec
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.models import sharding as sh

K = 4
rng = np.random.default_rng(7)
# 2049 elements -> 9 blocks of 256: odd row count forces the shard_map
# wrappers through their pad-to-shard-multiple path
x = jnp.asarray(rng.normal(size=(K, 2049)).astype(np.float32) * 0.01)
w = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
s = jnp.asarray(rng.integers(0, 5, K).astype(np.float32))
ids = jnp.arange(1, K + 1, dtype=jnp.uint32)
part = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
seeds = sec.pair_seeds(jax.random.PRNGKey(3), ids)
coef = sec.pair_coef_int(ids, part)
leaves = [x, jnp.asarray(rng.normal(size=(K, 3, 130)).astype(np.float32))]

ref = {
    "quant": kops.quantize_dequant(x[0], bits=8),
    "topk": kops.topk_sparsify(x[0], k=26),
    "accum": kops.fused_accum(x, w, s, 0.5),
    "plain": kops.fused_plain_commit(x, w, s, 0.5, bits=8, k=26),
    "secure": kops.fused_secure_commit(x, w, seeds, coef, 7, bits=8),
    "tree": kops.fused_secure_commit_tree(leaves, w, seeds, coef, bits=8),
}

mesh = make_mesh((2,), ("data",))
out = {}
with sh.use_mesh(mesh):
    assert build_update_pipeline(FLConfig()).fused, "gate-lift regression"
    got = {
        "quant": kops.quantize_dequant(x[0], bits=8),
        "topk": kops.topk_sparsify(x[0], k=26),
        "accum": kops.fused_accum(x, w, s, 0.5),
        "plain": kops.fused_plain_commit(x, w, s, 0.5, bits=8, k=26),
        "secure": kops.fused_secure_commit(x, w, seeds, coef, 7, bits=8),
        "tree": kops.fused_secure_commit_tree(leaves, w, seeds, coef,
                                              bits=8),
    }
    for name in ref:
        out[name] = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(ref[name]), jax.tree.leaves(got[name])))
    # mask cancellation stays BITWISE with sharded PRF seeds: each shard
    # derives the mask stream from the GLOBAL element index (base + flat
    # shard offset), so masked == coef-zeroed exactly under the mesh
    masked = kops.fused_secure_commit(x, w * part, seeds, coef, 0, bits=8)
    unmasked = kops.fused_secure_commit(x, w * part, seeds,
                                        jnp.zeros_like(coef), 0, bits=8)
    out["mask_cancel"] = float(jnp.abs(masked - unmasked).max())
print(json.dumps(out))
"""


def test_sharded_matches_unsharded_bitwise():
    """Every fused entry point under an ACTIVE 2-device mesh must equal its
    no-mesh result BITWISE (row-sharding preserves block membership and all
    per-block quantities), and the integer mask stream must still cancel
    exactly with position-independent per-shard PRF bases."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {name: 0.0 for name in res}, res


# --------------------------------------- fused vs unfused, all four regimes
C, H, b, S = 4, 2, 2, 16
DET_COMP = dict(quantize_bits=8, topk_frac=0.1, stochastic_rounding=False)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-charlm").replace(n_layers=2, d_model=64, d_ff=128,
                                             n_heads=2, kv_heads=2)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (C, H, b, S + 1), 0,
                              cfg.vocab, jnp.int32)
    batches = {"tokens": toks[..., :-1], "targets": toks[..., 1:]}
    return m, params, batches


@pytest.mark.parametrize("exec_mode,secure", [
    ("parallel", False), ("parallel", True),
    ("sequential", False), ("sequential", True),
])
def test_sync_fused_matches_unfused(setup, exec_mode, secure):
    m, params, batches = setup
    outs = {}
    for use_fused in (True, False):
        comp = CompressionConfig(use_fused=use_fused, **DET_COMP)
        fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                      client_exec=exec_mode, secure_agg=secure,
                      compression=comp)
        step = jax.jit(build_fl_round_step(
            m.loss_fn, get_client_optimizer("sgd"),
            get_server_optimizer("fedavg"), fl))
        outs[use_fused] = step(params, (), batches,
                               jnp.asarray([1.0, 2.0, 3.0, 4.0]),
                               jnp.asarray([1.0, 0.0, 1.0, 1.0]),
                               jax.random.PRNGKey(2))
    _close(outs[True][0], outs[False][0])


def test_pod_sequential_fused_matches_unfused(setup):
    m, params, batches = setup
    outs = {}
    for use_fused in (True, False):
        comp = CompressionConfig(use_fused=use_fused, **DET_COMP)
        fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                      client_exec="pod_sequential", compression=comp)
        step = jax.jit(build_fl_round_step(
            m.loss_fn, get_client_optimizer("sgd"),
            get_server_optimizer("fedavg"), fl, n_pods=2))
        outs[use_fused] = step(params, (), batches,
                               jnp.asarray([1.0, 2.0, 3.0, 4.0]),
                               jnp.ones((C,)), jax.random.PRNGKey(2))
    _close(outs[True][0], outs[False][0])


@pytest.mark.parametrize("secure", [False, True])
def test_async_commit_fused_matches_unfused(setup, secure):
    m, params, batches = setup
    copt, sopt = get_client_optimizer("sgd"), get_server_optimizer("fedavg")
    rng = jax.random.PRNGKey(4)
    outs = {}
    for use_fused in (True, False):
        comp = CompressionConfig(use_fused=use_fused, **DET_COMP)
        fl = FLConfig(mode="async", num_clients=C, local_steps=H,
                      client_lr=0.1, secure_agg=secure, compression=comp)
        client_step = jax.jit(build_client_update_step(m.loss_fn, copt, fl))
        rngs = jax.random.split(rng, C)
        deltas = [client_step(params,
                              jax.tree.map(lambda x: x[c], batches),
                              rngs[c])[0] for c in range(C)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        commit = jax.jit(build_buffer_commit_step(
            sopt, fl, AsyncConfig(buffer_size=C)))
        outs[use_fused] = commit(
            params, (), stacked, jnp.asarray([1.0, 2.0, 3.0, 4.0]),
            jnp.asarray([0.0, 1.0, 3.0, 2.0]), jnp.zeros(C),
            jnp.asarray([1.0, 1.0, 0.0, 1.0]),
            jnp.arange(C, dtype=jnp.int32), jnp.float32(0.5), rng)
    _close(outs[True][0], outs[False][0])


def test_fused_masked_equals_plain_uncompressed(setup):
    """The pre-existing acceptance property survives fusion: with
    compression off and use_fused on (the default), a masked round equals
    the plain round to 1e-5 (float-domain masks vs fused accumulate)."""
    m, params, batches = setup
    outs = {}
    for secure in (False, True):
        fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                      secure_agg=secure)
        assert fl.compression.use_fused            # default on
        step = jax.jit(build_fl_round_step(
            m.loss_fn, get_client_optimizer("sgd"),
            get_server_optimizer("fedavg"), fl))
        outs[secure] = step(params, (), batches,
                            jnp.asarray([1.0, 2.0, 3.0, 4.0]),
                            jnp.asarray([1.0, 0.0, 1.0, 1.0]),
                            jax.random.PRNGKey(2))
    _close(outs[False][0], outs[True][0])


# ----------------------------------------------------------------- gating
def test_fusion_gates_off():
    cfg = FLConfig(compression=CompressionConfig(use_fused=False))
    assert build_update_pipeline(cfg).fused is False
    cfg = FLConfig()
    assert build_update_pipeline(cfg, allow_fused=False).fused is False
    assert build_update_pipeline(cfg).fused is True


def test_stochastic_rounding_uses_oracle_not_kernel(setup):
    """Stochastic quantize needs per-element randomness: the secure commit
    must route through the jnp oracle (noise path) and still cancel masks
    — masked equals coef-zeroed exactly."""
    x, w, _ = _slots((300,), seed=8)
    ids = jnp.arange(1, K + 1, dtype=jnp.uint32)
    seeds = sec.pair_seeds(jax.random.PRNGKey(2), ids)
    coef = sec.pair_coef_int(ids, jnp.ones((K,), jnp.float32))
    nr = jax.random.PRNGKey(6)
    masked = kops.fused_secure_commit(x, w, seeds, coef, 0, bits=8,
                                      noise_rng=nr)
    unmasked = kops.fused_secure_commit(x, w, seeds, jnp.zeros_like(coef),
                                        0, bits=8, noise_rng=nr)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(unmasked))
