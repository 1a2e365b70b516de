"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU's compiler
refuses: unsupported vector ops, misaligned tiles, VMEM limits.  These tests
compile each kernel with ``interpret=False`` for a described ``v5e:2x2``
topology (no chip attached) at the shape of a real commit: the leaf-bucketed
``xlstm-125m`` delta, K=4 slots, 256-wide blocks.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_accum as fa
from repro.kernels import fused_quant_mask as fqm
from repro.kernels import ops as kops
from repro.kernels import quantize as q
from repro.kernels import topk_sparsify as tk

K, BLOCK, BITS, TOPK = 4, 256, 8, 26
KERNEL_NAMES = {"fused_accum_blocks": "fl_accum",
                "plain_commit_blocks": "fl_plain_commit",
                "secure_commit_blocks": "fl_secure_commit",
                "quantize_dequant_blocks": "fl_quantize",
                "topk_sparsify_blocks": "fl_topk"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bucket_rows():
    """Rows of the one [K, rows, BLOCK] bucket an xlstm-125m commit packs
    its slot-stacked delta into (kernels/ops.pack_blocks)."""
    from repro.configs import get_config
    from repro.models import build_model
    params = jax.eval_shape(build_model(get_config("xlstm-125m")).init,
                            jax.random.PRNGKey(0))
    stacked = [jax.ShapeDtypeStruct((K,) + p.shape, jnp.float32)
               for p in jax.tree.leaves(params)]
    return jax.eval_shape(lambda ls: kops.pack_blocks(ls, BLOCK)[0],
                          stacked).shape[1]


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_case(name, R):
    """(kernel with interpret off, its argument shapes and dtypes)."""
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    stack, vec, one = ((K, R, BLOCK), f32), ((K, 1), f32), ((1, 1), f32)
    return {
        "fused_accum_blocks": (
            lambda x, w, s, a: fa.fused_accum_blocks(x, w, s, a, False),
            [stack, vec, vec, one]),
        "plain_commit_blocks": (
            lambda x, w, s, a: fqm.plain_commit_blocks(
                x, w, s, a, bits=BITS, k=TOPK, interpret=False),
            [stack, vec, vec, one]),
        "secure_commit_blocks": (
            lambda x, w, sd, cf, b: fqm.secure_commit_blocks(
                x, w, sd, cf, b, bits=BITS, k=TOPK, interpret=False),
            [stack, vec, ((K, K), u32), ((K, K), i32), ((1, 1), u32)]),
        "quantize_dequant_blocks": (
            lambda x: q.quantize_dequant_blocks(x, BITS, False),
            [((R, BLOCK), f32)]),
        "topk_sparsify_blocks": (
            lambda x: tk.topk_sparsify_blocks(x, TOPK, False),
            [((R, BLOCK), f32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "fused_accum_blocks", "plain_commit_blocks", "secure_commit_blocks",
    "quantize_dequant_blocks", "topk_sparsify_blocks"])
def test_kernel_compiles_for_v5e(name, one_chip, bucket_rows,
                                 no_persistent_cache):
    fn, shapes = _kernel_case(name, bucket_rows)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's instruction carries its stable name, which a trace's
    # operation names show
    kernel = KERNEL_NAMES[name]
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call", text), kernel


def test_padded_bucket_is_packing(one_chip, no_persistent_cache):
    """XLA merges the bucket's concatenate (``kops.pack_blocks``) with the
    kernel wrapper's zero rows that fill its last tile, and the merged
    operations carry one op_name: both sit under ``fl.commit.pack``, so a
    trace counts the whole assembly of the bucket as packing."""
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32

    def commit(leaves, w, seeds, coef, base):
        xb = kops.pack_blocks(leaves, BLOCK)[0]
        return fqm.secure_commit_blocks(xb, w, seeds, coef, base, bits=BITS,
                                        k=TOPK, interpret=False)

    # 10 + 1 rows of 256: the wrapper adds 5 zero rows to fill a tile of 8
    shapes = [[((K, 5, 300), f32), ((K, 100), f32)], ((K, 1), f32),
              ((K, K), u32), ((K, K), i32), ((1, 1), u32)]
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    text = jax.jit(commit).lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*concatenate)"', text)
    assert names and all("/fl.commit.pack/" in n for n in names), names
