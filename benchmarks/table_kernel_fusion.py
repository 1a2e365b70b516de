"""Fused-commit roofline table (ISSUE 7 acceptance artifact).

Measures the one-pass Pallas commit path (core.pipeline, use_fused) against
the unfused stage stack per (leaf-size x quantize-bits x secure_agg) cell:

  * achieved parity          max |fused - unfused| on the committed delta
  * predicted bytes-touched  costmodel.commit_bytes_touched fused vs the
                             per-stage unfused stack (acceptance: <= 0.5x)
  * masked wire bytes        secure_agg.masked_payload_bytes vs the plain
                             quantized payload (acceptance: 8-bit masked
                             within 1.25x of plain — the integer-domain
                             masking collapse of the historical ~3.9x)

Two extra sections ride along (PR 10):

  * bucketing      launches per commit on a many-leaf tree: the bucketed
                   tree entry points (kernels/ops.fused_*_tree, what
                   core/pipeline dispatches) vs one kernel call per leaf
                   (acceptance: O(#buckets), i.e. independent of #leaves)
  * sharded        the same fused-vs-unfused parity under an active
                   2-device GSPMD mesh — UpdatePipeline.fused must stay
                   True and parity hold now that the kernels shard_map
                   themselves over the mesh

Run:  PYTHONPATH=src:. python benchmarks/table_kernel_fusion.py
"""
from __future__ import annotations

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # the sharded section needs >= 2 devices; must be set before the jax
    # backend initializes (harmless no-op if something already booted it —
    # the section then skips itself)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save
from benchmarks.costmodel import commit_bytes_touched
from repro.core.compression import CompressionConfig, payload_bytes
from repro.core.round import FLConfig
from repro.core.pipeline import build_update_pipeline
from repro.core.secure_agg import masked_payload_bytes
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.models import sharding as sh

K = 4                                   # commit slots (async buffer size)
LEAF_SIZES = [1 << 16, 1 << 20]
BITS = [4, 8]


def _launches(fn, *args):
    """Kernel launches in one fresh trace of fn (kernels/ops counts at
    call time, i.e. while the jit traces)."""
    kops.KERNEL_LAUNCHES = 0
    jax.block_until_ready(fn(*args))
    return kops.KERNEL_LAUNCHES


def _cell(n_elems, bits, secure, rng):
    comp = CompressionConfig(quantize_bits=bits, topk_frac=0.1,
                             stochastic_rounding=False)
    # magnitudes constructed distinct: an exact float32 tie at the k-th
    # top-k boundary is the one place sort-based (unfused) and threshold
    # -based (kernel) selection legitimately differ, and 2^20 normal draws
    # collide on the float32 grid often enough to hit it
    mags = np.linspace(1e-3, 1.0, n_elems, dtype=np.float64)
    signs = rng.choice([-1.0, 1.0], n_elems)
    tree = {"w": jnp.asarray((rng.permutation(mags) * signs * 0.01)
                             .astype(np.float32))}
    deltas = {"w": jnp.stack([tree["w"] * (i + 1) * 0.5 for i in range(K)])}
    weights = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
    mask = jnp.ones((K,), jnp.float32)
    staleness = jnp.asarray(rng.integers(0, 4, K).astype(np.float32))
    key = jax.random.PRNGKey(0)

    def build(use_fused):
        cfg = FLConfig(secure_agg=secure, compression=dataclasses.replace(
            comp, use_fused=use_fused))
        pipe = build_update_pipeline(cfg)

        @jax.jit
        def commit(d, w, m, s, r):
            summed, _, w_raw = pipe.combine_unnormalised(
                d, w, m, None, r, staleness=s, exponent=0.5)
            return pipe.normalise(summed, w_raw.sum())
        return commit

    fused, unfused = build(True), build(False)
    args = (deltas, weights, mask, staleness, key)
    launches = _launches(fused, *args)
    diff = float(jnp.max(jnp.abs(fused(*args)["w"] - unfused(*args)["w"])))

    pred_f = commit_bytes_touched(n_elems, K, quantize_bits=bits, topk=True,
                                  secure=secure, fused=True)
    pred_u = commit_bytes_touched(n_elems, K, quantize_bits=bits, topk=True,
                                  secure=secure)
    # wire baseline is the DENSE quantized payload: masking ships dense
    # finite-ring words, so sparsity never survives the masked wire and the
    # honest comparison is masked ring words vs plain quantized words
    quant_only = dataclasses.replace(comp, topk_frac=0.0)
    plain_wire = payload_bytes(tree, quant_only)
    masked_wire = masked_payload_bytes(tree, quant_only, n_slots=K)
    return {
        "n_elems": n_elems, "bits": bits, "secure": secure,
        "launches_fused": launches,
        "fused_vs_unfused_max_abs": diff,
        "pred_bytes_fused": pred_f, "pred_bytes_unfused": pred_u,
        "pred_bytes_fused_x": pred_f / pred_u,
        "plain_quant_wire_bytes": plain_wire,
        "masked_wire_bytes": masked_wire,
        "masked_wire_x": masked_wire / plain_wire,
    }


def _bucketing_row(rng, n_leaves=32):
    """Launches per commit on a many-leaf tree: the bucketed pipeline path
    vs one kernel call per leaf (the pre-bucketing dispatch pattern)."""
    leaves = [jnp.asarray(rng.normal(size=(K, 1 << (8 + i % 6)))
                          .astype(np.float32) * 0.01)
              for i in range(n_leaves)]
    w = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 4, K).astype(np.float32))
    bucketed = jax.jit(lambda ls: kops.fused_plain_commit_tree(
        ls, w, s, 0.5, bits=8, k=26))
    per_leaf = jax.jit(lambda ls: [kops.fused_plain_commit(
        l, w, s, 0.5, bits=8, k=26) for l in ls])
    l_b, l_p = _launches(bucketed, leaves), _launches(per_leaf, leaves)
    parity = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(bucketed(leaves), per_leaf(leaves)))
    row = {"n_leaves": n_leaves, "launches_bucketed": l_b,
           "launches_per_leaf": l_p,
           "bucketed_vs_per_leaf_max_abs": parity}
    print(f"bucketing: {n_leaves} leaves -> {l_b} launch(es) bucketed vs "
          f"{l_p} per-leaf, parity={parity:.2e}")
    return row


def _sharded_rows(rng):
    """Fused-vs-unfused commit parity with an ACTIVE 2-device mesh: the
    gate-lift acceptance — UpdatePipeline.fused stays True and the
    shard_mapped kernels match the unfused GSPMD lowering."""
    if len(jax.devices()) < 2:
        print("sharded: skipped (single device; jax initialized before "
              "the device-count flag could apply)")
        return []
    mesh = make_mesh((2,), ("data",))
    out = []
    for secure in (False, True):
        comp = CompressionConfig(quantize_bits=8, topk_frac=0.1,
                                 stochastic_rounding=False)
        deltas = {"w": jnp.asarray(
            rng.normal(size=(K, 1 << 16)).astype(np.float32) * 0.01)}
        weights = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
        mask = jnp.ones((K,), jnp.float32)
        key = jax.random.PRNGKey(0)
        with sh.use_mesh(mesh):
            def build(use_fused):
                cfg = FLConfig(secure_agg=secure,
                               compression=dataclasses.replace(
                                   comp, use_fused=use_fused))
                pipe = build_update_pipeline(cfg)

                @jax.jit
                def commit(d, w, m, r):
                    summed, _, w_raw = pipe.combine_unnormalised(
                        d, w, m, None, r)
                    return pipe.normalise(summed, w_raw.sum())
                return pipe, commit

            pipe_f, fused = build(True)
            _, unfused = build(False)
            assert pipe_f.fused, "gate-lift regression: fused off under mesh"
            args = (deltas, weights, mask, key)
            launches = _launches(fused, *args)
            diff = float(jnp.max(jnp.abs(fused(*args)["w"]
                                         - unfused(*args)["w"])))
        out.append({"devices": 2, "mesh_axes": ["data"], "secure": secure,
                    "fused_stays_on": True, "launches_fused": launches,
                    "sharded_parity_max_abs": diff})
        print(f"sharded: secure={int(secure)} parity={diff:.2e} "
              f"launches={launches} (2-device mesh, fused stayed on)")
    return out


def main():
    rng = np.random.default_rng(0)
    rows = []
    for n in LEAF_SIZES:
        for bits in BITS:
            for secure in (False, True):
                r = _cell(n, bits, secure, rng)
                rows.append(r)
                print(f"n={n:>8d} bits={bits} secure={int(secure)} "
                      f"parity={r['fused_vs_unfused_max_abs']:.2e} "
                      f"bytes-fused={r['pred_bytes_fused_x']:.3f}x "
                      f"wire-masked={r['masked_wire_x']:.3f}x "
                      f"launches={r['launches_fused']}")
    bucketing = _bucketing_row(rng)
    sharded = _sharded_rows(rng)
    headline = {
        "masked_wire_x_8bit": max(r["masked_wire_x"] for r in rows
                                  if r["bits"] == 8 and r["secure"]),
        "pred_bytes_fused_x_max": max(r["pred_bytes_fused_x"] for r in rows),
        "parity_max_abs": max(r["fused_vs_unfused_max_abs"] for r in rows),
        "launches_bucketed": bucketing["launches_bucketed"],
        "launches_per_leaf": bucketing["launches_per_leaf"],
        "sharded_parity_max_abs": max(
            (r["sharded_parity_max_abs"] for r in sharded), default=None),
    }
    print("headline:", headline)
    save("table_kernel_fusion", {
        "rows": rows, "bucketing": bucketing, "sharded": sharded,
        "headline": headline, "n_slots": K,
        "note": ("bytes columns are the analytic roofline "
                 "(costmodel.commit_bytes_touched) and wire accounting "
                 "(secure_agg.masked_payload_bytes); no device time is "
                 "measured here")})
    return rows


if __name__ == "__main__":
    main()
