"""CI fast-lane smoke: fused commit under an active 2-device CPU mesh.

Run directly (NOT a pytest file — the XLA device count must be forced
before jax initialises, so this runs as its own process):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python tests/mesh_smoke.py

Asserts the PR 10 gate-lift acceptance on the cheapest possible case:
with an active ("data",) mesh, ``UpdatePipeline.fused`` stays True and the
fused (shard_mapped Pallas) commit matches the unfused stage stack <= 1e-5
for one sync sequential round AND one async buffered secure commit.  The
exhaustive version (four regimes, 1x2 + 2x2 meshes, real archs) lives in
tests/test_mesh_small.py on the slow lane.
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from repro.configs import get_config                          # noqa: E402
from repro.core import (AsyncConfig, CompressionConfig,       # noqa: E402
                        FLConfig, build_buffer_commit_step,
                        build_client_update_step, build_fl_round_step,
                        build_update_pipeline)
from repro.launch.mesh import make_mesh                       # noqa: E402
from repro.models import build_model, sharding as sh          # noqa: E402
from repro.optim import (get_client_optimizer,                # noqa: E402
                         get_server_optimizer)

C, H, b, S = 4, 1, 2, 16
DET = dict(quantize_bits=8, topk_frac=0.1, stochastic_rounding=False)


def tree_diff(t1, t2):
    return max(float(jnp.abs(a - b2).max())
               for a, b2 in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))


def main():
    assert len(jax.devices()) >= 2, (
        "needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
    cfg = get_config("paper-charlm").replace(n_layers=1, d_model=64,
                                             d_ff=128, n_heads=2, kv_heads=2)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (C, H, b, S + 1), 0,
                              cfg.vocab, jnp.int32)
    batches = {"tokens": toks[..., :-1], "targets": toks[..., 1:]}
    copt, sopt = get_client_optimizer("sgd"), get_server_optimizer("fedavg")
    mesh = make_mesh((2,), ("data",))

    with sh.use_mesh(mesh), mesh:
        assert build_update_pipeline(FLConfig()).fused, (
            "gate-lift regression: fused off under an active mesh")

        # sync sequential round, fused vs unfused
        sync = {}
        for use_fused in (True, False):
            fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                          client_exec="sequential",
                          compression=CompressionConfig(use_fused=use_fused,
                                                        **DET))
            step = jax.jit(build_fl_round_step(m.loss_fn, copt, sopt, fl))
            sync[use_fused] = step(params, (), batches, jnp.ones((C,)),
                                   jnp.ones((C,)), jax.random.PRNGKey(2))[0]
        d_sync = tree_diff(sync[True], sync[False])
        assert d_sync <= 1e-5, f"sync fused/unfused diverged: {d_sync}"

        # async buffered secure commit, fused vs unfused
        rng = jax.random.PRNGKey(4)
        acfg = AsyncConfig(buffer_size=C)
        asy = {}
        for use_fused in (True, False):
            fl = FLConfig(mode="async", num_clients=C, local_steps=H,
                          client_lr=0.1, secure_agg=True,
                          compression=CompressionConfig(use_fused=use_fused,
                                                        **DET))
            client_step = jax.jit(build_client_update_step(m.loss_fn, copt,
                                                           fl))
            rngs = jax.random.split(rng, C)
            deltas = [client_step(params,
                                  jax.tree.map(lambda x: x[c], batches),
                                  rngs[c])[0] for c in range(C)]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
            commit = jax.jit(build_buffer_commit_step(sopt, fl, acfg))
            asy[use_fused] = commit(
                params, (), stacked, jnp.ones((C,)),
                jnp.asarray([0.0, 1.0, 3.0, 2.0]), jnp.zeros(C),
                jnp.ones((C,)), jnp.arange(C, dtype=jnp.int32),
                jnp.float32(0.5), rng)[0]
        d_async = tree_diff(asy[True], asy[False])
        assert d_async <= 1e-5, f"async fused/unfused diverged: {d_async}"

    print(f"mesh smoke OK: devices={len(jax.devices())} "
          f"sync_diff={d_sync:.2e} async_diff={d_async:.2e} (fused stayed on)")


if __name__ == "__main__":
    main()
