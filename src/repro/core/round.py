"""The jit'd federated round step — the paper's Algorithm 1 lines 5-12.

``build_fl_round_step`` closes over the model loss, client/server optimizers,
aggregation strategy, and compression config, and returns one pure function:

    round_step(global_params, server_state, client_batches, weights, mask, rng)
        -> (new_params, new_server_state, metrics)

client_batches leaves are [C, H, ...] (C clients, H local steps).  ``mask``
[C] (0/1) implements deadline cutoff / fastest-k / dropouts decided host-side
by the orchestrator, so one compiled step serves every round.

Client execution modes (DESIGN.md §2):
  * parallel   — vmap over clients; client dim sharded over the batch mesh
                 axes (pod x data).  Aggregation lowers to the cross-client
                 psum — the client->server "transfer".  Hierarchical
                 compression: pod-local mean, compress, cross-pod mean.
  * sequential — lax.scan over clients; each client's local batch uses the
                 full mesh.  Required when C parallel model replicas cannot
                 fit HBM (>=100B-param archs).

All modes fold their client updates through the SAME composable stage stack
(compress -> weight -> secure_mask -> aggregate -> normalise) built once by
``repro.core.pipeline.build_update_pipeline`` — the async buffered commit
(core.async_round) closes over the identical stack.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.compression import CompressionConfig
from repro.core.pipeline import build_update_pipeline
from repro.models import sharding as shd
from repro.optim import Optimizer, ServerOptimizer


def _axes_tuple(ax):
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


@dataclass(frozen=True)
class FLConfig:
    mode: str = "sync"                # sync (barrier rounds) | async (FedBuff
    #                                   buffered commits; see core.async_round)
    num_clients: int = 8              # clients per round (C)
    local_steps: int = 2              # H local epochs/steps per round
    client_lr: float = 0.05
    fedprox_mu: float = 0.0           # 0 -> FedAvg; >0 -> FedProx proximal term
    aggregation: str = "fedavg"       # fedavg | weighted | trimmed_mean
    client_exec: str = "parallel"     # parallel | sequential | pod_sequential
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    hierarchical: bool = False        # pod-local then compressed cross-pod agg
    accum_dtype: str = "float32"      # sequential-mode delta accumulator
    use_fused_update: bool = False    # Pallas fedprox_update kernel
    secure_agg: bool = False          # commit-keyed pairwise masking: the
    #                                   server only sees masked updates whose
    #                                   masks cancel per commit (core.pipeline)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_add_scaled(a, b, s):
    return jax.tree.map(lambda x, y: x + s * y.astype(x.dtype), a, b)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def constrain_like(tree, shardings):
    """Pin a pytree (grads / deltas / accumulators) to the parameter
    shardings.  Without this GSPMD materialises weight grads REPLICATED
    (full f32 all-reduce per layer, measured 5.2 GB/layer bwd for
    mistral-large) instead of reduce-scattering to the FSDP layout —
    EXPERIMENTS.md §Perf iteration 2."""
    if shardings is None:
        return tree

    def apply(x, s):
        if s is None:
            return x
        ndim = len(s.spec) if hasattr(s, "spec") else None
        if ndim is not None and x.ndim != ndim:
            # under vmap (parallel/pod_sequential) the tracer carries a
            # mapped leading dim; constraining it to the unmapped spec would
            # force replication across the mapped mesh axis (measured: +H x
            # cross-pod grad traffic).  Skip — the batched case relies on
            # propagation instead.
            return x
        return jax.lax.with_sharding_constraint(x, s)

    return jax.tree.map(apply, tree, shardings)


def server_step(server_opt: ServerOptimizer, params, delta, state):
    """The server optimizer's step, under the ``fl.server_step`` scope."""
    with jax.named_scope("fl.server_step"):
        return server_opt.apply(params, delta, state)


def build_local_train(loss_fn: Callable, client_opt: Optimizer, cfg: FLConfig,
                      param_shardings=None):
    """Returns local_train(global_params, batches_H, rng) -> (delta, mean_loss).

    FedProx (mu>0): the proximal term mu/2 ||w - w0||^2 enters as the exact
    gradient correction mu (w - w0) — cheaper than autodiff through the norm
    and fusable into the Pallas fedprox_update kernel."""

    def local_train(global_params, batches, rng):
        with jax.named_scope("fl.local_train"):
            return _local_train(global_params, batches, rng)

    def _local_train(global_params, batches, rng):
        opt0 = client_opt.init(global_params)

        def step(carry, xs):
            w, opt_state, loss_sum = carry
            batch, r = xs
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(w, batch)
            grads = constrain_like(grads, param_shardings)
            if cfg.use_fused_update and client_opt.name == "sgd":
                from repro.kernels import ops as kops
                w = jax.tree.map(
                    lambda wi, gi, w0i: kops.fedprox_update(
                        wi, gi, w0i, lr=cfg.client_lr, mu=cfg.fedprox_mu),
                    w, grads, global_params)
            else:
                if cfg.fedprox_mu:
                    grads = jax.tree.map(
                        lambda gi, wi, w0i: gi + cfg.fedprox_mu *
                        (wi - w0i).astype(gi.dtype),
                        grads, w, global_params)
                w, opt_state = client_opt.update(grads, opt_state, w, cfg.client_lr)
            return (w, opt_state, loss_sum + loss), None

        rngs = jax.random.split(rng, cfg.local_steps)
        (w, _, loss_sum), _ = jax.lax.scan(
            step, (global_params, opt0, jnp.float32(0.0)), (batches, rngs))
        delta = constrain_like(tree_sub(w, global_params), param_shardings)
        return delta, loss_sum / cfg.local_steps

    return local_train


def build_fl_round_step(loss_fn: Callable, client_opt: Optimizer,
                        server_opt: ServerOptimizer, cfg: FLConfig,
                        n_pods: int = 1, param_shardings=None,
                        client_spmd_axes=None):
    """client_spmd_axes: mesh axis name(s) the vmapped client (or pod) dim is
    sharded over.  Without it GSPMD replicates every per-client/per-pod
    intermediate (weights included!) across the mapped axis — measured as
    ~600 MB cross-pod all-gathers of the per-pod weight copies per layer per
    step (EXPERIMENTS.md §Perf iteration 4)."""
    if (cfg.client_exec == "parallel" and client_spmd_axes is None
            and shd.get_mesh() is not None):
        # Not just a perf footgun: vmapping clients WITHOUT spmd_axis_name
        # while the params carry full shardings makes GSPMD mis-partition
        # the scan transpose — the PRIMAL loss comes out wrong (~5e-2 on
        # the 2x2x2 mesh test before this guard; minimal trigger is a
        # down-projection whose output dim is sharded over a batch axis).
        raise ValueError(
            "client_exec='parallel' under an active mesh requires "
            "client_spmd_axes (the mesh axes the vmapped client dim is "
            "sharded over, e.g. ('pod', 'data')); vmap without "
            "spmd_axis_name over sharded params is numerically unsupported")
    local_train = build_local_train(loss_fn, client_opt, cfg, param_shardings)
    # explicit shardings no longer force the unfused stages: the fused
    # kernel entry points shard_map themselves over the active mesh
    # (kernels/ops.py), so cfg.compression.use_fused alone decides
    pipe = build_update_pipeline(cfg, n_pods=n_pods)
    C = cfg.num_clients

    # All three modes consume the SAME stage stack (core.pipeline): they
    # differ only in how client training is laid out (vmap / scan / pod
    # scan-of-vmap) and therefore in which pipeline entry point — batched
    # ``combine``, streaming ``contribution``/``accum_add``, or the cross-pod
    # ``combine_pods`` tail — folds the updates.

    # ------------------------------------------------------------- parallel
    def round_parallel(global_params, server_state, client_batches, weights,
                       mask, rng):
        def client_fn(gp, b, r):
            # the mapped client dim owns client_spmd_axes; model-internal
            # constraints must not mention them inside the vmap body
            with shd.exclude_axes(*_axes_tuple(client_spmd_axes)):
                return local_train(gp, b, r)

        rngs = jax.random.split(rng, C)
        deltas, losses = jax.vmap(client_fn, in_axes=(None, 0, 0),
                                  spmd_axis_name=client_spmd_axes)(
            global_params, client_batches, rngs)
        delta, _, _ = pipe.combine(deltas, weights, mask, losses, rng)
        new_params, new_state = server_step(server_opt, global_params,
                                            delta, server_state)
        metrics = {
            "client_loss": (losses * mask).sum() / jnp.maximum(mask.sum(), 1),
            "delta_norm": global_norm(delta),
            "participation": mask.mean(),
        }
        return new_params, new_state, metrics

    # ----------------------------------------------------------- sequential
    def round_sequential(global_params, server_state, client_batches, weights,
                         mask, rng):
        zero = pipe.accum_init(global_params)
        key = pipe.mask_key(rng)
        ids = jnp.arange(C, dtype=jnp.int32)

        def client_body(carry, xs):
            acc, wsum, loss_sum = carry
            batch_c, w_c, m_c, idx, r = xs
            delta, loss = local_train(global_params, batch_c, r)
            wt = pipe.client_weight(w_c, m_c, loss)
            contrib = pipe.contribution(delta, wt, r, idx=idx, ids=ids,
                                        participation=mask, key=key)
            acc = constrain_like(pipe.accum_add(acc, contrib),
                                 param_shardings)
            return (acc, wsum + wt, loss_sum + loss * m_c), None

        rngs = jax.random.split(rng, C)
        (acc, wsum, loss_sum), _ = jax.lax.scan(
            client_body, (zero, jnp.float32(0.0), jnp.float32(0.0)),
            (client_batches, weights, mask, ids, rngs))
        delta = pipe.normalise(acc, wsum)
        new_params, new_state = server_step(server_opt, global_params,
                                            delta, server_state)
        metrics = {
            "client_loss": loss_sum / jnp.maximum(mask.sum(), 1),
            "delta_norm": global_norm(delta),
            "participation": mask.mean(),
        }
        return new_params, new_state, metrics

    # ------------------------------------------------------- pod_sequential
    # Clients are pinned to pods (sites): the client dim is split [P, C/P]
    # and vmapped over the `pod` mesh axis while each pod scans its own
    # clients sequentially.  During local training NO traffic crosses pods
    # (each client's batch is sharded over `data` within its pod only);
    # pods exchange exactly one compressed delta per round — the paper's
    # hierarchical HPC-site/cloud-site topology (EXPERIMENTS.md §Perf it. 4).
    # The compress stage runs inside the pod body (pod-local under GSPMD);
    # the cross-pod tail (secure-mask-between-pods -> sum -> normalise) is
    # the pipeline's ``combine_pods`` stage.
    def round_pod_sequential(global_params, server_state, client_batches,
                             weights, mask, rng):
        P = n_pods
        Cp = C // P

        def pod_body(batches_p, w_p, m_p, rng_p):
            with shd.exclude_axes(*_axes_tuple(client_spmd_axes)):
                zero = pipe.accum_init(global_params)

                accum_dt = jnp.dtype(cfg.accum_dtype)

                def client_body(carry, xs):
                    acc, wsum, loss_sum = carry
                    batch_c, w_c, m_c, r = xs
                    delta, loss = local_train(global_params, batch_c, r)
                    wt = pipe.client_weight(w_c, m_c, loss)
                    acc = pipe.accum_add(
                        acc, jax.tree.map(
                            lambda d: wt.astype(accum_dt)
                            * d.astype(accum_dt), delta))
                    return (acc, wsum + wt, loss_sum + loss * m_c), None

                rngs = jax.random.split(rng_p, Cp)
                (acc, wsum, loss_sum), _ = jax.lax.scan(
                    client_body, (zero, jnp.float32(0.0), jnp.float32(0.0)),
                    (batches_p, w_p, m_p, rngs))
                # compress the POD-level sum INSIDE the spmd-mapped body —
                # this is what crosses the slow cross-pod link (paper:
                # compress on WAN, not Infiniband), and doing it here keeps
                # the quantize/top-k work pod-local under GSPMD
                acc = pipe.compress(acc, rng_p)
                return acc, wsum, loss_sum

        resh = jax.tree.map(
            lambda x: x.reshape((P, Cp) + x.shape[1:]), client_batches)
        w2 = weights.reshape(P, Cp)
        m2 = mask.reshape(P, Cp)
        accs, wsums, loss_sums = jax.vmap(
            pod_body, spmd_axis_name=client_spmd_axes)(
            resh, w2, m2, jax.random.split(rng, P))
        delta = pipe.combine_pods(accs, wsums.sum(), rng, compressed=True)
        new_params, new_state = server_step(server_opt, global_params,
                                            delta, server_state)
        metrics = {
            "client_loss": loss_sums.sum() / jnp.maximum(mask.sum(), 1),
            "delta_norm": global_norm(delta),
            "participation": mask.mean(),
        }
        return new_params, new_state, metrics

    return {"parallel": round_parallel,
            "sequential": round_sequential,
            "pod_sequential": round_pod_sequential}[cfg.client_exec]
