"""Federated training launcher — the deployable entry point (deliverable b).

    PYTHONPATH=src python -m repro.launch.train \
        --dataset cifar10 --algo fedprox --rounds 100 \
        --clients-pool 60 --clients-per-round 20 --local-steps 5 \
        --quantize-bits 8 --topk-frac 0.1 --fastest-k 16 \
        --checkpoint-dir ckpts/run1 --render-jobs artifacts/jobs

Defaults mirror the paper's §5.1 configuration (60-node hybrid fleet,
20 clients/round, 5 local epochs, 100 rounds).  --render-jobs additionally
emits the sbatch scripts / pod manifests the scheduler adapter would submit
for each selected client (deployability artifact).
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointManager, CheckpointManager
from repro.configs import get_config
from repro.core import AsyncConfig, CompressionConfig, FLConfig
from repro.data import (FederatedDataset, cifar10_like, medmnist_like,
                        partition_by_class, partition_by_group,
                        shakespeare_like)
from repro.models import build_model
from repro.models.cnn import CIFAR_CNN, CNN, MEDMNIST_CNN
from repro.core import payload_bytes
from repro.exec import BACKEND_NAMES, make_backend
from repro.comm import LinkClass, WANTopology
from repro.orchestrator import (AsyncOrchestrator, BatchedAsyncOrchestrator,
                                CohortFleet, EventWindowOrchestrator,
                                FaultConfig, HierarchicalOrchestrator,
                                Orchestrator, StragglerPolicy,
                                equivalent_preempt_rate_per_min,
                                make_facilities, make_hybrid_fleet,
                                split_fleet)
from repro.orchestrator.straggler import expected_attempt_s
from repro.sched import HybridAdapter, JobSpec, K8sAdapter, SlurmAdapter

REPO_ROOT = Path(__file__).resolve().parents[3]


def init_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home and return it.
    A ``JAX_COMPILATION_CACHE_DIR`` from the environment wins (JAX reads it
    itself, so nothing is set); otherwise ``<repo>/.jax_compile_cache``.
    The directory is part of every cache key, so it is never derived from a
    tmpdir, a pid or the clock.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_compile_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# --engine auto crossover: below this fleet size the per-event engine wins
# (no vmap padding / bucketing overhead on tiny fleets — see the committed
# artifacts/bench/table_megafleet.json sweep: legacy 3.1 vs batched 3.9
# wall_per_sim_s at 100 clients, batched/window ~11x faster from 1k up)
AUTO_ENGINE_THRESHOLD = 300


def resolve_engine(engine: str, fleet) -> str:
    """Map --engine auto to a concrete engine from the fleet size."""
    if engine != "auto":
        return engine
    if isinstance(fleet, CohortFleet) or len(fleet) >= AUTO_ENGINE_THRESHOLD:
        return "window"
    return "legacy"


def _staleness_exp(v: str):
    if v == "adaptive":
        return v
    try:
        return float(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected a float or 'adaptive', got {v!r}")


def build_task(name: str, n_clients: int, seed: int):
    if name == "cifar10":
        ds = cifar10_like(n=20_000, seed=seed)
        parts = partition_by_class(ds.y, n_clients, 2, seed=seed)
        model = CNN(CIFAR_CNN)
    elif name == "medmnist":
        ds = medmnist_like(n=12_000, seed=seed)
        parts = partition_by_class(ds.y, n_clients, 3, seed=seed)
        model = CNN(MEDMNIST_CNN)
    elif name == "shakespeare":
        ds = shakespeare_like(n_seqs=8000, seq_len=64, n_speakers=2 * n_clients,
                              seed=seed)
        parts = partition_by_group(ds.y, n_clients, seed=seed)
        model = build_model(get_config("paper-charlm"))
    else:
        raise ValueError(name)
    fed = FederatedDataset(ds, parts, seed=seed)
    params = model.init(jax.random.PRNGKey(seed))
    if hasattr(model, "accuracy"):
        eval_batch = jax.tree.map(jnp.asarray, fed.eval_batch(1024))
        acc = jax.jit(model.accuracy)
        eval_fn = lambda p: acc(p, eval_batch)
    else:
        eval_fn = None
    return fed, model, params, eval_fn


def render_jobs(fleet, out_dir: Path):
    hy = HybridAdapter()
    out_dir.mkdir(parents=True, exist_ok=True)
    for c in fleet:
        spec = JobSpec(
            name=f"fl-client-{c.cid}",
            command=f"python -m repro.worker --client-id {c.cid}",
            gpus_per_node=1 if c.profile.compute_tflops > 4 else 0,
            mem_gb=int(c.profile.memory_gb), site=c.site,
            preemptible=c.profile.spot)
        h = hy.submit(spec)
        ext = "sbatch" if c.site == "hpc" else "json"
        (out_dir / f"client{c.cid:03d}.{ext}").write_text(h.artifact)
    return len(fleet)


def main(argv=None):
    """Run one federated job from CLI-style ``argv`` (default: sys.argv)
    and return the summary it prints."""
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cifar10",
                    choices=["cifar10", "medmnist", "shakespeare"])
    ap.add_argument("--algo", default="fedavg", choices=["fedavg", "fedprox"])
    ap.add_argument("--mode", default="sync", choices=["sync", "async"],
                    help="sync: barrier rounds; async: FedBuff buffered "
                         "commits (--rounds then counts server commits)")
    ap.add_argument("--exec-backend", default="closed-form",
                    choices=list(BACKEND_NAMES),
                    help="where simulated client time comes from: "
                         "'closed-form' (lognormal straggler model, the fast "
                         "default) or 'scheduler' (dispatch every attempt as "
                         "a job through the SLURM+K8s hybrid adapter: queue "
                         "waits, elastic HPC->cloud overflow, and spot "
                         "preemptions from the K8s adapter's event stream)")
    ap.add_argument("--hpc-nodes", type=int, default=0,
                    help="scheduler backend: SLURM partition size "
                         "(0 = one node per HPC client)")
    ap.add_argument("--cloud-nodes", type=int, default=0,
                    help="scheduler backend: K8s autoscale ceiling "
                         "(0 = one node per cloud client)")
    ap.add_argument("--spot-preempt-per-min", type=float, default=0.0,
                    help="scheduler backend: per-minute spot reclaim rate "
                         "for preemptible pods (replaces the injector's "
                         "--spot-preempt-prob draw)")
    ap.add_argument("--buffer-k", type=int, default=8,
                    help="async: commit every K buffered updates")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "legacy", "batched", "window"],
                    help="async event engine: 'legacy' processes one event "
                         "at a time; 'batched' defers client training into "
                         "vmap chunks and batches dispatch; 'window' "
                         "additionally blocks every RNG/key draw per commit "
                         "window, keeps pending arrivals in numpy structured "
                         "arrays and performs ONE host sync per window.  All "
                         "three are bit-identical on flat fleets "
                         "(tests/test_megafleet_equivalence.py).  'auto' "
                         "(default) picks by fleet size: per-event dispatch "
                         "is faster below ~%d clients, the window engine "
                         "above (crossover measured in artifacts/bench/"
                         "table_megafleet.json: legacy 3.1 vs batched 3.9 "
                         "wall_per_sim_s at 100 clients, 11x the other way "
                         "at 1k+)" % AUTO_ENGINE_THRESHOLD)
    ap.add_argument("--train-chunk", type=int, default=32,
                    help="batched/window engines: max vmap lanes per "
                         "deferred training chunk")
    ap.add_argument("--event-window", type=int, default=256,
                    help="window engine: events per blocked RNG/key draw "
                         "(and per scheduler GC window)")
    ap.add_argument("--commit-chunk", type=int, default=0,
                    help="async: accumulate the commit buffer this many "
                         "slots at a time instead of stacking all K (0 = "
                         "single-shot; chunked commits agree to ~1e-5, not "
                         "bitwise — float summation order changes)")
    ap.add_argument("--staleness-exp", type=_staleness_exp, default=0.5,
                    help="async: staleness discount 1/(1+s)^a — a float, or "
                         "'adaptive' for the online FedAsync-style alpha "
                         "tuned from the observed staleness distribution")
    ap.add_argument("--secure-agg", action="store_true",
                    help="commit-keyed pairwise masking (Bonawitz-style "
                         "secure aggregation): the server only sees masked "
                         "updates whose masks cancel within each round/"
                         "commit; works in BOTH --mode sync and async")
    ap.add_argument("--facilities", type=int, default=0,
                    help="two-tier federation: split the fleet into N "
                         "facilities, each running --mode locally over its "
                         "own backend, with a tier-2 server federating "
                         "facility deltas over WAN (dcn) links; --rounds "
                         "then counts tier-2 commits/epochs (0 = flat)")
    ap.add_argument("--facility-backend", default="",
                    choices=[""] + list(BACKEND_NAMES),
                    help="execution backend each facility runs on "
                         "(default: inherit --exec-backend)")
    ap.add_argument("--inter-facility-mode", default="sync",
                    choices=["sync", "async"],
                    help="tier-2 regime: 'sync' barriers on every facility "
                         "per epoch; 'async' commits facility deltas as "
                         "they arrive, staleness-discounted")
    ap.add_argument("--local-rounds", type=int, default=2,
                    help="tier-1 rounds/commits one facility runs per "
                         "tier-2 epoch")
    ap.add_argument("--inter-buffer", type=int, default=1,
                    help="async inter-facility mode: tier-2 commit every "
                         "K facility deltas")
    ap.add_argument("--wan-bw", type=float, default=6.25,
                    help="inter-facility WAN bandwidth, GB/s (dcn class)")
    ap.add_argument("--wan-latency", type=float, default=1e-3,
                    help="inter-facility WAN latency, seconds")
    ap.add_argument("--wan-jitter", type=float, default=0.0,
                    help="exponential jitter tail added per WAN transfer, "
                         "seconds (0 = deterministic)")
    ap.add_argument("--max-staleness", type=int, default=20)
    ap.add_argument("--commit-timeout", type=float, default=0.0,
                    help="async: commit a partial buffer after T sim-seconds")
    ap.add_argument("--max-concurrency", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients-pool", type=int, default=60)
    ap.add_argument("--clients-per-round", type=int, default=20)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.08)
    ap.add_argument("--mu", type=float, default=0.02)
    ap.add_argument("--quantize-bits", type=int, default=0)
    ap.add_argument("--topk-frac", type=float, default=0.0)
    ap.add_argument("--fed-dropout", type=float, default=0.0)
    ap.add_argument("--use-fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused Pallas commit path (compress+mask+accumulate "
                         "in one pass; interpret mode on CPU). --no-use-fused "
                         "forces the unfused jnp stages")
    ap.add_argument("--stochastic-rounding",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="stochastic rounding for quantization "
                         "(--no-stochastic-rounding selects deterministic "
                         "round-to-nearest, the fully-fusable mode)")
    ap.add_argument("--fastest-k", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=0.0)
    ap.add_argument("--dropout-prob", type=float, default=0.0)
    ap.add_argument("--spot-preempt-prob", type=float, default=0.0)
    ap.add_argument("--partition-prob", type=float, default=0.0)
    ap.add_argument("--recovery-policy", default="restart",
                    choices=["restart", "resume", "discard", "adaptive"],
                    help="async: what a preempted/partitioned client does "
                         "with its interrupted attempt (paper §5.4); "
                         "'adaptive' picks per fault from observed "
                         "staleness + remaining work")
    ap.add_argument("--recovery-overhead-s", type=float, default=0.0)
    ap.add_argument("--server-opt", default="fedavg",
                    choices=["fedavg", "fedadam", "fedyogi"])
    ap.add_argument("--selection", default="adaptive",
                    choices=["adaptive", "random"])
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="sync: rounds between snapshots; async: commits")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--checkpoint-dir (async resumes bit-identically: "
                         "event heap, buffer and RNG streams are restored)")
    ap.add_argument("--render-jobs", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    fed, model, params, eval_fn = build_task(args.dataset, args.clients_pool,
                                             args.seed)
    n_hpc = args.clients_pool // 2
    n_cloud = args.clients_pool - n_hpc

    def build_backend():
        if args.exec_backend != "scheduler":
            return make_backend("closed-form")
        spot_rate = args.spot_preempt_per_min
        if args.spot_preempt_prob and not spot_rate:
            # under the scheduler backend spot preemptions originate from
            # the K8s adapter's reclaim events, not an injector draw — map
            # the per-ATTEMPT Bernoulli probability onto the equivalent
            # per-minute exponential rate at this fleet's mean attempt time
            mean_s = expected_attempt_s(
                fleet, 3e12, payload_bytes(params, fl.compression),
                StragglerPolicy())
            spot_rate = equivalent_preempt_rate_per_min(
                args.spot_preempt_prob, mean_s)
            print(f"scheduler backend: mapped --spot-preempt-prob "
                  f"{args.spot_preempt_prob:g}/attempt onto "
                  f"{spot_rate:.4f} reclaims/min "
                  f"(mean attempt {mean_s:.1f}s)")
        elif args.spot_preempt_prob:
            print("warning: --spot-preempt-per-min overrides the "
                  "--spot-preempt-prob mapping under --exec-backend "
                  "scheduler")
        cloud = args.cloud_nodes or n_cloud
        return make_backend(
            "scheduler",
            slurm=SlurmAdapter(total_nodes=args.hpc_nodes or n_hpc,
                               seed=args.seed),
            k8s=K8sAdapter(initial_nodes=max(1, cloud // 2), max_nodes=cloud,
                           preempt_prob_per_min=spot_rate,
                           seed=args.seed + 1))
    fl = FLConfig(
        mode=args.mode,
        num_clients=args.clients_per_round, local_steps=args.local_steps,
        client_lr=args.lr, fedprox_mu=args.mu if args.algo == "fedprox" else 0.0,
        secure_agg=args.secure_agg,
        compression=CompressionConfig(quantize_bits=args.quantize_bits,
                                      topk_frac=args.topk_frac,
                                      dropout_frac=args.fed_dropout,
                                      stochastic_rounding=args.stochastic_rounding,
                                      use_fused=args.use_fused))
    fleet = make_hybrid_fleet(n_hpc, n_cloud, seed=args.seed,
                              data_sizes=[fed.client_size(c)
                                          for c in range(fed.num_clients)])
    if args.render_jobs:
        n = render_jobs(fleet, Path(args.render_jobs))
        print(f"rendered {n} scheduler artifacts -> {args.render_jobs}")
    faults = FaultConfig(dropout_prob=args.dropout_prob,
                         spot_preempt_prob=args.spot_preempt_prob,
                         partition_prob=args.partition_prob,
                         recovery_policy=args.recovery_policy,
                         recovery_overhead_s=args.recovery_overhead_s)
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.facilities:
        fac_backend = args.facility_backend or args.exec_backend
        subs, _ = split_fleet(fleet, args.facilities)

        def backend_factory(f):
            if fac_backend != "scheduler":
                return make_backend("closed-form")
            n_h = sum(c.site == "hpc" for c in subs[f])
            n_c = max(1, sum(c.site == "cloud" for c in subs[f]))
            return make_backend(
                "scheduler",
                slurm=SlurmAdapter(total_nodes=max(1, args.hpc_nodes or n_h),
                                   seed=args.seed + 10 * f),
                k8s=K8sAdapter(initial_nodes=max(1, n_c // 2), max_nodes=n_c,
                               preempt_prob_per_min=args.spot_preempt_per_min,
                               seed=args.seed + 10 * f + 1))

        local_async = AsyncConfig(
            buffer_size=args.buffer_k, staleness_exponent=args.staleness_exp,
            max_staleness=args.max_staleness,
            commit_timeout_s=args.commit_timeout,
            max_concurrency=args.max_concurrency,
            commit_chunk=args.commit_chunk)
        facs = make_facilities(
            args.facilities, fleet, fed, model.loss_fn, fl,
            local_mode=args.mode, async_cfg=local_async,
            local_rounds=args.local_rounds, backend_factory=backend_factory,
            seed=args.seed,
            orch_kw=dict(selection_name=args.selection,
                         straggler=StragglerPolicy(), faults=faults,
                         batch_size=args.batch_size,
                         flops_per_client_round=3e12))
        wan = WANTopology(
            default=LinkClass("dcn", args.wan_bw, args.wan_latency),
            jitter_s=args.wan_jitter)
        mgr = (AsyncCheckpointManager(args.checkpoint_dir)
               if args.checkpoint_dir else None)
        hier = HierarchicalOrchestrator(
            facs, fl, inter_mode=args.inter_facility_mode,
            async_cfg=AsyncConfig(buffer_size=args.inter_buffer,
                                  staleness_exponent=args.staleness_exp
                                  if args.staleness_exp != "adaptive"
                                  else 0.5,
                                  max_staleness=args.max_staleness),
            wan=wan, server_opt_name=args.server_opt, eval_fn=eval_fn,
            eval_every=1, checkpoint_mgr=mgr,
            checkpoint_every=args.checkpoint_every, seed=args.seed)
        server_state = None
        if args.resume and mgr.latest_round() is not None:
            params, server_state = mgr.restore_hier(hier, params)
            print(f"resumed hierarchical run at commit {hier.version} "
                  f"(sim t={hier.clock:.1f}s, {len(hier._events)} facility "
                  f"deltas in flight, {len(hier._buffer)} buffered)")
        params, _ = hier.run(params, args.rounds, server_state=server_state,
                             verbose=True)
        summary = {
            "dataset": args.dataset, "algo": args.algo, "mode": "hier",
            "local_mode": args.mode,
            "inter_facility_mode": args.inter_facility_mode,
            "facilities": args.facilities,
            "local_rounds": args.local_rounds,
            "exec_backend": fac_backend,
            "secure_agg": args.secure_agg,
            "commits": hier.version,
            "dropped_stale": hier.dropped_stale,
            "final_eval": hier.logs[-1].eval_metric if hier.logs else None,
            "virtual_time_s": hier.clock,
            "inter_facility_bytes": hier.inter_facility_bytes,
            "total_bytes": hier.total_bytes(),
            "facility_clocks": [f.clock for f in facs],
        }
    elif args.mode == "async":
        if args.deadline_s or args.fastest_k:
            print("warning: --deadline-s/--fastest-k are barrier-round "
                  "mitigations; the async regime ignores them (staleness "
                  "discounting replaces them)")
        mgr = (AsyncCheckpointManager(args.checkpoint_dir)
               if args.checkpoint_dir else None)
        engine = resolve_engine(args.engine, fleet)
        if args.engine == "auto":
            print(f"--engine auto: {len(fleet)} clients -> {engine} "
                  f"(crossover {AUTO_ENGINE_THRESHOLD})")
        orch_cls = {"legacy": AsyncOrchestrator,
                    "batched": BatchedAsyncOrchestrator,
                    "window": EventWindowOrchestrator}[engine]
        engine_kw = ({} if engine == "legacy"
                     else {"train_chunk": args.train_chunk})
        if engine == "window":
            engine_kw["window"] = args.event_window
        orch = orch_cls(
            fleet=fleet, fed_data=fed, loss_fn=model.loss_fn, fl=fl,
            async_cfg=AsyncConfig(buffer_size=args.buffer_k,
                                  staleness_exponent=args.staleness_exp,
                                  max_staleness=args.max_staleness,
                                  commit_timeout_s=args.commit_timeout,
                                  max_concurrency=args.max_concurrency,
                                  commit_chunk=args.commit_chunk),
            server_opt_name=args.server_opt, selection_name=args.selection,
            straggler=StragglerPolicy(), faults=faults,
            batch_size=args.batch_size, flops_per_client_round=3e12,
            eval_fn=eval_fn, eval_every=10, checkpoint_mgr=mgr,
            checkpoint_every=args.checkpoint_every,
            backend=build_backend(), seed=args.seed, **engine_kw)
        server_state = None
        if args.resume and mgr.latest_round() is not None:
            params, server_state = mgr.restore_async(orch, params)
            print(f"resumed async run at commit {orch.version} "
                  f"(sim t={orch.clock:.1f}s, {len(orch._inflight)} clients "
                  f"in flight, {len(orch._buffer)} updates buffered)")
        params, _ = orch.run(params, args.rounds, server_state=server_state,
                             verbose=True)
        summary = {
            "dataset": args.dataset, "algo": args.algo, "mode": "async",
            "exec_backend": args.exec_backend, "engine": engine,
            "secure_agg": args.secure_agg,
            "mask_overhead_bytes": sum(l.mask_overhead_bytes
                                       for l in orch.logs),
            "commits": orch.version,
            "updates_applied": orch.updates_applied,
            "dropped_stale": orch.dropped_stale,
            "recovered_updates": orch.recovered_updates,
            "lost_to_faults": orch.lost_to_faults,
            "final_eval": orch.logs[-1].eval_metric if orch.logs else None,
            "final_loss": orch.logs[-1].client_loss if orch.logs else None,
            "virtual_time_s": orch.clock,
            "updates_per_sim_s": orch.updates_per_sim_second,
            "mean_queue_wait_s": (float(np.mean([l.queue_wait_s
                                                 for l in orch.logs]))
                                  if orch.logs else 0.0),
            "overflow_updates": sum(l.n_overflow for l in orch.logs),
            "recovery_actions": sum(len(l.recovery_actions)
                                    for l in orch.logs),
        }
    else:
        mgr = (CheckpointManager(args.checkpoint_dir)
               if args.checkpoint_dir else None)
        orch = Orchestrator(
            fleet=fleet, fed_data=fed, loss_fn=model.loss_fn, fl=fl,
            server_opt_name=args.server_opt, selection_name=args.selection,
            straggler=StragglerPolicy(deadline_s=args.deadline_s,
                                      fastest_k=args.fastest_k),
            faults=faults,
            batch_size=args.batch_size, flops_per_client_round=3e12,
            eval_fn=eval_fn, eval_every=10, checkpoint_mgr=mgr,
            checkpoint_every=args.checkpoint_every,
            backend=build_backend(), seed=args.seed)
        server_state, start_round = None, 0
        if args.resume and mgr.latest_round() is not None:
            server_state = orch.init_server_state(params)
            params, server_state, meta = mgr.restore(params, server_state)
            start_round = meta["round"] + 1
            orch.virtual_clock = meta.get("clock", 0.0)
            if meta.get("exec_backend", "closed-form") != args.exec_backend:
                raise SystemExit(
                    f"checkpoint was written under --exec-backend "
                    f"{meta.get('exec_backend', 'closed-form')}; resume "
                    f"with the same backend")
            if meta.get("backend_state"):
                orch.backend.set_state(meta["backend_state"])
            print(f"resumed sync run at round {start_round} "
                  f"(sim t={orch.virtual_clock:.1f}s)")
        params, _ = orch.run(params, args.rounds, server_state=server_state,
                             start_round=start_round, verbose=True)
        summary = {
            "dataset": args.dataset, "algo": args.algo, "mode": "sync",
            "exec_backend": args.exec_backend,
            "secure_agg": args.secure_agg,
            "rounds": args.rounds,
            "final_eval": orch.logs[-1].eval_metric if orch.logs else None,
            "final_loss": orch.logs[-1].client_loss if orch.logs else None,
            "virtual_time_s": orch.virtual_clock,
            "mean_bytes_per_client_round":
                orch.comm.mean_bytes_per_client_round(),
            "mean_queue_wait_s": (float(np.mean([l.mean_queue_wait_s
                                                 for l in orch.logs]))
                                  if orch.logs else 0.0),
            "overflow_clients": sum(l.n_overflow for l in orch.logs),
            "preempted_clients": sum(l.n_preempted for l in orch.logs),
        }
    print(json.dumps(summary, indent=1))
    return summary


if __name__ == "__main__":
    main()
