"""Program spans and device scopes.

Host spans land on the profiler's clock, nested and in the order a round
runs them; the async server's phases keep partitioning its wall clock while
they emit spans; the compiled sync round and buffered commit carry the
device scopes in their operations' ``op_name``."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncConfig, CompressionConfig, FLConfig,
                        build_buffer_commit_step, build_fl_round_step)
from repro.data import FederatedDataset, medmnist_like, partition_dirichlet
from repro.models.cnn import CNN, CNNConfig
from repro.optim import get_client_optimizer, get_server_optimizer
from repro.orchestrator import (AsyncOrchestrator, Orchestrator,
                                StragglerPolicy, make_hybrid_fleet)
from repro.spans import span

CFG = CNNConfig("tiny-cnn", (28, 28, 1), 9, channels=(4, 8), dense=32)
ROUND_PHASES = ["fl.round.simulate", "fl.round.data", "fl.round.dispatch",
                "fl.round.fetch", "fl.round.account"]
SECURE8 = dict(secure_agg=True, compression=CompressionConfig(
    quantize_bits=8, stochastic_rounding=False))


def host_spans(logdir):
    """[(name, start_ns, end_ns, stats)] of the program's spans, by start."""
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if ev.name.startswith("fl.")]
    return sorted(out, key=lambda s: s[1])


def fed_setup(n_clients, seed=0):
    data = medmnist_like(n=400, seed=seed)
    parts = partition_dirichlet(data.y, n_clients, alpha=0.5, seed=seed)
    fleet = make_hybrid_fleet(n_clients // 2, n_clients - n_clients // 2,
                              seed=seed, data_sizes=[len(p) for p in parts])
    model = CNN(CFG)
    return (fleet, FederatedDataset(data, parts, seed=seed), model,
            model.init(jax.random.PRNGKey(seed)))


def test_span_nests_on_the_profiler_clock(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with span("fl.outer", round=7):
            with span("fl.outer.inner"):
                jnp.ones(3).block_until_ready()
    (o, os_, oe, ostats), (i, is_, ie, _) = host_spans(tmp_path)
    assert (o, i) == ("fl.outer", "fl.outer.inner")
    assert os_ <= is_ <= ie <= oe
    assert ostats["round"] == 7


def test_round_emits_its_phases_in_order(tmp_path):
    fleet, fed, model, params = fed_setup(4)
    orch = Orchestrator(
        fleet=fleet, fed_data=fed, loss_fn=model.loss_fn,
        fl=FLConfig(num_clients=4, local_steps=1, client_lr=0.05),
        selection_name="random", batch_size=8, seed=0)
    state = orch.init_server_state(params)
    params, state, _ = orch.run_round(0, params, state)     # compiles
    with jax.profiler.trace(str(tmp_path)):
        params, state, log = orch.run_round(1, params, state)
    spans = host_spans(tmp_path)
    assert [n for n, *_ in spans] == ["fl.round"] + ROUND_PHASES
    _, rs, re_, stats = spans[0]
    assert stats["round"] == 1
    ends = [rs]
    for _, s, e, _ in spans[1:]:
        assert ends[-1] <= s <= e <= re_        # children in order, inside
        ends.append(e)
    assert np.isfinite(log.client_loss) and log.delta_norm > 0


def test_async_phases_emit_spans_and_partition_wall_time(tmp_path):
    n = 8
    fleet, fed, model, params = fed_setup(n)
    orch = AsyncOrchestrator(
        fleet=fleet, fed_data=fed, loss_fn=model.loss_fn,
        fl=FLConfig(mode="async", num_clients=n, local_steps=1,
                    client_lr=0.05),
        async_cfg=AsyncConfig(buffer_size=4, max_concurrency=6),
        straggler=StragglerPolicy(contention_sigma=0.5),
        batch_size=8, flops_per_client_round=2e12, seed=0)
    orch.run(params, num_commits=1)                           # compiles
    orch.logs.clear()
    before = sum(orch._phase.values())   # booked since that run's commit
    with jax.profiler.trace(str(tmp_path)):
        orch.run(params, num_commits=4)
    spans = host_spans(tmp_path)
    phases = ("dispatch", "train", "commit", "host_sync")
    assert {n for n, *_ in spans} == {f"fl.async.{p}" for p in phases}
    for log in orch.logs:
        assert set(log.phase_wall) == set(phases) | {"host_syncs"}
        assert all(log.phase_wall[p] >= 0 for p in phases)
    # the counters split the outermost spans' time among the phases: their
    # sum is the time the spans cover, up to each span's own entry cost
    booked = sum(log.phase_wall[p] for log in orch.logs for p in phases)
    booked += sum(orch._phase.values()) - before    # since the last commit
    covered, end = 0, 0
    for _, s, e, _ in spans:
        if e > end:
            covered += (e - max(s, end)) * 1e-9
            end = e
    assert covered == pytest.approx(booked, rel=0.05, abs=0.01)


def _op_names(lowered):
    return set(re.findall(r'op_name="([^"]+)"',
                          lowered.as_text(dialect="hlo", debug_info=True)))


def _has_scope(names, scope):
    pat = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    return any(pat.search(n) for n in names)


def test_sync_round_carries_device_scopes():
    C = 2
    _, _, model, params = fed_setup(4)
    fl = FLConfig(num_clients=C, local_steps=1, client_lr=0.05,
                  client_exec="sequential", **SECURE8)
    step = build_fl_round_step(model.loss_fn, get_client_optimizer("sgd"),
                               get_server_optimizer("fedavg"), fl)
    batches = {"image": jnp.zeros((C, 1, 4, 28, 28, 1)),
               "label": jnp.zeros((C, 1, 4), jnp.int32)}
    names = _op_names(jax.jit(step).lower(
        params, (), batches, jnp.ones(C), jnp.ones(C),
        jax.random.PRNGKey(0)))
    for scope in ("fl.local_train", "fl.commit", "fl.server_step"):
        assert _has_scope(names, scope), scope


def test_buffer_commit_carries_device_scopes():
    K = 4
    _, _, _, params = fed_setup(4)
    fl = FLConfig(mode="async", num_clients=K, client_exec="parallel",
                  **SECURE8)
    server_opt = get_server_optimizer("fedavg")
    step = build_buffer_commit_step(server_opt, fl,
                                    AsyncConfig(buffer_size=K))
    deltas = jax.tree.map(lambda p: jnp.zeros((K,) + p.shape, p.dtype),
                          params)
    ones = jnp.ones(K)
    names = _op_names(jax.jit(step).lower(
        params, server_opt.init(params), deltas, ones, jnp.zeros(K),
        jnp.zeros(K), ones, jnp.arange(K, dtype=jnp.int32),
        jnp.float32(0.5), jax.random.PRNGKey(0)))
    for scope in ("fl.commit", "fl.commit.pack", "fl.commit.unpack",
                  "fl.server_step", "fl_secure_commit"):
        assert _has_scope(names, scope), scope
