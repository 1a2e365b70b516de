"""Hypothesis property-based tests on system invariants: aggregation,
compression, and non-IID partitioning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dep: property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402
from hypothesis.extra import numpy as hnp  # noqa: E402

from repro.core import aggregation as agg
from repro.core.compression import (CompressionConfig, payload_bytes,
                                    quantize_dequant, topk_sparsify)
from repro.data.partition import (partition_by_class, partition_dirichlet,
                                  partition_quantity_skew)

settings.register_profile("fast", max_examples=25, deadline=None)
settings.load_profile("fast")

floats = st.floats(-10, 10, allow_nan=False, width=32)


# ---------------------------------------------------------------- aggregation
@given(hnp.arrays(np.float32, st.tuples(st.integers(2, 6), st.integers(1, 16)),
                  elements=floats))
def test_weighted_mean_of_identical_is_identity(d):
    d = np.repeat(d[:1], d.shape[0], axis=0)          # all clients identical
    out = agg.weighted_mean({"x": jnp.asarray(d)},
                            jnp.ones(d.shape[0]))["x"]
    np.testing.assert_allclose(out, d[0], rtol=1e-5, atol=1e-5)


@given(hnp.arrays(np.float32, st.tuples(st.integers(2, 6), st.integers(1, 16)),
                  elements=floats))
def test_weighted_mean_within_convex_hull(d):
    w = jnp.ones(d.shape[0])
    out = np.asarray(agg.weighted_mean({"x": jnp.asarray(d)}, w)["x"])
    assert (out <= d.max(0) + 1e-4).all()
    assert (out >= d.min(0) - 1e-4).all()


@given(hnp.arrays(np.float32, st.tuples(st.integers(3, 6), st.integers(1, 8)),
                  elements=floats),
       st.integers(0, 5))
def test_masked_client_never_contributes(d, drop):
    C = d.shape[0]
    drop = drop % C
    mask = np.ones(C, np.float32)
    mask[drop] = 0
    w = agg.effective_weights(jnp.ones(C), jnp.asarray(mask))
    out1 = np.asarray(agg.weighted_mean({"x": jnp.asarray(d)}, w)["x"])
    d2 = d.copy()
    d2[drop] = 1e6                                     # poison the masked client
    out2 = np.asarray(agg.weighted_mean({"x": jnp.asarray(d2)}, w)["x"])
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


def test_effective_weights_loss_mode_prefers_low_loss():
    w = agg.effective_weights(jnp.ones(2), jnp.ones(2),
                              jnp.asarray([0.1, 10.0]), "weighted")
    assert float(w[0]) > float(w[1])


# ---------------------------------------------------------------- compression
@given(hnp.arrays(np.float32, st.integers(1, 600), elements=floats))
def test_quantize_error_bounded_by_half_step(x):
    x = jnp.asarray(x)
    y = quantize_dequant(x, bits=8, block=128, stochastic=False)
    xb = np.asarray(x)
    # global bound: per-block scale <= global max / 127
    step = np.abs(xb).max() / 127 if xb.size else 0
    assert (np.abs(np.asarray(y) - xb) <= step * 0.500001 + 1e-6).all()


@given(hnp.arrays(np.float32, st.integers(1, 600), elements=floats),
       st.integers(1, 64))
def test_topk_is_subset_with_unchanged_values(x, k):
    x = jnp.asarray(x)
    y = np.asarray(topk_sparsify(x, k / 128, block=128))
    xv = np.asarray(x)
    nz = y != 0
    np.testing.assert_array_equal(y[nz], xv[nz])
    # zeros only where magnitude below the per-block max
    assert (np.abs(y) <= np.abs(xv) + 1e-9).all()


@given(st.integers(1, 2000), st.sampled_from([4, 8]),
       st.floats(0.01, 0.9))
def test_payload_bytes_monotone(n, bits, frac):
    tree = {"w": np.zeros(n, np.float32)}
    full = payload_bytes(tree, None)
    q = payload_bytes(tree, CompressionConfig(quantize_bits=bits))
    assert full == n * 4
    assert q < full + 132  # quant never bigger (mod per-block scale overhead)
    both = payload_bytes(tree, CompressionConfig(quantize_bits=bits,
                                                 topk_frac=frac))
    lighter = payload_bytes(tree, CompressionConfig(quantize_bits=bits,
                                                    topk_frac=frac / 2 + 1e-3))
    assert lighter <= both + 1


def test_paper_table4_compression_ratio():
    """Paper Table 4: 43-45 MB -> 13-16 MB (~65% reduction) with
    quantization+sparsification.  Our defaults should land in that band."""
    tree = {"w": np.zeros(11_250_000, np.float32)}     # ~45 MB fp32 model
    full = payload_bytes(tree, None)
    comp = payload_bytes(tree, CompressionConfig(quantize_bits=8,
                                                 topk_frac=0.1))
    ratio = comp / full
    assert 0.1 < ratio < 0.45, ratio


# ---------------------------------------------------------------- partitioning
@given(st.integers(40, 400), st.integers(2, 10))
def test_partition_by_class_covers_all(n, c):
    y = np.random.default_rng(0).integers(0, 10, n)
    parts = partition_by_class(y, c, 2)
    allidx = np.concatenate(parts)
    assert len(allidx) == n
    assert len(np.unique(allidx)) == n                # disjoint cover


@given(st.integers(100, 500), st.integers(2, 8),
       st.floats(0.05, 5.0))
def test_dirichlet_partition_covers_all(n, c, alpha):
    y = np.random.default_rng(1).integers(0, 10, n)
    parts = partition_dirichlet(y, c, alpha, min_size=1)
    allidx = np.concatenate(parts)
    assert len(allidx) == n
    assert len(np.unique(allidx)) == n


def test_pathological_partition_is_skewed():
    y = np.random.default_rng(2).integers(0, 10, 2000)
    parts = partition_by_class(y, 10, 2)
    n_classes = [len(np.unique(y[p])) for p in parts]
    # 2 shards per client; a shard can straddle one class boundary, so 2-4
    # classes max, and on average the paper's 2-3.
    assert max(n_classes) <= 4
    assert np.mean(n_classes) <= 3.0


@given(st.integers(50, 500), st.integers(2, 8))
def test_quantity_skew_covers_all(n, c):
    parts = partition_quantity_skew(n, c)
    allidx = np.concatenate(parts)
    assert len(np.unique(allidx)) == len(allidx) == n


# ------------------------------------------------------------ async resume
# checkpoint -> restore at a random event index is a NO-OP on the final
# state, for random (K, T, dropout, preempt, recovery_policy) configs
_ASYNC_CACHE: dict = {}


def _mini_async(K, T, dropout, preempt, policy, mgr=None):
    from repro.core import AsyncConfig, FLConfig
    from repro.data import FederatedDataset, medmnist_like, partition_dirichlet
    from repro.models.cnn import CNN, CNNConfig
    from repro.orchestrator import (AsyncOrchestrator, FaultConfig,
                                    StragglerPolicy, make_hybrid_fleet)
    seed, n_clients = 5, 4
    if "base" not in _ASYNC_CACHE:
        data = medmnist_like(n=200, seed=seed)
        parts = partition_dirichlet(data.y, n_clients, alpha=0.5, seed=seed)
        model = CNN(CNNConfig("prop-cnn", (28, 28, 1), 9, channels=(2, 4),
                              dense=8))
        _ASYNC_CACHE["base"] = (data, parts, model,
                                model.init(jax.random.PRNGKey(seed)))
    data, parts, model, params = _ASYNC_CACHE["base"]
    orch = AsyncOrchestrator(
        fleet=make_hybrid_fleet(2, 2, seed=seed,
                                data_sizes=[len(p) for p in parts]),
        fed_data=FederatedDataset(data, parts, seed=seed),
        loss_fn=model.loss_fn,
        fl=FLConfig(mode="async", num_clients=n_clients, local_steps=1,
                    client_lr=0.05),
        async_cfg=AsyncConfig(buffer_size=K, commit_timeout_s=T,
                              max_concurrency=3, max_staleness=50),
        straggler=StragglerPolicy(contention_sigma=0.5),
        faults=FaultConfig(dropout_prob=dropout, spot_preempt_prob=preempt,
                           recovery_policy=policy),
        batch_size=4, flops_per_client_round=2e12,
        checkpoint_mgr=mgr, seed=seed)
    # the jit'd steps depend only on (model cfg, FLConfig, K) — share them
    # across examples so each K compiles once
    if K in _ASYNC_CACHE:
        orch._client_update, orch._commit_step = _ASYNC_CACHE[K]
    else:
        _ASYNC_CACHE[K] = (orch._client_update, orch._commit_step)
    return orch, params


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 3), st.sampled_from([0.0, 0.6]),
       st.sampled_from([0.0, 0.3]), st.sampled_from([0.0, 0.5]),
       st.sampled_from(["restart", "resume", "discard"]),
       st.integers(0, 30))
def test_async_checkpoint_restore_is_noop(K, T, dropout, preempt, policy,
                                          kill_idx):
    import tempfile
    from repro.checkpoint import AsyncCheckpointManager

    n_commits = 3
    straight, params = _mini_async(K, T, dropout, preempt, policy)
    p_straight, _ = straight.run(params, n_commits)
    events = straight.events_processed
    assert events, "run produced no events"
    # cut at the (kill_idx mod len)-th processed event's sim-time
    budget = events[kill_idx % len(events)][0]

    with tempfile.TemporaryDirectory() as ckdir:
        mgr = AsyncCheckpointManager(ckdir, keep=2)
        killed, params2 = _mini_async(K, T, dropout, preempt, policy, mgr=mgr)
        killed.run(params2, n_commits, max_sim_time=budget)

        resumed, params3 = _mini_async(K, T, dropout, preempt, policy)
        p0, st0 = mgr.restore_async(resumed, params3)
        p_resumed, _ = resumed.run(p0, n_commits, server_state=st0)

    assert resumed.version == straight.version
    assert [l.sim_time for l in resumed.logs] \
        == [l.sim_time for l in straight.logs]
    assert resumed.events_processed == events
    for a, b in zip(jax.tree.leaves(p_resumed), jax.tree.leaves(p_straight)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)


# ------------------------------------------------------- pipeline invariants
# hypothesis front-end over the checkers in test_pipeline_properties.py
# (which also runs them as a seeded sweep when hypothesis is unavailable):
# slot-permutation invariance, mask cancellation for arbitrary
# participation vectors, and chunked == single-shot commit accumulation
from test_pipeline_properties import (check_chunked_equals_single_shot,  # noqa: E402
                                      check_masked_equals_plain,
                                      check_permutation_invariant)


@st.composite
def _buffers(draw):
    K = draw(st.integers(2, 8))
    D = draw(st.integers(1, 12))
    d = draw(hnp.arrays(np.float32, (K, D), elements=floats))
    w = draw(hnp.arrays(np.float32, (K,),
                        elements=st.floats(np.float32(0.1), 5, width=32)))
    m = np.asarray(draw(st.lists(st.integers(0, 1), min_size=K, max_size=K)),
                   np.float32)
    s = np.asarray(draw(st.lists(st.integers(0, 10), min_size=K, max_size=K)),
                   np.float32)
    l = draw(hnp.arrays(np.float32, (K,),
                        elements=st.floats(0.0, 5.0, width=32)))
    return d, w, m, s, l


@settings(max_examples=15, deadline=None)
@given(_buffers(), st.integers(0, 10_000), st.booleans())
def test_commit_is_permutation_invariant_within_buffer(buf, pseed, secure):
    check_permutation_invariant(buf, perm_seed=pseed, secure=secure)


@settings(max_examples=15, deadline=None)
@given(_buffers())
def test_masked_equals_plain_for_arbitrary_participation(buf):
    check_masked_equals_plain(buf)


@settings(max_examples=10, deadline=None)
@given(_buffers(), st.integers(1, 8), st.booleans())
def test_chunked_commit_equals_single_shot(buf, C, secure):
    check_chunked_equals_single_shot(buf, C, secure)
