"""FL round-step semantics: exec-mode equivalence, masking, FedProx,
server optimizers, hierarchical compression path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import CompressionConfig, FLConfig, build_fl_round_step
from repro.models import build_model
from repro.optim import get_client_optimizer, get_server_optimizer

C, H, b, S = 4, 2, 2, 16


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


def run(setup, **kw):
    m, params, batches = setup
    defaults = dict(num_clients=C, local_steps=H, client_lr=0.1)
    defaults.update(kw)
    fl = FLConfig(**defaults)
    step = jax.jit(build_fl_round_step(
        m.loss_fn, get_client_optimizer("sgd"),
        get_server_optimizer("fedavg"), fl,
        n_pods=kw.pop("n_pods", 1) if "n_pods" in kw else 1))
    weights = jnp.ones((C,))
    mask = jnp.ones((C,))
    return step(params, (), batches, weights, mask, jax.random.PRNGKey(2))


def test_parallel_equals_sequential(setup):
    p1, _, m1 = run(setup, client_exec="parallel")
    p2, _, m2 = run(setup, client_exec="sequential")
    for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m1["client_loss"], m2["client_loss"], rtol=1e-5)


def test_masked_client_is_ignored(setup):
    m, params, batches = setup
    fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1)
    step = jax.jit(build_fl_round_step(
        m.loss_fn, get_client_optimizer("sgd"), get_server_optimizer("fedavg"), fl))
    weights = jnp.ones((C,))
    mask = jnp.array([1.0, 1.0, 1.0, 0.0])
    p1, _, _ = step(params, (), batches, weights, mask, jax.random.PRNGKey(2))
    # corrupt client 3's data; result must be identical
    bad = jax.tree.map(lambda x: x.at[3].set(x[3] * 0 + 1), batches)
    p2, _, _ = step(params, (), bad, weights, mask, jax.random.PRNGKey(2))
    for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(a, b_, rtol=1e-6, atol=1e-7)


def test_fedprox_shrinks_delta(setup):
    _, _, m0 = run(setup, fedprox_mu=0.0)
    _, _, m1 = run(setup, fedprox_mu=1.0)
    assert float(m1["delta_norm"]) < float(m0["delta_norm"])


def test_single_client_fullmask_equals_local_sgd(setup):
    m, params, batches = setup
    fl = FLConfig(num_clients=1, local_steps=H, client_lr=0.1)
    step = jax.jit(build_fl_round_step(
        m.loss_fn, get_client_optimizer("sgd"), get_server_optimizer("fedavg"), fl))
    one = jax.tree.map(lambda x: x[:1], batches)
    p1, _, _ = step(params, (), one, jnp.ones((1,)), jnp.ones((1,)),
                    jax.random.PRNGKey(2))
    # manual 2-step SGD
    w = params
    for h in range(H):
        g = jax.grad(lambda p: m.loss_fn(p, jax.tree.map(
            lambda x: x[0, h], one))[0])(w)
        w = jax.tree.map(lambda p, gi: p - 0.1 * gi, w, g)
    for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(w)):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)


def test_compression_changes_but_approximates(setup):
    p_ref, _, _ = run(setup)
    p_q, _, _ = run(setup, compression=CompressionConfig(
        quantize_bits=8, stochastic_rounding=False))
    ref_l = jax.tree.leaves(p_ref)
    q_l = jax.tree.leaves(p_q)
    diffs = [float(jnp.abs(a - b_).max()) for a, b_ in zip(ref_l, q_l)]
    assert max(diffs) > 0                     # actually compressed
    rel = [float(jnp.abs(a - b_).mean() / (jnp.abs(a).mean() + 1e-9))
           for a, b_ in zip(ref_l, q_l)]
    assert max(rel) < 0.05                    # but close


def test_server_optimizers_update(setup):
    m, params, batches = setup
    for name in ("fedadam", "fedyogi"):
        fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1)
        sopt = get_server_optimizer(name)
        step = jax.jit(build_fl_round_step(
            m.loss_fn, get_client_optimizer("sgd"), sopt, fl))
        state = sopt.init(params)
        p, state, _ = step(params, state, batches, jnp.ones((C,)),
                           jnp.ones((C,)), jax.random.PRNGKey(2))
        moved = any(float(jnp.abs(a - b_).max()) > 0
                    for a, b_ in zip(jax.tree.leaves(p), jax.tree.leaves(params)))
        assert moved, name


def test_hierarchical_matches_flat_when_uncompressed(setup):
    m, params, batches = setup
    kw = dict(num_clients=C, local_steps=H, client_lr=0.1)
    flat = FLConfig(**kw)
    hier = FLConfig(hierarchical=True, **kw)
    opt, sopt = get_client_optimizer("sgd"), get_server_optimizer("fedavg")
    s1 = jax.jit(build_fl_round_step(m.loss_fn, opt, sopt, flat, n_pods=1))
    s2 = jax.jit(build_fl_round_step(m.loss_fn, opt, sopt, hier, n_pods=2))
    args = ((), batches, jnp.ones((C,)), jnp.ones((C,)), jax.random.PRNGKey(2))
    p1 = s1(params, *args)[0]
    p2 = s2(params, *args)[0]
    for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)


def test_parallel_under_mesh_requires_spmd_axes(setup):
    # vmapping clients without spmd_axis_name while a mesh is active is the
    # layout that made GSPMD mis-partition the scan transpose (wrong primal
    # loss) — the builder must reject it loudly at build time
    m, _, _ = setup
    from repro.models import sharding as sh
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    fl = FLConfig(num_clients=C, local_steps=H, client_lr=0.1,
                  client_exec="parallel")
    with sh.use_mesh(mesh):
        with pytest.raises(ValueError, match="client_spmd_axes"):
            build_fl_round_step(m.loss_fn, get_client_optimizer("sgd"),
                                get_server_optimizer("fedavg"), fl)
        # declaring the mapped axes is the supported layout
        build_fl_round_step(m.loss_fn, get_client_optimizer("sgd"),
                            get_server_optimizer("fedavg"), fl,
                            client_spmd_axes="data")
