"""Driver: the buffered asynchronous commit, server side only.

Set-up makes the weights and two buffers of K slot deltas (each
``N(0, delta_std^2)`` in the configuration's dtype) from the seed, each in
one jitted call, and builds the commit step exactly as
``orchestrator/async_server.py`` does: ``jax.jit(build_buffer_commit_step(
fedavg, fl, AsyncConfig(buffer_size=K)))``.  Every commit's slot inputs
(data-size weights of clients drawn from a pool, staleness, key) are drawn
on the host from the seed.  The first ``check_commits`` commits run in
set-up (the first compiles) and are the ones the reference follows.  The
window is a closed loop, as the server runs: the next commit is dispatched
once the host holds the last one's ``delta_norm``; it ends at the first
commit after ``--seconds``.  The buffers are used in turn.

Traffic parameters: buffer_k, quantize_bits, secure_agg,
staleness_exponent, max_staleness, client_pool, client_rows (range of a
client's data size), delta_std, check_commits, trace_commits,
max_commits (host inputs drawn in advance).
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import fl_reference as flr
from chipbench import lm, weights


def slot_inputs(run) -> dict:
    """Host inputs of every commit: weights of K clients drawn from the
    pool (with replacement: one client may fill two slots), staleness in
    [0, max_staleness], all slots live, slot ids 0..K-1 as the server
    keys its masks."""
    tr = run.traffic
    K, N = tr["buffer_k"], tr["max_commits"]
    rng = np.random.default_rng([run.seed, 1])
    sizes = rng.integers(tr["client_rows"][0], tr["client_rows"][1] + 1,
                         tr["client_pool"]).astype(np.float32)
    cids = rng.integers(0, tr["client_pool"], (N, K))
    keys = np.asarray(jax.random.split(weights.seed_key(run.seed), N))
    return {"weights": sizes[cids],
            "staleness": rng.integers(0, tr["max_staleness"] + 1,
                                      (N, K)).astype(np.float32),
            "losses": np.zeros((N, K), np.float32),
            "mask": np.ones((K,), np.float32),
            "ids": np.arange(K, dtype=np.int32),
            "keys": keys}


def make_deltas(model: lm.Model, run):
    """Both buffers in one jitted call: [K, ...] per leaf, N(0, std^2)."""
    tr = run.traffic
    K, std, n = tr["buffer_k"], tr["delta_std"], 2
    shapes = weights.shapes(model.layout, model.dtype)

    @jax.jit
    def draw(key):
        leaves, treedef = jax.tree.flatten(shapes)
        out = []
        for b in range(n):
            kb = jax.random.fold_in(key, b)
            out.append(jax.tree.unflatten(treedef, [
                (std * jax.random.normal(jax.random.fold_in(kb, i),
                                         (K,) + s.shape, jnp.float32)
                 ).astype(s.dtype) for i, s in enumerate(leaves)]))
        return out
    return draw(jax.random.fold_in(weights.seed_key(run.seed), 2))


def build(run):
    from repro.core import (AsyncConfig, CompressionConfig, FLConfig,
                            build_buffer_commit_step)
    from repro.optim import get_server_optimizer
    tr = run.traffic
    K = tr["buffer_k"]
    fl = FLConfig(mode="async", num_clients=K, client_exec="parallel",
                  secure_agg=tr["secure_agg"],
                  compression=CompressionConfig(
                      quantize_bits=tr["quantize_bits"],
                      stochastic_rounding=False))
    acfg = AsyncConfig(buffer_size=K,
                       staleness_exponent=tr["staleness_exponent"])
    server_opt = get_server_optimizer("fedavg")
    step = jax.jit(build_buffer_commit_step(server_opt, fl, acfg))
    return run.wrap(step), server_opt


def commit(run, st, n: int):
    """Dispatch commit n and wait for its delta norm on the host."""
    ins, tr = st["inputs"], run.traffic
    with run.span("chipbench.commit"):
        params, state, metrics = st["step"](
            st["params"], st["state"], st["deltas"][n % 2],
            ins["weights"][n], ins["staleness"][n], ins["losses"][n],
            ins["mask"], ins["ids"], jnp.float32(tr["staleness_exponent"]),
            ins["keys"][n])
    with run.span("chipbench.fetch"):
        norm = float(metrics["delta_norm"])
    st.update(params=params, state=state)
    return norm


def setup(run):
    tr = run.traffic
    model = lm.Model(run.config)
    step, server_opt = build(run)
    params = model.params(run.seed)
    st = {"model": model, "step": step, "params": params,
          "state": server_opt.init(params), "inputs": slot_inputs(run),
          "deltas": make_deltas(model, run)}
    prog = {"delta_norm": []}
    for n in range(tr["check_commits"]):
        p_prev = st["params"]
        prog["delta_norm"].append(commit(run, st, n))
        if n == 0:
            prog["delta1"] = np.asarray(
                flr.leaf_change_norms(st["params"], p_prev))
        del p_prev
    p0 = model.params(run.seed)
    prog["change"] = np.asarray(flr.leaf_change_norms(st["params"], p0))
    del p0
    st.update(n=tr["check_commits"], prog=prog)
    return st


def window(run, st) -> dict:
    tr = run.traffic
    n = st["n"]
    lat, failed = [], 0
    t0 = run.start_window()
    while True:
        if n >= tr["max_commits"]:
            raise RuntimeError(f"more than max_commits={tr['max_commits']}")
        t = time.perf_counter()
        norm = commit(run, st, n)
        lat.append(time.perf_counter() - t)
        failed += not math.isfinite(norm)
        n += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    window_s = time.perf_counter() - t0
    run.end_window()
    st["n"] = n
    commits = len(lat)
    return {"attempted": commits, "failed": failed,
            "end_to_end": {
                "updates_per_s": commits * tr["buffer_k"] / window_s,
                "commit_p95_ms": 1e3 * float(np.percentile(lat, 95))},
            "layer": {"commits": commits, "window_s": window_s,
                      "commit_median_ms": 1e3 * float(np.median(lat)),
                      "commit_max_ms": 1e3 * float(np.max(lat))}}


def traced(run, st):
    n = st["n"]
    with run.traced():
        for _ in range(run.traffic["trace_commits"]):
            commit(run, st, n)
            n += 1
    st["n"] = n
    return {"traced_commits": run.traffic["trace_commits"]}


def commit_bytes(model: lm.Model, tr) -> int:
    """Least bytes one commit moves: read K slot deltas and the params,
    write the params, each in the configuration's dtype."""
    item = jnp.dtype(model.dtype).itemsize
    return (tr["buffer_k"] + 2) * model.n_params * item


def free(st):
    for k in ("step", "params", "state", "deltas"):
        st.pop(k, None)
    gc.collect()


def reference(run, st, bits: int | None = None) -> dict:
    """The compared commits replayed by the plain reference from the seed;
    ``bits`` below the traffic's gives the control."""
    tr = run.traffic
    bits = tr["quantize_bits"] if bits is None else bits
    model, ins = st["model"], st["inputs"]
    deltas = make_deltas(model, run)
    p0 = params = model.params(run.seed)
    out = {"delta_norm": []}
    for n in range(tr["check_commits"]):
        new, norm = flr.buffer_commit(
            params, deltas[n % 2], ins["weights"][n], ins["staleness"][n],
            ins["mask"], tr["staleness_exponent"], bits)
        out["delta_norm"].append(norm)
        if n == 0:
            out["delta1"] = np.asarray(flr.leaf_change_norms(new, params))
        params = new
    out["change"] = np.asarray(flr.leaf_change_norms(params, p0))
    return out


def compare(run, st, ref: dict) -> dict:
    return lm.step_checks(run, st["model"].leaf_names(), st["prog"], ref,
                          "delta_norm")


def control(run, st) -> dict:
    """The reference put in the program's place one precision below the
    configuration's: 4-bit words on the commit grid."""
    return reference(run, st, bits=run.traffic["quantize_bits"] // 2)


def _unchanged(step):
    def f(params, state, *args):
        _, _, metrics = step(params, state, *args)
        return params, state, metrics
    return f


def _half_batch(step):
    def f(params, state, deltas, weights, staleness, losses, mask, *args):
        K = mask.shape[0]
        half = np.where(np.arange(K) < K // 2, mask, 0.0).astype(np.float32)
        return step(params, state, deltas, weights, staleness, losses, half,
                    *args)
    return f


def _altered(step):
    def f(params, state, *args):
        new, state, metrics = step(params, state, *args)
        new = jax.tree.map(lambda p, n: (p + 1.5 * (n - p)).astype(p.dtype),
                           params, new)
        return new, state, metrics
    return f


# faults planted in the timed step, each of which ``correct`` has to catch:
# the state returned unchanged, half of the slots left out (the mean taken
# over the rest), and the commit's answer, its update, off by half where
# the commit produces it (a smaller error rounds away in bf16)
FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


def main(run) -> dict:
    st = setup(run)
    out = window(run, st)
    if run.trace:
        out["layer"].update(traced(run, st))
    run.read_memory_peak(jax.local_devices()[:run.cell.chips])
    out["layer"]["commit_bytes"] = commit_bytes(st["model"], run.traffic)
    free(st)
    compare(run, st, reference(run, st))
    return out
