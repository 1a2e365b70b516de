"""Bring-up smoke test: drive the federated training path once on the TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: phase C only

A. The CLI path (``repro.launch.train.main``, called in-process): an async
   secure 8-bit cifar10 job with deterministic rounding, so the fused
   Pallas commit kernels are on the path, repeated with ``--no-use-fused``
   (``final_eval`` and ``final_loss`` must agree bit for bit); then a sync
   ``shakespeare`` (paper-charlm) job whose losses must be finite.
B. ``xlstm-125m`` at its published widths through ``build_fl_round_step``:
   sequential clients, K=4, 2 local steps, per-client batch 4x2048, secure
   8-bit commit, 2 rounds on non-IID tokens drawn from ``--seed``.  Losses
   finite, the compiled round holds a ``tpu_custom_call``, fused == unfused
   new params bit for bit when both are compiled without XLA's excess
   precision (``STRICT_BF16``) and to ``DEFAULT_PAIR_TOL`` when compiled as
   users compile them.  Then the async server's buffered secure commit
   (``build_buffer_commit_step``, K=4) of four xlstm-125m-shaped deltas
   drawn from ``--seed``: the bucketed Pallas secure kernel against its jnp
   oracle, bit for bit.
C. (``--chips 4``) one ``xlstm-125m`` round in ``parallel`` mode on a 2x2
   ("data", "model") mesh with the client dim over "data" and the secure
   fused commit, against the same round on one device without a mesh
   (``tests/test_mesh_small.py``'s xlstm tolerances) and against the
   strict unfused commit under the mesh (bit for bit, as in B).

Every phase prints one JSON line: wall and compile seconds, each device's
``peak_bytes_in_use`` and what it checked.  These are set-up figures, not
speed measurements.  A failed check raises, so the script exits non-zero
and prints no final line.  Without a TPU it exits non-zero before any
phase.  The last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (AsyncConfig, CompressionConfig,  # noqa: E402
                        FLConfig, build_buffer_commit_step,
                        build_fl_round_step)
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import specs as sp  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.train import init_compile_cache  # noqa: E402
from repro.launch.train import main as train_main  # noqa: E402
from repro.models import build_model, sharding as sh  # noqa: E402
from repro.optim import (get_client_optimizer,  # noqa: E402
                         get_server_optimizer)

C, H, B, S = 4, 2, 4, 2048        # clients, local steps, per-client batch
MESH_B, MESH_S = 2, 1024          # phase C per-client batch (see phase_c)
CLIENT_LR = 1e-3                  # SGD; 0.1 makes xlstm-125m NaN by round 2
# XLA may keep bf16 intermediates in f32 inside a fusion ("excess
# precision"), and where it does depends on what else is fused.  So the
# fused and unfused round programs round a few thousand of the 162M bf16
# delta elements differently.  The "_strict" pair is compiled without
# excess precision: there the two executors agree bit for bit, so it checks
# the kernels alone.  The default pair read a relative L2 of 5.1e-6 to
# 9.8e-6 per round on a v5e at client lr 1e-3 and 1e-2; DEFAULT_PAIR_TOL
# leaves twice the largest reading.
STRICT_BF16 = {"xla_allow_excess_precision": False}
DEFAULT_PAIR_TOL = 2e-5
VARIANTS = (("fused", True, False), ("unfused", False, False),
            ("fused_strict", True, True), ("unfused_strict", False, True))
MESH_PARAM_TOL, MESH_LOSS_TOL = 3e-2, 5e-3   # tests/test_mesh_small.py xlstm

_compile_s = 0.0


def _on_duration(event, secs, **_):
    global _compile_s
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s += secs


def check(ok, msg):
    if not ok:
        raise RuntimeError(msg)


def run_phase(name, fn):
    c0, t0 = _compile_s, time.perf_counter()
    rec = fn()
    rec = {"phase": name, "wall_s": time.perf_counter() - t0,
           "compile_s": _compile_s - c0,
           "peak_bytes_in_use": [d.memory_stats()["peak_bytes_in_use"]
                                 for d in jax.local_devices()], **rec}
    print(json.dumps(rec), flush=True)


def tree_rel_err(a, b):
    """||a - b|| / ||b|| over the whole tree, in float32."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
        num += float(jnp.sum(jnp.square(x - y)))
        den += float(jnp.sum(jnp.square(y)))
    return math.sqrt(num / den)


def tree_max_abs_err(a, b):
    return max(float(np.abs(np.asarray(x, np.float32)
                            - np.asarray(y, np.float32)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------------------------ phase A

def phase_a_async(seed):
    argv = ["--dataset", "cifar10", "--mode", "async", "--secure-agg",
            "--quantize-bits", "8", "--no-stochastic-rounding",
            "--rounds", "3", "--clients-pool", "8", "--buffer-k", "4",
            "--max-concurrency", "4", "--local-steps", "2",
            "--batch-size", "16", "--seed", str(seed)]
    fused = train_main(argv)
    unfused = train_main(argv + ["--no-use-fused"])
    check(fused["commits"] == 3, f"expected 3 commits: {fused}")
    rec = {}
    # the CNN is float32 end to end and the secure kernel computes its
    # oracle's integer-domain commit, so the two jobs agree bit for bit
    for key in ("final_eval", "final_loss"):
        rec[key], rec[key + "_unfused"] = fused[key], unfused[key]
        check(math.isfinite(fused[key]), f"{key}: {fused}")
        check(fused[key] == unfused[key], f"fused/unfused {key}: {rec}")
    return rec


def phase_a_charlm(seed):
    out = train_main(["--dataset", "shakespeare", "--mode", "sync",
                      "--rounds", "2", "--clients-pool", "8",
                      "--clients-per-round", "4", "--local-steps", "2",
                      "--batch-size", "16", "--seed", str(seed)])
    check(out["final_loss"] is not None and math.isfinite(out["final_loss"]),
          f"shakespeare loss not finite: {out}")
    return {"final_loss": out["final_loss"], "rounds": out["rounds"]}


# ------------------------------------------------------- phases B and C

def xlstm_model(seed):
    cfg = get_config("xlstm-125m")
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(seed))


def non_iid_batches(cfg, seed, rnd, b, s):
    """[C, H, b, s] next-token batches; client c draws its tokens from its
    own half of the vocabulary (examples/federated_llm_finetune.py)."""
    ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), rnd),
                          C)
    toks = []
    for c in range(C):
        lo = (c * cfg.vocab) // (2 * C)
        toks.append(jax.random.randint(ks[c], (H, b, s + 1), lo,
                                       lo + cfg.vocab // 2, jnp.int32))
    t = jnp.stack(toks)
    return {"tokens": t[..., :-1], "targets": t[..., 1:]}


def fl_config(client_exec, use_fused):
    return FLConfig(num_clients=C, local_steps=H, client_lr=CLIENT_LR,
                    fedprox_mu=0.01, client_exec=client_exec,
                    secure_agg=True,
                    compression=CompressionConfig(
                        quantize_bits=8, stochastic_rounding=False,
                        use_fused=use_fused))


def round_step(m, fl, **kw):
    return build_fl_round_step(m.loss_fn, get_client_optimizer("sgd"),
                               get_server_optimizer("fedavg"), fl, **kw)


def compile_variants(make_step, args, variants=VARIANTS):
    """Compile one round program per (tag, use_fused, strict) variant;
    returns the compiled programs and their compile seconds."""
    steps, secs = {}, {}
    for tag, use_fused, strict in variants:
        t0 = time.perf_counter()
        steps[tag] = make_step(use_fused).lower(*args).compile(
            compiler_options=STRICT_BF16 if strict else None)
        secs[f"compile_s_{tag}"] = time.perf_counter() - t0
    return steps, secs


def phase_b(seed):
    cfg, m, params0 = xlstm_model(seed)
    weights, mask = jnp.ones((C,)), jnp.ones((C,))
    args = lambda p, r: (p, (), non_iid_batches(cfg, seed, r, B, S),
                         weights, mask, jax.random.PRNGKey(seed + r))
    steps, rec = compile_variants(
        lambda fused: jax.jit(round_step(m, fl_config("sequential", fused))),
        args(params0, 0))
    rec.update(batch=[C, H, B, S], client_lr=CLIENT_LR,
               tpu_custom_calls=steps["fused"].as_text().count(
                   "tpu_custom_call"))
    check(rec["tpu_custom_calls"] > 0,
          "no tpu_custom_call in the fused round program")
    # every round starts all programs from the same (fused) params, so a
    # difference is that round's own, not one carried over
    p = params0
    for r in range(2):
        outs = {tag: step(*args(p, r)) for tag, step in steps.items()}
        for tag, (_, _, metrics) in outs.items():
            rec.setdefault(f"losses_{tag}", []).append(
                float(metrics["client_loss"]))
        for pair in ("", "_strict"):
            rec.setdefault(f"rel_err_fused_unfused{pair}", []).append(
                tree_rel_err(outs["fused" + pair][0],
                             outs["unfused" + pair][0]))
        p = outs["fused"][0]
    losses = [l for tag, _, _ in VARIANTS for l in rec[f"losses_{tag}"]]
    check(all(map(math.isfinite, losses)), f"losses not finite: {rec}")
    check(max(rec["rel_err_fused_unfused_strict"]) == 0.0,
          f"strict fused/unfused params differ: {rec}")
    check(max(rec["rel_err_fused_unfused"]) <= DEFAULT_PAIR_TOL,
          f"fused/unfused params differ: {rec}")
    return rec


def phase_b_commit(seed):
    """One buffered secure 8-bit commit of K=C xlstm-125m-shaped bf16
    deltas (std 1e-3) with staleness discount: Pallas kernel vs oracle."""
    _, _, params0 = xlstm_model(seed)

    @jax.jit   # one program, not one small compile per leaf
    def draw(params, key):
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            1e-3 * jax.random.normal(k, (C,) + x.shape, x.dtype)
            for k, x in zip(keys, leaves)])

    deltas = draw(params0, jax.random.PRNGKey(seed + 1))
    args = (params0, (), deltas, jnp.ones((C,)),
            jnp.arange(C, dtype=jnp.float32), jnp.zeros((C,)),
            jnp.ones((C,)), jnp.arange(C, dtype=jnp.int32),
            jnp.float32(0.5), jax.random.PRNGKey(seed))
    steps, rec = compile_variants(
        lambda fused: jax.jit(build_buffer_commit_step(
            get_server_optimizer("fedavg"), fl_config("parallel", fused),
            AsyncConfig(buffer_size=C))),
        args, [v for v in VARIANTS if not v[2]])
    rec["tpu_custom_calls"] = steps["fused"].as_text().count(
        "tpu_custom_call")
    outs = {tag: step(*args) for tag, step in steps.items()}
    rec["delta_norm"] = float(outs["fused"][2]["delta_norm"])
    rec["delta_norm_unfused"] = float(outs["unfused"][2]["delta_norm"])
    rec["max_param_err_fused_unfused"] = tree_max_abs_err(
        outs["fused"][0], outs["unfused"][0])
    check(rec["tpu_custom_calls"] > 0,
          "no tpu_custom_call in the fused commit program")
    check(math.isfinite(rec["delta_norm"]) and rec["delta_norm"] > 0,
          f"commit delta: {rec}")
    check(rec["max_param_err_fused_unfused"] == 0.0,
          f"fused/unfused commit differ: {rec}")
    return rec


def phase_c(seed):
    """Parallel rounds vmap all C clients at once, so the single-device
    reference holds C clients' activations; MESH_B x MESH_S keeps it
    inside one chip's memory."""

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices: "
          f"{jax.devices()}")
    cfg, m, params0 = xlstm_model(seed)
    batches = non_iid_batches(cfg, seed, 0, MESH_B, MESH_S)
    weights, mask = jnp.ones((C,)), jnp.ones((C,))
    rng = jax.random.PRNGKey(seed)
    mesh = make_test_mesh(4)
    spmd = ("data",)
    with sh.use_mesh(mesh), mesh:
        param_sh = sp.sanitize_specs(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params0), m.logical_specs, mesh)
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, P(spmd, *(None,) * (x.ndim - 1))),
            batches)
        args = (jax.device_put(params0, param_sh), (),
                jax.device_put(batches, batch_sh), weights, mask, rng)
        steps, rec = compile_variants(
            lambda fused: jax.jit(
                round_step(m, fl_config("parallel", fused),
                           client_spmd_axes=spmd),
                in_shardings=(param_sh, None, batch_sh, None, None, None),
                out_shardings=(param_sh, None, None)), args,
            # four chips are dear: the default-precision unfused program
            # only repeats what phase B reports
            [v for v in VARIANTS if v[0] != "unfused"])
        outs = {tag: step(*args) for tag, step in steps.items()}
    rec.update(mesh=dict(mesh.shape), batch=[C, H, MESH_B, MESH_S],
               client_lr=CLIENT_LR,
               tpu_custom_calls=steps["fused"].as_text().count(
                   "tpu_custom_call"))
    p_fused = outs["fused"][0]
    rec["param_bytes_per_device"] = {
        str(d.id): sum(s.data.nbytes for leaf in jax.tree.leaves(p_fused)
                       for s in leaf.addressable_shards if s.device == d)
        for d in mesh.devices.flat}

    dev0 = jax.devices()[0]
    p_ref, _, metrics = jax.jit(round_step(m, fl_config("parallel", True)))(
        jax.device_put(params0, dev0), (),
        jax.device_put(jax.tree.map(np.asarray, batches), dev0), weights,
        mask, rng)
    rec["client_loss"] = float(outs["fused"][2]["client_loss"])
    rec["client_loss_single_device"] = float(metrics["client_loss"])
    rec["max_param_err_vs_single_device"] = tree_max_abs_err(p_fused, p_ref)
    rec["rel_err_fused_unfused_strict"] = tree_rel_err(
        outs["fused_strict"][0], outs["unfused_strict"][0])
    check(math.isfinite(rec["client_loss"]), f"loss: {rec}")
    check(rec["tpu_custom_calls"] > 0,
          "no tpu_custom_call in the fused mesh round")
    check(abs(rec["client_loss"] - rec["client_loss_single_device"])
          < MESH_LOSS_TOL, f"sharded vs single-device loss: {rec}")
    check(rec["max_param_err_vs_single_device"] < MESH_PARAM_TOL,
          f"sharded vs single-device params: {rec}")
    check(rec["rel_err_fused_unfused_strict"] == 0.0,
          f"fused/unfused under the mesh: {rec}")
    per_dev = rec["param_bytes_per_device"].values()
    check(0 < min(per_dev) and max(per_dev) < sum(
        leaf.nbytes for leaf in jax.tree.leaves(p_fused)),
          f"params not spread over the mesh: {rec['param_bytes_per_device']}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B; 4: the 2x2 mesh phase C only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    init_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (devices: {devices})")
    check(not kops._interpret(), "Pallas would run in interpret mode")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 4:
        run_phase("C.mesh_2x2_parallel_secure", lambda: phase_c(args.seed))
    else:
        run_phase("A.cli_async_secure_cifar10",
                  lambda: phase_a_async(args.seed))
        run_phase("A.cli_sync_shakespeare", lambda: phase_a_charlm(args.seed))
        run_phase("B.xlstm_125m_sequential_secure",
                  lambda: phase_b(args.seed))
        run_phase("B.xlstm_125m_buffer_commit_secure",
                  lambda: phase_b_commit(args.seed))
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
