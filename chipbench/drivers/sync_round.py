"""Driver: synchronous federated rounds of a language model.

Set-up makes the weights from the seed, builds one ``Orchestrator`` (the
program's sync server, ``orchestrator/server.py``) over a token stream of
the benchmark's making, and runs its first ``check_rounds`` rounds through
``Orchestrator.run_round``: they compile and warm the round program, and
they are the rounds the reference follows.  The window then drives the same
orchestrator round after round until ``--seconds`` have passed; it ends at
the first round boundary after that.  A traced run then records
``trace_rounds`` more rounds.  Once the program's state is freed, the
reference replays the compared rounds from the seed.

Traffic parameters: clients, local_steps, batch, seq_len, client_lr,
fedprox_mu, secure_agg, quantize_bits, client_rows (data size per client,
the FedAvg weights), check_rounds, trace_rounds, ref_rows (rows per
gradient block in the reference).
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from chipbench import fl_reference as flr
from chipbench import lm, weights


def token_stream(run, model_cfg):
    """A FederatedDataset whose round batches are fresh token rows drawn from
    the seed: client c's k-th batch is ``weights.token_batch(seed, c, k)``
    over c's half of the vocabulary, so every row of every round differs."""
    from repro.data.federated import FederatedDataset
    from repro.data.synthetic import Dataset
    tr = run.traffic
    C, S, V = tr["clients"], tr["seq_len"], model_cfg.vocab

    class TokenStream(FederatedDataset):
        def sample_round(self, client_ids, local_steps, batch_size):
            rows = []
            for c in client_ids:
                c = int(c)
                lo, hi = weights.vocab_share(c, C, V)
                rows.append(weights.token_batch(
                    run.seed, c, self.calls[c], (local_steps, batch_size, S),
                    lo, hi))
                self.calls[c] += 1
            x = np.stack(rows)
            return {"tokens": x[..., :-1], "targets": x[..., 1:]}

    data = Dataset(name="tokens", x=np.zeros((0, S + 1), np.int32), y=None,
                   num_classes=V, kind="text")
    ds = TokenStream(data=data, client_indices=[
        np.arange(n) for n in tr["client_rows"]], seed=run.seed)
    ds.calls = [0] * C
    return ds


def reference_batches(run, model_cfg, rnd: int):
    tr = run.traffic
    C, H, b, S = tr["clients"], tr["local_steps"], tr["batch"], tr["seq_len"]
    out = []
    for c in range(C):
        lo, hi = weights.vocab_share(c, C, model_cfg.vocab)
        x = weights.token_batch(run.seed, c, rnd, (H, b, S), lo, hi)
        out.append([(x[h, :, :-1], x[h, :, 1:]) for h in range(H)])
    return out


def build(run, model: lm.Model):
    from repro.core import CompressionConfig, FLConfig
    from repro.orchestrator.registry import ClientInfo, ResourceProfile
    from repro.orchestrator.server import Orchestrator
    tr = run.traffic
    C = tr["clients"]
    fl = FLConfig(num_clients=C, local_steps=tr["local_steps"],
                  client_lr=tr["client_lr"], fedprox_mu=tr["fedprox_mu"],
                  client_exec="sequential", secure_agg=tr["secure_agg"],
                  compression=CompressionConfig(
                      quantize_bits=tr["quantize_bits"],
                      stochastic_rounding=False))
    # sites that always finish: no deadline, no faults, every client taken
    fleet = [ClientInfo(c, "hpc", ResourceProfile(
        compute_tflops=100.0, bandwidth_gbps=100.0, latency_ms=0.05,
        memory_gb=16.0, reliability=1.0)) for c in range(C)]
    orch = Orchestrator(fleet=fleet, fed_data=token_stream(run, model.cfg),
                        loss_fn=model.lm.loss_fn, fl=fl,
                        client_opt_name="sgd", server_opt_name="fedavg",
                        selection_name="random", batch_size=tr["batch"],
                        seed=run.seed)
    orch._round_step = run.wrap(orch._round_step)
    return orch


def tokens_per_round(tr) -> int:
    return tr["clients"] * tr["local_steps"] * tr["batch"] * tr["seq_len"]


def setup(run):
    """Weights, the orchestrator, and the compared rounds.  Returns the
    state the window continues from and the program's readings."""
    tr = run.traffic
    model = lm.Model(run.config)
    orch = build(run, model)
    params = model.params(run.seed)
    state = orch.init_server_state(params)
    prog = {"loss": []}
    for r in range(tr["check_rounds"]):
        p_prev = params
        with run.span("chipbench.run_round"):
            params, state, log = orch.run_round(r, params, state)
        if log.participated != tr["clients"]:
            raise RuntimeError(f"round {r}: {log.participated} of "
                               f"{tr['clients']} clients took part")
        prog["loss"].append(log.client_loss)
        if r == 0:
            prog["delta1"] = np.asarray(flr.leaf_change_norms(params, p_prev))
        del p_prev
    p0 = model.params(run.seed)
    prog["change"] = np.asarray(flr.leaf_change_norms(params, p0))
    del p0
    return {"model": model, "orch": orch, "params": params, "state": state,
            "round": tr["check_rounds"], "prog": prog}


def window(run, st) -> dict:
    """Rounds until ``--seconds`` have passed; tokens trained per second of
    the window, per chip."""
    orch, params, state = st["orch"], st["params"], st["state"]
    r = st["round"]
    t0 = run.start_window()
    rounds = failed = 0
    ends = [t0]
    while True:
        with run.span("chipbench.run_round"):
            params, state, log = orch.run_round(r, params, state)
        ends.append(time.perf_counter())
        r += 1
        rounds += 1
        failed += not math.isfinite(log.client_loss)
        if ends[-1] - t0 >= run.seconds:
            break
    window_s = ends[-1] - t0
    round_s = np.diff(ends)
    run.end_window()
    st.update(params=params, state=state, round=r)
    tokens = rounds * tokens_per_round(run.traffic)
    chips = run.cell.chips
    return {"attempted": rounds, "failed": failed,
            "end_to_end": {"tokens_per_s": tokens / window_s / chips},
            "layer": {"tokens": tokens, "window_s": window_s, "chips": chips,
                      "rounds": rounds, "round_s_min": float(round_s.min()),
                      "round_s_max": float(round_s.max())}}


def traced(run, st):
    orch, params, state = st["orch"], st["params"], st["state"]
    r = st["round"]
    with run.traced():
        for _ in range(run.traffic["trace_rounds"]):
            with run.span("chipbench.run_round"):
                params, state, _ = orch.run_round(r, params, state)
            r += 1
    st.update(params=params, state=state, round=r)
    return {"traced_rounds": run.traffic["trace_rounds"]}


def free(st):
    for k in ("orch", "params", "state"):
        st.pop(k, None)
    gc.collect()


def reference(run, st, precision: dict | None = None) -> dict:
    """The compared rounds replayed by the plain reference from the seed,
    its matrix products in float32 unless ``precision`` gives
    ``fl_reference.matmul`` other arguments."""
    tr = run.traffic
    model = st["model"]
    step = flr.make_client_step(model.ref, run.config["model"],
                                flr.matmul(**(precision or {})),
                                tr["ref_rows"],
                                tr["client_lr"], tr["fedprox_mu"])
    p0 = params = model.params(run.seed)
    out = {"loss": []}
    for r in range(tr["check_rounds"]):
        new, loss = flr.sync_round(step, params,
                                   reference_batches(run, model.cfg, r),
                                   tr["client_rows"], tr["quantize_bits"])
        out["loss"].append(loss)
        if r == 0:
            out["delta1"] = np.asarray(flr.leaf_change_norms(new, params))
        params = new
    out["change"] = np.asarray(flr.leaf_change_norms(params, p0))
    return out


def compare(run, st, ref: dict) -> dict:
    return lm.step_checks(run, st["model"].leaf_names(), st["prog"], ref,
                          "loss")


def control(run, st) -> dict:
    """The reference put in the program's place one precision below the
    configuration's: matrix products on float8 operands."""
    return reference(run, st, precision={"operands": "float8"})


def _unchanged(step):
    def f(params, state, *args):
        _, _, metrics = step(params, state, *args)
        return params, state, metrics
    return f


def _half_batch(step):
    def f(params, state, batches, *args):
        half = jax.tree.map(lambda x: x[:, :, :x.shape[2] // 2], batches)
        return step(params, state, half, *args)
    return f


def _altered(step):
    def f(params, state, *args):
        new, state, metrics = step(params, state, *args)
        new = jax.tree.map(lambda p, n: (p + 1.5 * (n - p)).astype(p.dtype),
                           params, new)
        return new, state, metrics
    return f


# faults planted in the timed step, each of which ``correct`` has to catch:
# the state returned unchanged, half of every client's rows left out (the
# mean taken over the rest), and the round's answer, its update, off by
# half where the round produces it (a smaller error rounds away in bf16)
FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


def main(run) -> dict:
    st = setup(run)
    out = window(run, st)
    if run.trace:
        out["layer"].update(traced(run, st))
    run.read_memory_peak(jax.local_devices()[:run.cell.chips])
    model = st["model"]
    out["layer"].update(
        flops_per_token=model.ref.fwd_flops_per_token(
            run.config["model"], run.traffic["seq_len"])["per_token"],
        kernel_bytes=quantize_kernel_bytes(model, run.traffic))
    free(st)
    compare(run, st, reference(run, st))
    return out


def quantize_kernel_bytes(model: lm.Model, tr) -> dict:
    """Least bytes the per-slot quantize kernel moves in one round: every
    leaf's delta as float32 blocks of 256 (rows padded to a multiple of 8),
    read once and written once, for each client."""
    total = 0
    for s in model.layout_shapes():
        rows = math.prod(s[:-1]) * -(-s[-1] // 256)
        rows += (-rows) % 8
        total += 2 * rows * 256 * 4
    return {"per_round": total * tr["clients"], "calls_per_round":
            len(model.layout_shapes()) * tr["clients"]}
