"""Staleness-aware asynchronous (FedBuff-style) server aggregation.

DESIGN
------
The synchronous round step (repro.core.round) stacks C client batches,
trains every client against the SAME global params, and applies one
aggregate per round — a barrier: the round is as slow as its slowest
participant.  This module is the other half of the paper's heterogeneity
story: clients train against whatever params snapshot they were handed,
their deltas land in a bounded server buffer whenever they finish, and the
server commits an aggregate every K arrivals (or T seconds of quiet).  An
update that was computed ``s`` commits ago is *stale* — it is discounted,
not discarded, with the polynomial weight of FedBuff/FedAsync:

    w_eff[i] = effective_weights(weights, mask)[i] * 1 / (1 + s_i)^a

The committed delta is normalised by the UN-discounted weight mass
(``sum w_eff * d / sum w_raw``), so the discount shrinks the absolute
server step: a buffer in which every update is equally stale takes a
``1/(1+s)^a``-scaled step rather than a full one (the discount must not
cancel in the mean's denominator).

Split of responsibilities (mirrors round.py):
  * ``build_client_update_step``  — the jit'd per-client local-training
    step: ``(params_snapshot, batches[H, b, ...], rng) -> (delta, loss)``.
    Reuses ``build_local_train`` so FedProx / fused-kernel / sharding
    behaviour is identical to the sync path.
  * ``build_buffer_commit_step``  — the jit'd server step over a FIXED-K
    buffer: ``(params, server_state, deltas[K, ...], weights[K],
    staleness[K], losses[K], mask[K], ids[K], exponent, rng)
    -> (params', state', metrics)``.
    Timeout commits with fewer than K live updates pad with zero deltas
    and mask 0, so one compiled step serves every commit.  The whole
    compress -> weight/discount -> secure_mask -> aggregate -> normalise
    transform is the SAME ``repro.core.pipeline`` stage stack the three
    sync execution modes consume — there is no async-only aggregation
    math left here.  ``ids`` carries UNIQUE per-commit slot indices for
    commit-keyed pairwise masking under ``FLConfig.secure_agg`` (slot
    indices, not cids: a client with two updates in one buffer is two
    logical participants); ``exponent`` is the staleness discount's
    ``a``, a runtime scalar so the adaptive controller below can move it
    between commits without recompiling.
  * Event ordering, buffer policy, staleness bookkeeping and comm
    accounting are HOST-side — repro.orchestrator.async_server.

Equivalence invariant (tested): with staleness forced to zero, a full
mask, and compression off, one buffer commit over the C deltas of a sync
round reproduces the sync round step's new params to <= 1e-5 — async is a
strict generalisation, not a different algorithm.  The same holds with
``secure_agg`` on in both regimes (masks cancel within the commit).

Limits encoded here rather than left to callers:
  * ``max_staleness`` — updates older than this are dropped by the
    orchestrator (weight would be ~0 anyway; dropping keeps the buffer
    from carrying dead weight).
  * accumulation/aggregation happens in float32 regardless of param
    dtype, like the sync path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.pipeline import build_update_pipeline, staleness_weights  # noqa: F401  (re-export)
from repro.core.round import (FLConfig, build_local_train, global_norm,
                              server_step)
from repro.optim import Optimizer, ServerOptimizer


@dataclass(frozen=True)
class AsyncConfig:
    """Policy knobs of the buffered-asynchronous execution regime."""
    buffer_size: int = 8            # K: commit every K buffered updates
    staleness_exponent: Union[float, str] = 0.5  # a in 1/(1+s)^a (0 -> no
    #                                 discount), or "adaptive": FedAsync-style
    #                                 online alpha from the observed staleness
    #                                 distribution (AdaptiveStalenessController)
    max_staleness: int = 20         # drop updates staler than this
    commit_timeout_s: float = 0.0   # T: commit a partial buffer once its
    #                                 oldest update has waited T sim-seconds
    #                                 without a K-commit (0 = off)
    max_concurrency: int = 16       # clients training at once
    commit_chunk: int = 0           # C: accumulate the buffer in C-sized
    #                                 chunks (one device call per chunk, one
    #                                 normalise+apply at the end) instead of
    #                                 stacking all K at once.  0 = off (the
    #                                 single-shot commit).  Chunked ==
    #                                 single-shot in exact arithmetic; float
    #                                 summation order differs, so the
    #                                 agreement is ~1e-5, not bitwise.

    def __post_init__(self):
        if self.buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {self.buffer_size}")
        if self.max_concurrency < 1:
            raise ValueError(
                f"max_concurrency must be >= 1, got {self.max_concurrency}")
        if self.commit_chunk < 0:
            raise ValueError(
                f"commit_chunk must be >= 0 (0 = single-shot commit), got "
                f"{self.commit_chunk}")
        if isinstance(self.staleness_exponent, str):
            if self.staleness_exponent != "adaptive":
                raise ValueError(
                    f"staleness_exponent must be a non-negative float or "
                    f"'adaptive', got {self.staleness_exponent!r}")
        elif self.staleness_exponent < 0:
            raise ValueError("staleness_exponent must be non-negative")
        if self.max_staleness < 0 or self.commit_timeout_s < 0:
            raise ValueError("max_staleness and commit_timeout_s must be "
                             "non-negative")

    @property
    def adaptive_staleness(self) -> bool:
        return self.staleness_exponent == "adaptive"

    def initial_exponent(self) -> float:
        return (AdaptiveStalenessController().alpha
                if self.adaptive_staleness else float(self.staleness_exponent))


class AdaptiveStalenessController:
    """Online FedAsync-style staleness exponent (host-side, deterministic).

    Rule: pick ``a`` so the polynomial discount at the OBSERVED tail
    staleness (EMA of the per-commit p90) equals ``w_floor``:

        a = ln(1/w_floor) / ln(1 + s_p90)

    A fleet whose updates arrive barely stale gets a sharp exponent (stale
    stragglers are outliers — discount them hard); a fleet where high
    staleness is the NORM gets a gentle one, so slow sites keep
    contributing instead of being starved (FedAsync's adaptive-alpha
    motivation).  A delta-norm drift brake tightens the discount whenever
    the committed step norm drifts above its EMA (divergence pressure —
    stale gradients amplifying the server step).

    The controller is pure host-side state: ``alpha`` is fed to the jit'd
    commit step as a runtime scalar, and ``state()``/``set_state()`` make
    it checkpointable so kill/--resume replays identical exponents.
    """

    def __init__(self, w_floor: float = 0.1, alpha0: float = 0.5,
                 alpha_min: float = 0.05, alpha_max: float = 4.0,
                 ema: float = 0.8, drift_gain: float = 1.0):
        self.w_floor = w_floor
        self.alpha = alpha0
        self.alpha_min, self.alpha_max = alpha_min, alpha_max
        self.ema = ema
        self.drift_gain = drift_gain
        self._stale_p90 = 0.0
        self._norm_ema = None

    def update(self, staleness, delta_norm: float) -> float:
        """Feed one commit's observed staleness values + committed delta
        norm; returns the alpha for the NEXT commit."""
        if len(staleness):
            p90 = float(np.quantile(np.asarray(staleness, np.float64), 0.9))
            self._stale_p90 = (self.ema * self._stale_p90
                               + (1.0 - self.ema) * p90)
        if self._stale_p90 > 0:
            base = np.log(1.0 / self.w_floor) / np.log1p(self._stale_p90)
        else:
            base = self.alpha_max     # nothing is stale: discount is inert
        drift = 0.0
        if delta_norm == delta_norm:  # skip NaN (empty commits)
            if self._norm_ema is None:
                self._norm_ema = float(delta_norm)
            else:
                drift = max(0.0, (float(delta_norm) - self._norm_ema)
                            / (self._norm_ema + 1e-12))
                self._norm_ema = (self.ema * self._norm_ema
                                  + (1.0 - self.ema) * float(delta_norm))
        self.alpha = float(np.clip(base * (1.0 + self.drift_gain * drift),
                                   self.alpha_min, self.alpha_max))
        return self.alpha

    def state(self) -> dict:
        return {"alpha": self.alpha, "stale_p90": self._stale_p90,
                "norm_ema": self._norm_ema}

    def set_state(self, s: dict):
        self.alpha = float(s["alpha"])
        self._stale_p90 = float(s["stale_p90"])
        self._norm_ema = (None if s["norm_ema"] is None
                          else float(s["norm_ema"]))


def build_client_update_step(loss_fn: Callable, client_opt: Optimizer,
                             cfg: FLConfig, param_shardings=None):
    """jit-able ``(params_snapshot, batches[H, b, ...], rng) -> (delta, loss)``.

    Exactly the sync path's local training (same FedProx handling, same
    optimizer), run for ONE client against the params snapshot it was
    dispatched with."""
    return build_local_train(loss_fn, client_opt, cfg, param_shardings)


def build_buffer_commit_step(server_opt: ServerOptimizer, cfg: FLConfig,
                             async_cfg: AsyncConfig):
    """jit-able server commit over a fixed-size buffer of K client deltas.

    commit(params, server_state, deltas, weights, staleness, losses, mask,
           ids, exponent, rng) -> (new_params, new_server_state, metrics)

    ``deltas`` leaves are [K, ...]; ``weights``/``staleness``/``losses``/
    ``mask`` are [K]; ``ids`` [K] int32 unique slot indices keying the
    pairwise secure-agg masks; ``exponent`` is the staleness discount's
    ``a`` as a runtime scalar (constant or adaptive).  Padding slots carry
    mask 0 (their deltas — and their masks — never contribute).
    ``losses`` feeds the "weighted" aggregation mode exactly as in the
    sync round; "trimmed_mean" is rejected at build time — coordinate-wise
    trimming over a staleness-discounted partial buffer has no agreed
    semantics yet (ROADMAP open item), and is incompatible with masking
    anyway.
    """
    if cfg.aggregation == "trimmed_mean":
        raise ValueError(
            "aggregation='trimmed_mean' is not supported by the async "
            "buffered commit (robust trimming over a padded, "
            "staleness-weighted buffer is undefined); use fedavg/weighted "
            "or the sync round loop")
    pipe = build_update_pipeline(cfg)

    def commit(params, server_state, deltas, weights, staleness, losses,
               mask, ids, exponent, rng):
        delta, w_eff, _ = pipe.combine(
            deltas, weights, mask, losses, rng, ids=ids,
            staleness=staleness, exponent=exponent)
        new_params, new_state = server_step(server_opt, params, delta,
                                            server_state)
        metrics = {
            "delta_norm": global_norm(delta),
            "n_updates": mask.sum(),
            "mean_staleness": (staleness * mask).sum()
            / jnp.maximum(mask.sum(), 1),
            "effective_weight": w_eff.sum(),
        }
        return new_params, new_state, metrics

    return commit


def build_chunked_commit_steps(server_opt: ServerOptimizer, cfg: FLConfig,
                               async_cfg: AsyncConfig):
    """jit-able (accumulate, finalize) pair: the buffer commit split into
    C-sized chunks with ONE device call per chunk.

    ``accumulate(acc, wsum, deltas[C, ...], weights, staleness, losses,
    mask, ids, exponent, rng) -> (acc', wsum')`` folds one chunk's
    unnormalised weighted(-masked) sum into a float32 accumulator;
    ``finalize(params, server_state, acc, wsum)`` normalises by the total
    raw mass and applies the server optimizer — by the additivity of every
    pre-normalise pipeline stage this equals the single-shot
    ``build_buffer_commit_step`` over the concatenated slots in exact
    arithmetic (float summation order differs: ~1e-5 agreement, pinned by a
    property test).  Each chunk gets its own rng (the caller fold_ins the
    chunk index) and its own arange ids, so pairwise secure-agg masks
    cancel chunk-locally.  Padding slots carry mask 0 as in the single-shot
    step."""
    if cfg.aggregation == "trimmed_mean":
        raise ValueError(
            "aggregation='trimmed_mean' is not supported by the async "
            "buffered commit (robust trimming over a padded, "
            "staleness-weighted buffer is undefined); use fedavg/weighted "
            "or the sync round loop")
    pipe = build_update_pipeline(cfg)

    def accumulate(acc, wsum, deltas, weights, staleness, losses, mask, ids,
                   exponent, rng):
        summed, _, w_raw = pipe.combine_unnormalised(
            deltas, weights, mask, losses, rng, ids=ids,
            staleness=staleness, exponent=exponent)
        acc = jax.tree.map(lambda a, s: a + s.astype(a.dtype), acc, summed)
        return acc, wsum + w_raw.sum()

    def finalize(params, server_state, acc, wsum):
        delta = pipe.normalise(acc, wsum)
        new_params, new_state = server_step(server_opt, params, delta,
                                            server_state)
        return new_params, new_state, {"delta_norm": global_norm(delta)}

    return accumulate, finalize
