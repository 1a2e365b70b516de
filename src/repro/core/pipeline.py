"""Composable update pipeline: ONE stage stack for every execution regime.

Before this module, the compress -> weight -> aggregate transform was
re-implemented four times (round.py parallel / sequential /
pod_sequential, async_round.py buffered commit), so every cross-cutting
feature — compression tweaks, secure aggregation, staleness discounting
— had to be patched in four places.  ``build_update_pipeline(cfg)``
builds the stack once from ``FLConfig`` and all four regimes close over
it.

Stage contract
--------------
Stages are pure, jit-compatible functions over update pytrees plus
per-slot scalars.  A "slot" is one client update in a batch of K (a sync
cohort or an async commit buffer).  The canonical order is

    compress -> weight/discount -> secure_mask -> aggregate -> normalise

  * ``compress(tree, rng)``            straight-through compression of
    what crosses the wire (repro.core.compression); per-slot rngs come
    from ``jax.random.split(rng, K)`` so batched and streaming callers
    draw identical randomness.
  * ``client_weights(...) -> (w_eff, w_raw)``  combines data-size
    weights, the participation mask, losses (aggregation='weighted') and
    — async only — the staleness discount ``1/(1+s)^a``.  ``w_raw`` is
    the UN-discounted mass; dividing by it (not by ``w_eff``) is what
    makes a uniformly stale buffer take a proportionally smaller server
    step (FedBuff) instead of having the discount cancel in the mean.
  * ``secure_mask``                    adds commit-keyed pairwise masks
    (core.secure_agg) to the PRE-WEIGHTED slot updates.  Masking must
    follow weighting: the server sums ``w_i * d_i + m_i`` and the
    ``m_i`` cancel only if they are not scaled per-slot afterwards.
    (The ISSUE's "compress -> secure_mask -> weight" stage list names
    the stages; the algebra fixes this order.)
  * ``aggregate``                      weighted sum over the slot dim
    (or a plain sum of pre-weighted masked slots).
  * ``normalise``                      divide by the raw weight mass.

Execution-mode mapping:
  * parallel / async commit — ``combine`` consumes the full [K, ...]
    stack (trimmed-mean and hierarchical pod variants included).
  * sequential — the scan builds per-slot contributions with
    ``contribution`` and folds them with ``accum_add``; ``normalise``
    closes the stream.  Identical math, streaming memory.
  * pod_sequential / hierarchical — per-pod partial sums are compressed
    (``compress``) and combined across pods with ``combine_pods``.

Commit-keyed masking scheme (cfg.secure_agg)
--------------------------------------------
Masks are ``PRF(commit_key, min(id_i, id_j), max(id_i, id_j))`` with
sign ``sgn(id_j - id_i)`` on slot i's side — symmetric in the pair, so
they cancel in the sum.  The commit key is derived (``fold_in``) from
the per-commit rng, which is unique per commit and checkpointed, so
kill/resume reproduces the exact masks.  Participant ids are UNIQUE
per-commit slot indices (arange over the cohort/buffer/pods — a fast
client landing two buffered updates in one async commit occupies two
slots, i.e. two logical participants; duplicate ids would make a pair
key collide and its mask survive the sum uncancelled).  Slots padded
out by timeout commits, dropped clients, or ``max_staleness`` drops
carry participation 0: every pair mask touching them is zeroed — the
functional stand-in for the protocol's seed-reveal unwinding.  The
server therefore only ever sees masked per-slot updates whose masks
cancel within each commit; masked-vs-plain aggregates agree to float32
cancellation error (<= 1e-5, pinned in tests/test_secure_pipeline.py).

Fused commit path (compression.use_fused, default on)
-----------------------------------------------------
Every stage between compress and normalise is elementwise or a slot
reduction — pure HBM bandwidth — so the batched combinators fuse the
whole ``compress -> weight/discount -> (mask) -> aggregate`` stack into
single-pass Pallas kernels (kernels/fused_quant_mask, kernels/
fused_accum): each slot leaf is read once and the reduced leaf written
once, instead of a full [K, ...] intermediate materialized per stage.
Which boundaries fuse:

  * plain commits, deterministic quantize and/or top-k
    -> one kernel (top-k + per-slot-block quantize + discounted sum).
  * plain commits, no compression -> the fused accumulate kernel
    (discount computed in-kernel from raw weights + staleness).
  * secure commits WITH quantization -> the integer-domain kernel; see
    below.  Secure WITHOUT quantization keeps the float-domain masks.
  * stochastic rounding / federated dropout need per-slot randomness, so
    those stages stay unfused (per-slot jnp or per-slot Pallas compress)
    and only the accumulate fuses.
  * streaming (sequential scan) and pod-local compress stages route
    per-slot work through the Pallas compress kernels
    (``use_kernels``) — there is no slot batch to fuse across.

Fusion is mesh-native: under an active GSPMD mesh the kernels/ops entry
points wrap every Pallas call in shard_map over the mesh's multi-device
axes (row-sharding the blocked commit stack; see kernels/ops.py), so
``UpdatePipeline.fused`` stays on when a mesh is active.  The fused
combinators also BUCKET the tree: all leaves of the slot-stacked update
tree are concatenated into one blocked [K, rows, block] bucket per
commit, so a 100+-leaf model costs O(1) kernel launches instead of one
per leaf shape (kops.fused_*_tree).  ``allow_fused=False`` remains the
explicit caller escape hatch, and stochastic rounding still routes to
the bit-identical jnp oracle.

Why masking moves to the integer domain under quantization: float-domain
pairwise masks are dense f32 noise, so a masked wire slot costs 4
bytes/element no matter how hard the plain payload was compressed
(the historical ~3.9x blowup in table_secure_agg.json).  Standard SecAgg
instead masks the quantized WIRE words with modular arithmetic in a
finite ring.  When ``secure_agg`` and ``quantize_bits`` are both set the
commit therefore (1) quantizes every slot's weighted values onto ONE
commit-common per-block grid (masks can only cancel if all slots share a
grid), (2) adds uint32 modular pairwise mask words to the int32 wire
words, and (3) sums — the masks cancel EXACTLY (integer wraparound, no
float cancellation error) and the sum dequantizes through the common
scale.  The wire then ships ring words of
``quantize_bits + ceil(log2(K))`` bits (secure_agg.masked_payload_bytes)
instead of dense f32.  This is a SCHEME property, engaged whether or not
the Pallas kernel runs: ``use_fused`` only picks the executor (kernel vs
the bit-identical jnp oracle in kernels/ref.py), so fused and unfused
paths agree and kill/resume replay is executor-independent.  The
streaming (sequential) and cross-pod secure paths keep float-domain
masks: a scan sees one slot at a time and pods quantize on per-pod
grids, so neither can share a commit-common grid.

Build-time rejections: ``secure_agg`` + ``trimmed_mean`` (coordinate
-wise trimming needs individual updates, which masking is designed to
hide).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg
from repro.core import secure_agg as sec
from repro.core.compression import compress_tree
from repro.core.secure_agg import MASK_DOMAIN_TAG
from repro.kernels import ops as kops

if TYPE_CHECKING:                       # avoid circular import with round.py
    from repro.core.round import FLConfig


def staleness_weights(staleness, exponent):
    """The FedBuff polynomial discount ``1 / (1 + s)^a``.

    ``staleness`` counts server commits between a client's dispatch and
    its update's arrival; works on jnp or np arrays (used as its own
    NumPy reference in tests).  ``exponent`` may be a traced scalar —
    the adaptive-alpha path feeds the controller's current value per
    commit."""
    return (1.0 + staleness) ** (-exponent)


def _commit_stage(fn):
    """Trace a stage under the ``fl.commit`` device scope, so that every
    operation of the commit carries it in its ``op_name``."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("fl.commit"):
            return fn(*args, **kwargs)
    return scoped


class UpdatePipeline:
    """The configured stage stack.  Stateless; every method is pure and
    jit-compatible, so one instance serves vmapped, scanned and batched
    callers alike."""

    def __init__(self, cfg: "FLConfig", n_pods: int = 1,
                 allow_fused: bool = True):
        if cfg.secure_agg and cfg.aggregation == "trimmed_mean":
            raise ValueError(
                "secure_agg is incompatible with aggregation='trimmed_mean': "
                "coordinate-wise trimming needs the individual updates that "
                "pairwise masking hides; use fedavg/weighted")
        comp = cfg.compression
        # Fusion survives an active mesh: kernels/ops wraps each Pallas call
        # in shard_map over the mesh (rows of the blocked commit stack are
        # sharded, the slot sum is shard-local), so the only off-switches
        # left are the config knob and the caller's explicit escape hatch.
        self.fused = (bool(getattr(comp, "use_fused", True))
                      and allow_fused)
        # fully-fusable compression: deterministic rounding, no per-slot
        # dropout randomness
        self._fusable_comp = (not comp.dropout_frac
                              and not (comp.quantize_bits
                                       and comp.stochastic_rounding))
        if self.fused and comp.enabled and not comp.use_kernels:
            # per-slot compress stages (sequential scan, pod-local compress)
            # route through the Pallas compress kernels under fusion
            cfg = dataclasses.replace(
                cfg, compression=dataclasses.replace(comp, use_kernels=True))
        self.cfg = cfg
        self.n_pods = n_pods

    # ------------------------------------------------------------- stage 1
    @_commit_stage
    def compress(self, tree, rng):
        return compress_tree(tree, self.cfg.compression, rng)

    def compress_each(self, stacked, rng):
        """vmap the compress stage over the leading slot dim."""
        K = jax.tree.leaves(stacked)[0].shape[0]
        rngs = jax.random.split(rng, K)
        return jax.vmap(self.compress)(stacked, rngs)

    # ------------------------------------------------------------- stage 2
    def client_weights(self, weights, mask, losses=None, staleness=None,
                      exponent=None):
        """(w_eff, w_raw): discounted and raw per-slot weight vectors."""
        w_raw = agg.effective_weights(weights, mask, losses,
                                      self.cfg.aggregation)
        if staleness is None:
            return w_raw, w_raw
        w_eff = w_raw * staleness_weights(staleness.astype(jnp.float32),
                                          exponent)
        return w_eff, w_raw

    def client_weight(self, w_c, m_c, loss_c):
        """Scalar form for streaming (scan) callers."""
        return agg.effective_weights(w_c[None], m_c[None], loss_c[None],
                                     self.cfg.aggregation)[0]

    # ------------------------------------------------------------- stage 3
    def mask_key(self, rng):
        """Commit key for this aggregation's pairwise masks.  rng is the
        per-commit step rng — unique per commit and checkpointed, so it
        stands in for fold_in(base, commit_id) with identical algebra."""
        return jax.random.fold_in(rng, MASK_DOMAIN_TAG)

    def secure_mask(self, weighted_stack, key, ids, participation):
        return sec.mask_batch(weighted_stack, key, ids, participation)

    # --------------------------------------------------------- stages 4/5
    def weighted_sum(self, stacked, w):
        """sum_i w_i * d_i over the slot dim, in float32."""
        def one(d):
            wb = w.reshape((-1,) + (1,) * (d.ndim - 1)).astype(jnp.float32)
            return (d.astype(jnp.float32) * wb).sum(0)
        return jax.tree.map(one, stacked)

    @_commit_stage
    def normalise(self, summed, w_raw_sum):
        denom = jnp.maximum(w_raw_sum, 1e-12)
        return jax.tree.map(lambda s: (s / denom.astype(s.dtype)), summed)

    # ----------------------------------------------------------- streaming
    def accum_init(self, params_like):
        dt = jnp.dtype(self.cfg.accum_dtype)
        return jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params_like)

    @_commit_stage
    def contribution(self, delta, wt, rng, idx=None, ids=None,
                     participation=None, key=None):
        """One slot's contribution to the running sum: compress ->
        weight -> (secure-mask).  The masked value is what "crosses the
        wire" to the server accumulator; masks cancel once every
        participant's contribution has been folded in.  The weighting
        product is carried in ``accum_dtype`` so streaming accumulation
        keeps the precision that knob asks for."""
        dt = jnp.dtype(self.cfg.accum_dtype)
        d = self.compress(delta, rng)
        pre = jax.tree.map(lambda x: wt.astype(dt) * x.astype(dt), d)
        if self.cfg.secure_agg:
            pre = sec.mask_slot(key, ids, participation, idx, pre)
        return pre

    @_commit_stage
    def accum_add(self, acc, contrib):
        return jax.tree.map(lambda a, c: a + c.astype(a.dtype), acc, contrib)

    # --------------------------------------------------------- combinators
    @_commit_stage
    def combine_unnormalised(self, deltas, weights, mask, losses, rng,
                             ids=None, staleness=None, exponent=None):
        """compress -> weight/discount -> (secure_mask) -> weighted sum,
        WITHOUT the closing normalise.  Returns (summed, w_eff, w_raw).

        Every stage up to normalise is slot-local or additive, so a commit
        over K slots equals the sum of this over any partition of the slots
        into chunks, normalised once by the total raw mass — the algebra the
        chunked async commit (AsyncConfig.commit_chunk) accumulates on.
        Each chunk must carry its own rng (fold_in per chunk): masks then
        cancel within each chunk independently, and per-slot compression
        randomness stays unique."""
        if self.cfg.aggregation == "trimmed_mean":
            raise ValueError(
                "trimmed_mean is not a chunk-accumulable aggregate: "
                "coordinate-wise trimming needs all slots at once")
        w_eff, w_raw = self.client_weights(weights, mask, losses,
                                           staleness, exponent)
        comp = self.cfg.compression
        if self.cfg.secure_agg:
            if ids is None:
                ids = jnp.arange(mask.shape[0], dtype=jnp.int32)
            if comp.quantize_bits:
                summed = self._fused_secure(deltas, w_eff, mask, rng, ids)
            else:
                stacked = self.compress_each(deltas, rng) \
                    if comp.enabled else deltas
                pre = jax.tree.map(
                    lambda d: d.astype(jnp.float32) * w_eff.reshape(
                        (-1,) + (1,) * (d.ndim - 1)), stacked)
                masked = self.secure_mask(pre, self.mask_key(rng), ids, mask)
                summed = jax.tree.map(lambda m: m.astype(jnp.float32).sum(0),
                                      masked)
        elif self.fused:
            s = (staleness.astype(jnp.float32) if staleness is not None
                 else jnp.zeros_like(w_raw))
            a = exponent if exponent is not None else 0.0
            if comp.enabled and self._fusable_comp:
                # one-pass: top-k + quantize + discount + sum, all leaves
                # bucketed into a single kernel launch
                leaves, treedef = jax.tree.flatten(deltas)
                summed = jax.tree.unflatten(
                    treedef, kops.fused_plain_commit_tree(
                        leaves, w_raw, s, a, bits=comp.quantize_bits,
                        k=comp.topk_k, block=comp.block))
            else:
                # per-slot stages that need slot randomness stay unfused;
                # the accumulate still fuses (one bucketed launch)
                stacked = (self.compress_each(deltas, rng)
                           if comp.enabled else deltas)
                leaves, treedef = jax.tree.flatten(stacked)
                summed = jax.tree.unflatten(
                    treedef, kops.fused_accum_tree(leaves, w_raw, s, a,
                                                   block=comp.block))
        else:
            stacked = self.compress_each(deltas, rng) \
                if comp.enabled else deltas
            summed = self.weighted_sum(stacked, w_eff)
        return summed, w_eff, w_raw

    def _fused_secure(self, deltas, w_eff, participation, rng, ids):
        """Integer-domain SecAgg commit (secure_agg + quantize_bits):
        weighted slot values quantize onto a commit-common per-block grid,
        int32 wire words pick up uint32 modular pairwise masks, masks
        cancel EXACTLY in the sum.  The scheme runs whether or not fusion
        is active — ``self.fused`` only picks the Pallas kernel over the
        bit-identical jnp oracle — so wire accounting, checkpoint replay
        and fused-vs-unfused parity are executor-independent."""
        comp = self.cfg.compression
        key = self.mask_key(rng)
        seeds = sec.pair_seeds(key, ids)
        coef = sec.pair_coef_int(ids, participation)
        stacked, k_in = deltas, comp.topk_k
        if comp.dropout_frac:
            # dropout draws per-slot randomness and must precede top-k, so
            # both run as per-slot pre-stages (quantize stays in the
            # integer-domain masked commit)
            pre = dataclasses.replace(comp, quantize_bits=0)
            rngs = jax.random.split(rng, ids.shape[0])
            stacked = jax.vmap(
                lambda t, r: compress_tree(t, pre, r))(stacked, rngs)
            k_in = 0
        leaves, treedef = jax.tree.flatten(stacked)
        # one bucketed launch for the whole tree; the bucket's row-major
        # element index reproduces the old per-leaf base accumulation, so
        # the mask stream is bitwise-unchanged
        nr = rng if comp.stochastic_rounding else None
        out = kops.fused_secure_commit_tree(
            leaves, w_eff, seeds, coef, bits=comp.quantize_bits, k=k_in,
            block=comp.block, use_pallas=self.fused, noise_rng=nr)
        return jax.tree.unflatten(treedef, out)

    @_commit_stage
    def combine(self, deltas, weights, mask, losses, rng, ids=None,
                staleness=None, exponent=None):
        """The full batched stack over [K, ...] slot deltas.

        Returns (delta, w_eff, w_raw).  Serves the parallel sync mode
        (staleness=None) and the async buffered commit (staleness +
        exponent set); handles the trimmed-mean and hierarchical pod
        variants so no execution mode re-implements them."""
        if self.cfg.aggregation == "trimmed_mean":
            # robust trimming consumes RAW per-slot deltas (no compression,
            # no masking — rejected at build time): same as the historic
            # inline path
            w_eff, w_raw = self.client_weights(weights, mask, losses,
                                               staleness, exponent)
            return agg.trimmed_mean(deltas, mask), w_eff, w_raw
        if self.cfg.hierarchical and self.n_pods > 1:
            w_eff, w_raw = self.client_weights(weights, mask, losses,
                                               staleness, exponent)
            delta = self._combine_hierarchical(deltas, w_eff, w_raw, rng)
            return delta, w_eff, w_raw
        summed, w_eff, w_raw = self.combine_unnormalised(
            deltas, weights, mask, losses, rng, ids=ids,
            staleness=staleness, exponent=exponent)
        return self.normalise(summed, w_raw.sum()), w_eff, w_raw

    def _combine_hierarchical(self, deltas, w_eff, w_raw, rng):
        """Pod-local weighted sums -> compress -> cross-pod combine: only
        the compressed pod sums cross the slow cross-pod link."""
        P = self.n_pods
        K = w_eff.shape[0]
        per_pod = K // P

        def pod_sums(d):
            wb = w_eff.reshape(P, per_pod)
            dp = d.reshape((P, per_pod) + d.shape[1:])
            return (dp * wb.reshape(wb.shape + (1,) * (d.ndim - 1)
                                    ).astype(d.dtype)).sum(1)

        sums = jax.tree.map(pod_sums, deltas)          # [P, ...] un-normalised
        return self.combine_pods(sums, w_raw.sum(), rng)

    @_commit_stage
    def combine_pods(self, pod_sums, w_total, rng, compressed=False):
        """Cross-pod tail of the stack: compress each pod's partial sum,
        secure-mask BETWEEN PODS (privacy at site granularity — each
        pod's aggregate is hidden from the others and the server), sum,
        normalise by the total raw weight mass.

        ``compressed=True`` when the caller already ran the compress
        stage per pod — pod_sequential compresses INSIDE its
        spmd-annotated pod vmap so the quantize/top-k work stays
        pod-local under GSPMD instead of all-gathering each pod's
        partial sum (see build_fl_round_step's client_spmd_axes note)."""
        P = jax.tree.leaves(pod_sums)[0].shape[0]
        sums = pod_sums if compressed else self.compress_each(pod_sums, rng)
        if self.cfg.secure_agg:
            # cross-pod masking stays float-domain even under quantization:
            # pod partial sums were quantized on per-pod grids, so there is
            # no common grid for integer masks to cancel on (and P is tiny
            # — the dense-mask bytes here are not the wire bottleneck)
            ones = jnp.ones((P,), jnp.float32)
            sums = self.secure_mask(sums, self.mask_key(rng),
                                    jnp.arange(P, dtype=jnp.int32), ones)
            summed = jax.tree.map(lambda s: s.astype(jnp.float32).sum(0),
                                  sums)
        elif self.fused:
            ones = jnp.ones((P,), jnp.float32)
            zeros = jnp.zeros((P,), jnp.float32)
            leaves, treedef = jax.tree.flatten(sums)
            summed = jax.tree.unflatten(
                treedef, kops.fused_accum_tree(
                    leaves, ones, zeros, 0.0,
                    block=self.cfg.compression.block))
        else:
            summed = jax.tree.map(lambda s: s.astype(jnp.float32).sum(0),
                                  sums)
        return self.normalise(summed, w_total)


def build_update_pipeline(cfg: "FLConfig", n_pods: int = 1,
                          allow_fused: bool = True) -> UpdatePipeline:
    """Build the stage stack once from FLConfig; all execution modes of
    round.py and async_round.py close over the returned pipeline.
    ``allow_fused=False`` forces the unfused stages — the explicit caller
    escape hatch.  An active mesh no longer disables fusion: the kernel
    entry points shard_map themselves over it (kernels/ops.py)."""
    return UpdatePipeline(cfg, n_pods=n_pods, allow_fused=allow_fused)
