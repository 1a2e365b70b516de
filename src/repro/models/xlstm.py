"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, chunkwise-parallel)
and sLSTM (scalar memory, true recurrence via lax.scan).

mLSTM uses the chunkwise-parallel form of gated linear attention: within a
chunk the quadratic (decay-weighted) attention is computed directly, across
chunks a matrix state (C [hd, hd], normaliser n [hd], stabiliser m) is
carried — O(S·chunk) instead of O(S^2), recurrent O(1) decode.

sLSTM has hidden-to-gate feedback so it cannot be parallelised over time;
we scan.  Exponential gating is stabilised with the max-state m as in the
paper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import XLSTMConfig
from repro.models import sharding as sh


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def init_mlstm(builder, path, d_model: int, n_heads: int, cfg: XLSTMConfig,
               n_groups: int):
    du = int(cfg.proj_factor * d_model)
    g = (n_groups,) if n_groups else ()
    pre = (None,) if n_groups else ()
    add = builder.add
    add({}, path + ["up"], g + (d_model, 2 * du), pre + (sh.DATA, sh.MODEL))
    add({}, path + ["wq"], g + (du, du), pre + (sh.MODEL, None))
    add({}, path + ["wk"], g + (du, du), pre + (sh.MODEL, None))
    add({}, path + ["wv"], g + (du, du), pre + (sh.MODEL, None))
    add({}, path + ["wi"], g + (du, n_heads), pre + (sh.MODEL, None))
    add({}, path + ["wf"], g + (du, n_heads), pre + (sh.MODEL, None))
    add({}, path + ["bi"], g + (n_heads,), pre + (None,), init="zeros")
    add({}, path + ["bf"], g + (n_heads,), pre + (None,),
        init=lambda k, s: jnp.full(s, 3.0))  # forget-gate bias -> remember
    add({}, path + ["down"], g + (du, d_model), pre + (sh.MODEL, sh.DATA))


def _mlstm_chunk(q, k, v, li, lf, C0, n0, m0):
    """One chunk of chunkwise-parallel mLSTM.
    q,k,v [B,H,L,hd]; li,lf log gates [B,H,L]; states C0 [B,H,hd,hd],
    n0 [B,H,hd], m0 [B,H].  Returns y [B,H,L,hd] + new states (f32)."""
    B, H, L, hd = q.shape
    f_cum = jnp.cumsum(lf, axis=-1)                       # log prod f_1..t
    # decay from chunk start to t (inclusive), and total chunk decay
    g_t = f_cum                                            # [B,H,L]
    g_all = f_cum[..., -1]
    # intra-chunk log decay matrix D[t,s] = sum_{u=s+1..t} lf_u + li_s  (s<=t)
    D = g_t[..., :, None] - g_t[..., None, :] + li[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    D = jnp.where(mask, D, -jnp.inf)
    # inter-chunk term decay: a_t = g_t + m0
    inter = g_t + m0[..., None]
    m_new = jnp.maximum(D.max(-1), inter)                 # [B,H,L] running stabiliser
    Dn = jnp.exp(D - m_new[..., None])                    # [B,H,L,L]
    an = jnp.exp(inter - m_new)                           # [B,H,L]
    scale = hd ** -0.5
    s = jnp.einsum("bhld,bhsd->bhls", q, k) * scale       # [B,H,L,L]
    num = jnp.einsum("bhls,bhsd->bhld", s * Dn, v) \
        + jnp.einsum("bhld,bhde->bhle", q * an[..., None] * scale, C0)
    # normaliser: n_t = sum_s Dn * (q.k) + an * (q.n0)
    nq = jnp.einsum("bhls,bhsd,bhld->bhl", Dn, k, q) * scale \
        + jnp.einsum("bhd,bhld->bhl", n0, q * an[..., None] * scale)
    denom = jnp.maximum(jnp.abs(nq), jnp.exp(-m_new))
    y = num / denom[..., None]
    # chunk-final states
    m_out = jnp.maximum(g_all + m0, (g_all[..., None] - g_t + li).max(-1))
    wC = jnp.exp(g_all[..., None] - g_t + li - m_out[..., None])   # [B,H,L]
    C_new = jnp.exp(g_all + m0 - m_out)[..., None, None] * C0 \
        + jnp.einsum("bhl,bhld,bhle->bhde", wC, k, v)
    n_new = jnp.exp(g_all + m0 - m_out)[..., None] * n0 \
        + jnp.einsum("bhl,bhld->bhd", wC, k)
    return y, C_new, n_new, m_out


def mlstm_apply(p, x, *, n_heads: int, cfg: XLSTMConfig, mode="train",
                state=None):
    B, S, D = x.shape
    du = p["wq"].shape[0]
    hd = du // n_heads
    uz = x @ p["up"]
    u, z = jnp.split(uz, 2, axis=-1)                      # [B,S,du]
    u = sh.shard(u, sh.BATCH, None, sh.MODEL)

    def heads(w):
        return (u @ w).reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    li = (u @ p["wi"] + p["bi"]).transpose(0, 2, 1).astype(jnp.float32)  # log-space input gate
    lf = jax.nn.log_sigmoid((u @ p["wf"] + p["bf"]).transpose(0, 2, 1).astype(jnp.float32))

    if mode in ("train", "prefill"):
        L = min(cfg.chunk, S)
        n = S // L
        rem = S - n * L
        sl = lambda a, lo, hi: a[:, :, lo:hi]
        qc = sl(q, 0, n * L).reshape(B, n_heads, n, L, hd).transpose(2, 0, 1, 3, 4)
        kc = sl(k, 0, n * L).reshape(B, n_heads, n, L, hd).transpose(2, 0, 1, 3, 4)
        vc = sl(v, 0, n * L).reshape(B, n_heads, n, L, hd).transpose(2, 0, 1, 3, 4)
        lic = sl(li, 0, n * L).reshape(B, n_heads, n, L).transpose(2, 0, 1, 3)
        lfc = sl(lf, 0, n * L).reshape(B, n_heads, n, L).transpose(2, 0, 1, 3)
        C0 = jnp.zeros((B, n_heads, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, n_heads, hd), jnp.float32)
        m0 = jnp.zeros((B, n_heads), jnp.float32)

        def body(carry, xs):
            C, nrm, m = carry
            y, C, nrm, m = _mlstm_chunk(xs[0].astype(jnp.float32),
                                        xs[1].astype(jnp.float32),
                                        xs[2].astype(jnp.float32),
                                        xs[3], xs[4], C, nrm, m)
            return (C, nrm, m), y

        (C, nrm, m), ys = jax.lax.scan(body, (C0, n0, m0), (qc, kc, vc, lic, lfc))
        y = ys.transpose(1, 2, 0, 3, 4).reshape(B, n_heads, n * L, hd)
        if rem:
            y_r, C, nrm, m = _mlstm_chunk(
                sl(q, n * L, S).astype(jnp.float32),
                sl(k, n * L, S).astype(jnp.float32),
                sl(v, n * L, S).astype(jnp.float32),
                sl(li, n * L, S), sl(lf, n * L, S), C, nrm, m)
            y = jnp.concatenate([y, y_r], axis=2)
        y = y.transpose(0, 2, 1, 3).reshape(B, S, du).astype(x.dtype)
        out = (jax.nn.silu(z) * y) @ p["down"]
        if mode == "prefill":
            return out, {"C": C, "n": nrm, "m": m}
        return out, None

    # decode step
    C, nrm, m = state["C"], state["n"], state["m"]
    q1, k1, v1 = q[:, :, 0], k[:, :, 0], v[:, :, 0]       # [B,H,hd]
    li1, lf1 = li[:, :, 0], lf[:, :, 0]
    m_new = jnp.maximum(lf1 + m, li1)
    fw = jnp.exp(lf1 + m - m_new)
    iw = jnp.exp(li1 - m_new)
    C = fw[..., None, None] * C + iw[..., None, None] * jnp.einsum(
        "bhd,bhe->bhde", k1.astype(jnp.float32), v1.astype(jnp.float32))
    nrm = fw[..., None] * nrm + iw[..., None] * k1.astype(jnp.float32)
    scale = hd ** -0.5
    num = jnp.einsum("bhd,bhde->bhe", q1.astype(jnp.float32) * scale, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", nrm,
                                         q1.astype(jnp.float32) * scale)),
                      jnp.exp(-m_new))
    y = (num / den[..., None]).reshape(B, 1, du).astype(x.dtype)
    out = (jax.nn.silu(z) * y) @ p["down"]
    return out, {"C": C, "n": nrm, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def init_slstm(builder, path, d_model: int, n_heads: int, n_groups: int):
    """Recurrent tensor parallelism by HEAD sharding: the sLSTM recurrence
    is block-diagonal per head (einsum contracts d->e WITHIN a head), so
    `model`-sharding the HEAD dim of the recurrent matrices makes the whole
    scan communication-free — each shard owns H/m heads end to end, and the
    recurrent-weight cotangents accumulate shard-locally (no per-timestep
    psum of the full weight grad; the scan body runs under shard_map, see
    ``slstm_apply``).  The gate projections stay output-dim sharded, which
    under the head layout is the same elements grouped head-major."""
    hd = d_model // n_heads
    g = (n_groups,) if n_groups else ()
    pre = (None,) if n_groups else ()
    add = builder.add
    for gate in ("i", "f", "z", "o"):
        add({}, path + [f"w{gate}"], g + (d_model, d_model),
            pre + (sh.DATA, sh.MODEL))
        add({}, path + [f"r{gate}"], g + (n_heads, hd, hd),
            pre + (sh.MODEL, None, None))
        add({}, path + [f"b{gate}"], g + (d_model,), pre + (sh.MODEL,),
            init="zeros" if gate != "f" else (lambda k, s: jnp.full(s, 3.0)))
    add({}, path + ["down"], g + (d_model, d_model), pre + (sh.MODEL, sh.DATA))


def _slstm_step(p, carry, xt):
    """One sLSTM time step.  xt: tuple of [B,H,hd] pre-projected gate inputs.

    Everything here is per-head (the einsum contracts within a head), so a
    head-sharded caller can run this shard-locally with H/m heads."""
    c, n, h, m = carry                                    # [B,H,hd] each

    def rec(w, hh):  # block-diagonal recurrent projection
        return jnp.einsum("bhd,hde->bhe", hh, w)

    xi, xf, xz, xo = xt
    hi = h
    i_t = xi + rec(p["ri"], hi)
    f_t = xf + rec(p["rf"], hi)
    z_t = jnp.tanh(xz + rec(p["rz"], hi))
    o_t = jax.nn.sigmoid(xo + rec(p["ro"], hi))
    lf = jax.nn.log_sigmoid(f_t)
    m_new = jnp.maximum(lf + m, i_t)
    i_w = jnp.exp(i_t - m_new)
    f_w = jnp.exp(lf + m - m_new)
    c_new = f_w * c + i_w * z_t
    n_new = jnp.maximum(f_w * n + i_w, jnp.exp(-m_new))
    h_new = o_t * c_new / n_new
    return (c_new, n_new, h_new, m_new)


def _scan_slstm(rp, xs, carry0):
    """Scan the sLSTM over time.  xs: tuple of [S,B,H,hd] gate inputs."""
    def body(carry, xt):
        new = _slstm_step(rp, carry, xt)
        return new, new[2]

    return jax.lax.scan(body, carry0, xs)


def _slstm_block(x, ws, bs, rp, down, carry0, *, model_axis, out_dtype):
    """The whole sLSTM block, shard-local: gate projections (output dim =
    this shard's heads), the recurrent scan over those heads, and the down
    projection (partial over the model axis, psummed here)."""
    B, S, _ = x.shape
    Hl, hd = carry0[0].shape[1], carry0[0].shape[2]
    xs = tuple((x @ w + b).swapaxes(0, 1).astype(jnp.float32)
               .reshape(S, B, Hl, hd) for w, b in zip(ws, bs))
    carry, hs = _scan_slstm(rp, xs, carry0)
    y = hs.swapaxes(0, 1).reshape(B, S, Hl * hd).astype(out_dtype)
    out = y @ down
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out, carry


def _head_shard_mesh(n_heads: int):
    """Mesh to head-shard the sLSTM block over, or None for the plain path.

    The recurrence is communication-free only if each `model` shard owns
    whole heads; when the mesh is absent, the model axis is vmap-excluded,
    or H doesn't divide, fall back to the replicated scan (GSPMD then
    partitions the time-parallel projections only, which is correct — the
    divergence this guards against came from GSPMD transposing the scan
    with model-sharded recurrent weights, not from the fallback)."""
    mesh = sh.get_mesh()
    if mesh is None or sh.MODEL not in mesh.axis_names:
        return None
    if sh.MODEL in sh.excluded_axes():
        return None
    m = mesh.shape[sh.MODEL]
    if m <= 1 or n_heads % m != 0:
        return None
    return mesh


def slstm_apply(p, x, *, n_heads: int, mode="train", state=None):
    B, S, D = x.shape
    H, hd = n_heads, D // n_heads

    if state is None:
        z0 = jnp.zeros((B, n_heads, hd), jnp.float32)
        state = {"c": z0, "n": z0 + 1e-6, "h": z0, "m": z0}
    carry0 = (state["c"], state["n"], state["h"], state["m"])
    rp = {k: p[k] for k in ("ri", "rf", "rz", "ro")}

    mesh = _head_shard_mesh(n_heads) if mode in ("train", "prefill") else None
    if mesh is not None:
        # One shard_map over the whole block, moe-style: heads manual over
        # the model axis, tokens over whichever batch axes divide B.  Every
        # cotangent that crosses the boundary does so along a MENTIONED
        # axis (tokens) or replicated params — with check_vma=False, an
        # output left unmentioned on an axis gets per-shard-inconsistent
        # cotangents whenever the incoming cotangent is sharded over it
        # (exactly what the batch-sharded residual stream produces), which
        # is how the pre-shard_map backward diverged.
        P = jax.sharding.PartitionSpec
        tok = []
        rem = B
        for a in sh.batch_axes(mesh):
            if rem % mesh.shape[a] == 0:
                tok.append(a)
                rem //= mesh.shape[a]
        tok = tuple(tok) if tok else None
        ws = tuple(p[f"w{g}"] for g in "ifzo")
        bs = tuple(p[f"b{g}"] for g in "ifzo")
        fn = partial(_slstm_block, model_axis=sh.MODEL, out_dtype=x.dtype)
        out, carry = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(tok, None, None),
                      tuple(P(None, sh.MODEL) for _ in ws),
                      tuple(P(sh.MODEL) for _ in bs),
                      {k: P(sh.MODEL, None, None) for k in rp},
                      P(sh.MODEL, None),
                      tuple(P(tok, sh.MODEL, None) for _ in carry0)),
            out_specs=(P(tok, None, None),
                       tuple(P(tok, sh.MODEL, None) for _ in carry0)),
            check_vma=False,
        )(x, ws, bs, rp, p["down"], carry0)
        # Pin the output (and hence, through the constraint's transpose, its
        # cotangent) to exactly the sharding the shard_map declared: batch
        # axes that don't divide B stay unmentioned, and an unmentioned-axis
        # cotangent must be replicated over that axis or the transpose reads
        # inconsistent per-shard values.
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(
                mesh, P(tok, None, None)))
        st = {"c": carry[0], "n": carry[1], "h": carry[2], "m": carry[3]}
        return out, (st if mode == "prefill" else None)

    xi, xf, xz, xo = (x @ p["wi"] + p["bi"], x @ p["wf"] + p["bf"],
                      x @ p["wz"] + p["bz"], x @ p["wo"] + p["bo"])
    if mode in ("train", "prefill"):
        xs = tuple(a.swapaxes(0, 1).astype(jnp.float32).reshape(S, B, H, hd)
                   for a in (xi, xf, xz, xo))
        carry, hs = _scan_slstm(rp, xs, carry0)
        y = hs.swapaxes(0, 1).reshape(B, S, D).astype(x.dtype)
        out = y @ p["down"]
        st = {"c": carry[0], "n": carry[1], "h": carry[2], "m": carry[3]}
        return out, (st if mode == "prefill" else None)

    xt = tuple(a[:, 0].astype(jnp.float32).reshape(B, H, hd)
               for a in (xi, xf, xz, xo))
    carry = _slstm_step(rp, carry0, xt)
    y = carry[2].reshape(B, 1, D).astype(x.dtype)
    return y @ p["down"], {"c": carry[0], "n": carry[1], "h": carry[2], "m": carry[3]}
