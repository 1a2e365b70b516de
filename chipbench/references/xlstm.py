"""Plain float32 reference of the xLSTM language model as the repo builds it
(arXiv:2405.04517: alternating mLSTM and sLSTM blocks, pre-norm residual,
no separate feed-forward).

Independent of the code under test: it imports nothing from ``repro``.  The
mLSTM is written in the paper's parallel (quadratic) form over the whole
sequence, not the chunkwise form the model runs, and the sLSTM as its
per-step recurrence.  Both carry the max-state stabiliser ``m`` from the
sequence start with ``m_0 = 0``, as the model's recurrence does.

Every matrix product goes through ``mm``: in float32 at ``highest``
precision for the reference, or through a rounding cast for the
lower-precision control (see ``fl_reference.matmul``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(model: dict) -> dict:
    D, H = model["d_model"], model["n_heads"]
    x = model["xlstm"]
    period = x["slstm_every"]
    du = int(x["proj_factor"] * D)
    vp = ((model["vocab"] + 255) // 256) * 256
    return dict(D=D, H=H, du=du, hd_m=du // H, hd_s=D // H, V=model["vocab"],
                Vp=vp, period=period, G=model["n_layers"] // period,
                eps=model["norm_eps"])


def layout(model: dict) -> dict:
    """Parameter tree as the model builds it: {path: (shape, init)} nested,
    with init ``("normal", std)`` or ``("const", value)``.  Layers are
    stacked over the ``G`` groups of one period (mLSTM slots, then one
    sLSTM slot)."""
    d = dims(model)
    D, H, du, G, hd = d["D"], d["H"], d["du"], d["G"], d["hd_s"]
    n = lambda shape, fan: (shape, ("normal", fan ** -0.5))
    c = lambda shape, v: (shape, ("const", v))
    layers = {}
    for si in range(d["period"]):
        slot = {"norm1": c((G, D), 1.0)}
        if si < d["period"] - 1:
            slot["mlstm"] = {
                "up": n((G, D, 2 * du), D), "wq": n((G, du, du), du),
                "wk": n((G, du, du), du), "wv": n((G, du, du), du),
                "wi": n((G, du, H), du), "wf": n((G, du, H), du),
                "bi": c((G, H), 0.0), "bf": c((G, H), 3.0),
                "down": n((G, du, D), du)}
        else:
            s = {}
            for g in "ifzo":
                s[f"w{g}"] = n((G, D, D), D)
                s[f"r{g}"] = n((G, H, hd, hd), hd)
                s[f"b{g}"] = c((G, D), 3.0 if g == "f" else 0.0)
            s["down"] = n((G, D, D), D)
            slot["slstm"] = s
        layers[f"slot{si}"] = slot
    return {"embed": n((d["V"], D), D), "final_norm": c((D,), 1.0),
            "layers": layers, "unembed": n((D, d["Vp"]), D)}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mlstm(p, x, H, mm):
    """Parallel mLSTM: h_t = sum_s W_ts (q_t.k_s) v_s / max(|sum_s W_ts
    (q_t.k_s)|, exp(-m_t)), W_ts = exp(sum_{u=s+1..t} log f_u + log i_s
    - m_t), m_t the running max of those log weights and of the cumulative
    forget gate (the stabiliser's start value 0)."""
    B, S, _ = x.shape
    du = p["wq"].shape[0]
    hd = du // H
    uz = mm("bsd,de->bse", x, p["up"])
    u, z = uz[..., :du], uz[..., du:]

    def heads(w):
        return mm("bse,ef->bsf", u, w).reshape(B, S, H, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    li = (mm("bse,eh->bsh", u, p["wi"]) + p["bi"]).transpose(0, 2, 1)
    lf = jax.nn.log_sigmoid(
        (mm("bse,eh->bsh", u, p["wf"]) + p["bf"]).transpose(0, 2, 1))
    g = jnp.cumsum(lf, -1)                                 # [B,H,S]
    logw = g[..., :, None] - g[..., None, :] + li[..., None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))
    logw = jnp.where(causal, logw, -jnp.inf)
    m = jnp.maximum(logw.max(-1), g)                       # [B,H,S]
    w = jnp.exp(logw - m[..., None])
    s = mm("bhtd,bhsd->bhts", q, k) * hd ** -0.5
    num = mm("bhts,bhsd->bhtd", s * w, v)
    den = jnp.maximum(jnp.abs((s * w).sum(-1)), jnp.exp(-m))
    y = (num / den[..., None]).transpose(0, 2, 1, 3).reshape(B, S, du)
    return mm("bse,ed->bsd", jax.nn.silu(z) * y, p["down"])


def slstm(p, x, H, mm):
    B, S, D = x.shape
    hd = D // H
    pre = {g: (mm("bsd,de->bse", x, p[f"w{g}"]) + p[f"b{g}"])
           .reshape(B, S, H, hd).swapaxes(0, 1) for g in "ifzo"}

    def step(carry, xt):
        c, n, h, m = carry
        r = {g: mm("bhd,hde->bhe", h, p[f"r{g}"]) for g in "ifzo"}
        i_t = xt["i"] + r["i"]
        lf = jax.nn.log_sigmoid(xt["f"] + r["f"])
        z_t = jnp.tanh(xt["z"] + r["z"])
        o_t = jax.nn.sigmoid(xt["o"] + r["o"])
        m_new = jnp.maximum(lf + m, i_t)
        i_w, f_w = jnp.exp(i_t - m_new), jnp.exp(lf + m - m_new)
        c = f_w * c + i_w * z_t
        n = jnp.maximum(f_w * n + i_w, jnp.exp(-m_new))
        h = o_t * c / n
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, H, hd), F32)
    _, hs = jax.lax.scan(step, (zero, zero + 1e-6, zero, zero), pre)
    y = hs.swapaxes(0, 1).reshape(B, S, D)
    return mm("bsd,de->bse", y, p["down"])


def loss(params, tokens, targets, model: dict, mm):
    """Mean next-token cross-entropy over every position of the batch."""
    d = dims(model)
    x = params["embed"][tokens]

    def group(x, gp):
        for si in range(d["period"]):
            sp = gp[f"slot{si}"]
            h = rms_norm(x, sp["norm1"], d["eps"])
            if "mlstm" in sp:
                x = x + mlstm(sp["mlstm"], h, d["H"], mm)
            else:
                x = x + slstm(sp["slstm"], h, d["H"], mm)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(group), x, params["layers"])
    x = rms_norm(x, params["final_norm"], d["eps"])
    return cross_entropy(x, params["unembed"], targets, d["V"], mm)


def cross_entropy(x, unembed, targets, vocab, mm):
    """Mean CE of ``x @ unembed`` over the first ``vocab`` columns (the rest
    are padding and take no probability)."""
    logits = mm("bsd,dv->bsv", x, unembed)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < vocab, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (lse - picked).mean()


def fwd_flops_per_token(model: dict, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of each layer kind and of the head,
    for the model as built: the mLSTM in its chunkwise form (``chunk``
    positions inside a chunk, a [hd, hd] state across chunks), the sLSTM
    with its block-diagonal recurrence.  Elementwise work is not counted."""
    d = dims(model)
    D, H, du, hdm, hds = d["D"], d["H"], d["du"], d["hd_m"], d["hd_s"]
    L = min(model["xlstm"]["chunk"], seq_len)
    mlstm_f = (2 * D * 2 * du + 3 * 2 * du * du + 2 * 2 * du * H
               + 3 * 2 * L * du           # q.k, (s*w).v, normaliser
               + 2 * 2 * du * hdm         # q.C and the state update k v^T
               + 2 * 2 * du               # q.n and the normaliser update
               + 2 * du * D)
    slstm_f = 4 * 2 * D * D + 4 * 2 * D * hds + 2 * D * D
    return {"mlstm": mlstm_f, "slstm": slstm_f, "head": 2 * D * d["Vp"],
            "per_token": d["G"] * ((d["period"] - 1) * mlstm_f + slstm_f)
            + 2 * D * d["Vp"]}
