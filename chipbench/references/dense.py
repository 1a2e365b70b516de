"""Plain float32 reference of the dense decoder LM as the repo builds it:
pre-norm blocks of grouped-query causal attention with rotary positions and
a SwiGLU feed-forward, an untied output head over a vocabulary padded to a
multiple of 256.

Independent of the code under test: it imports nothing from ``repro``.
Attention is one softmax over the whole causal score matrix, taken one
kv head (and its query group) at a time to bound the memory.
Every matrix product goes through ``mm`` (see ``fl_reference.matmul``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.xlstm import cross_entropy, rms_norm


def dims(model: dict) -> dict:
    D, H = model["d_model"], model["n_heads"]
    return dict(D=D, H=H, KV=model["kv_heads"],
                hd=model.get("head_dim") or D // H, F=model["d_ff"],
                V=model["vocab"], Vp=((model["vocab"] + 255) // 256) * 256,
                L=model["n_layers"], eps=model["norm_eps"],
                theta=model["rope_theta"])


def layout(model: dict) -> dict:
    d = dims(model)
    D, H, KV, hd, F, L = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["L"]
    n = lambda shape, fan: (shape, ("normal", fan ** -0.5))
    slot = {"norm1": ((L, D), ("const", 1.0)),
            "norm2": ((L, D), ("const", 1.0)),
            "wq": n((L, D, H * hd), D), "wk": n((L, D, KV * hd), D),
            "wv": n((L, D, KV * hd), D), "wo": n((L, H * hd, D), H * hd),
            "w1": n((L, D, F), D), "w3": n((L, D, F), D),
            "w2": n((L, F, D), F)}
    return {"embed": n((d["V"], D), D), "final_norm": ((D,), ("const", 1.0)),
            "layers": {"slot0": slot}, "unembed": n((D, d["Vp"]), D)}


def rope(x, theta):
    """Rotary positions on [B, S, heads, hd], the two halves of each head
    rotated as pairs (x_i, x_{i + hd/2})."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(p, x, d, mm):
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    h = rms_norm(x, p["norm1"], d["eps"])
    q = rope(mm("bsd,de->bse", h, p["wq"]).reshape(B, S, H, hd), d["theta"])
    k = rope(mm("bsd,de->bse", h, p["wk"]).reshape(B, S, KV, hd), d["theta"])
    v = mm("bsd,de->bse", h, p["wv"]).reshape(B, S, KV, hd)
    G = H // KV                                 # query head j reads kv j//G
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(qkv):                             # one kv head, its G queries
        qg, kg, vg = qkv
        s = mm("bqgd,bkd->bgqk", qg, kg) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return mm("bgqk,bkd->bqgd", jax.nn.softmax(s, -1), vg)

    a = jax.lax.map(group, (q.reshape(B, S, KV, G, hd).transpose(2, 0, 1, 3, 4),
                            k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    a = a.transpose(1, 2, 0, 3, 4).reshape(B, S, H * hd)
    x = x + mm("bse,ed->bsd", a, p["wo"])
    h = rms_norm(x, p["norm2"], d["eps"])
    f = jax.nn.silu(mm("bsd,df->bsf", h, p["w1"])) * mm("bsd,df->bsf", h,
                                                         p["w3"])
    return x + mm("bsf,fd->bsd", f, p["w2"])


def loss(params, tokens, targets, model: dict, mm):
    d = dims(model)
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, lp: (block(lp, x, d, mm), None)),
        x, params["layers"]["slot0"])
    x = rms_norm(x, params["final_norm"], d["eps"])
    return cross_entropy(x, params["unembed"], targets, d["V"], mm)


def fwd_flops_per_token(model: dict, seq_len: int, causal: bool = True
                        ) -> dict:
    """Forward matmul FLOPs per token.  Attention reads (S+1)/2 keys per
    query on average under the causal mask (``causal=True``, the work the
    pass requires), or all S (``causal=False``, what a dense score matrix
    computes)."""
    d = dims(model)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    ctx = (seq_len + 1) / 2 if causal else seq_len
    layer = (2 * D * (H + 2 * KV) * hd + 2 * 2 * ctx * H * hd
             + 2 * H * hd * D + 3 * 2 * D * F)
    return {"attn_mlp": layer, "head": 2 * D * d["Vp"],
            "per_token": d["L"] * layer + 2 * D * d["Vp"]}
