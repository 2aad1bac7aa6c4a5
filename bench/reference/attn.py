"""Reference mixer ``attn``: causal multi-head attention with grouped
key/value heads and rotary positions, scale head_dim^-0.5."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, norm, rope


def mixer(p, x, model: dict, blk: dict, mm):
    """x: (S, D) float32 residual stream; returns the block's update."""
    s, d = x.shape
    h, kv = model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"] or d // h
    xn = norm(x, p["norm"], model["norm"])
    q = rope(mm(xn, p["wq"]).reshape(s, h, hd), model["rope_theta"])
    k = rope(mm(xn, p["wk"]).reshape(s, kv, hd), model["rope_theta"])
    v = mm(xn, p["wv"]).reshape(s, kv, hd)
    g = h // kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", mm.round(q, -1), mm.round(k, -1),
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    i = jnp.arange(s)
    allowed = i[:, None] >= i[None, :]
    if blk.get("window"):
        allowed &= (i[:, None] - i[None, :]) < blk["window"]
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(F32), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", mm.round(probs, -1), mm.round(v, 0),
                   precision=jax.lax.Precision.HIGHEST)
    return mm(o.reshape(s, h * hd), p["wo"])
