"""Reference ffn ``moe``: softmax router, the top-k experts per token
with their probabilities renormalised to sum to one, each expert a gated
MLP.  Every expert is computed for every token and weighted by its gate
(zero outside the top k), so no token is ever dropped."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, activation, norm


def ffn(p, x, model: dict, mm):
    e, k = model["num_experts"], model["top_k"]
    xn = norm(x, p["norm"], model["norm"])
    probs = jax.nn.softmax(mm(xn, p["router"]).astype(F32), -1)  # (S, E)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)           # (S, E)

    def expert(carry, w):
        wg, wu, wd, g = w
        h = activation(mm(xn, wg), model["act"]) * mm(xn, wu)
        return carry + g[:, None] * mm(h, wd), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (p["w_gate"], p["w_up"], p["w_down"], gate.T))
    return out
