"""The whole reference forward: embedding, the stack of blocks, final
norm and LM head, in float32 at full matmul precision.  Each block's
mixer and ffn are found by name (``reference/<kind>.py``), and the stack
runs one period at a time, its weights cast to float32 per layer."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from bench.reference.common import F32, Matmul, norm


def _kind(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def logits(params, tokens, model: dict, lower: str = ""):
    """tokens: (S,) int32 -> (S, V) float32 logits at every position."""
    mm = Matmul(lower)
    pattern = model["pattern"]
    mixers = [_kind(b["mixer"]).mixer for b in pattern]
    ffns = [_kind(b["ffn"]).ffn for b in pattern]
    x = params["embed"][tokens].astype(F32)

    def period(x, pp):
        for i, blk in enumerate(pattern):
            bp = pp[f"b{i}"]
            x = x + mixers[i](bp[blk["mixer"]], x, model, blk, mm)
            x = x + ffns[i](bp[blk["ffn"]], x, model, mm)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(period, x, params["blocks"])
        x = norm(x, params["final_norm"], model["norm"])
        head = (params["embed"].T if model["tie_embeddings"]
                else params["lm_head"])
        return mm(x, head)
