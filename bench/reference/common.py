"""Pieces of the plain reference shared by every layer kind.

The reference is a straightforward float32 forward pass written from the
model's published description in ``jax.numpy``: no kernels, no cache, no
batching, and nothing imported from the program.  Matrix products go
through ``Matmul`` so that the precision control can run the same
mathematics with its operands rounded to a lower precision.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Matmul:
    """``x @ w`` in float32 at full precision, or, for the control, with
    both operands rounded to ``lower`` (float8_e4m3fn) under a per-row
    (activations) and per-tensor (weights) absmax scale."""
    lower: str = ""

    def round(self, a, axis):
        if not self.lower:
            return a
        dt = jnp.dtype(self.lower)
        top = float(jnp.finfo(dt).max)
        amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / top, 1.0)
        return (a / scale).astype(dt).astype(F32) * scale

    def __call__(self, x, w):
        x = self.round(x.astype(F32), -1)
        w = self.round(w.astype(F32), None)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def norm(x, scale, kind: str):
    """rmsnorm (eps 1e-6, gain 1 + scale) or OLMo's non-parametric
    LayerNorm (eps 1e-5), over the last axis."""
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * (1.0 + scale.astype(F32))
    if kind == "ln_nonparam":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5)
    raise ValueError(f"no reference norm {kind!r}")


def rope(x, theta: float):
    """Rotary embedding, rotate-half form, positions 0..S-1.
    x: (S, H, D)."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def activation(x, kind: str):
    if kind == "silu":
        return x * jax.nn.sigmoid(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jnp.maximum(x, 0)
    raise ValueError(f"no reference activation {kind!r}")
