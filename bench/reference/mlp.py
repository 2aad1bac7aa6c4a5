"""Reference ffn ``mlp``: act(x W_gate) * (x W_up), then W_down."""
from __future__ import annotations

from bench.reference.common import activation, norm


def ffn(p, x, model: dict, mm):
    xn = norm(x, p["norm"], model["norm"])
    h = activation(mm(xn, p["w_gate"]), model["act"]) * mm(xn, p["w_up"])
    return mm(h, p["w_down"])
