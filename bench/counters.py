"""Operations and bytes the model's work needs, counted from the
configuration, the sparsity and the rows a call computes.

Nothing here reads the program's kernels, blocks or padding: the counts
are what any implementation of these projections has to do, so a later
PR that changes how a projection runs changes the time, not the work.

* A pruned projection (K, N) at density rho takes ``2 M rho K N``
  operations and moves ``rho K N`` values at their dtype, one bitmap bit
  per weight (``K N / 8`` bytes) and its activations in and out.
* A routed expert stack (E, K, N) computes only its ``top_k`` experts per
  row (``2 M k rho K N``) and moves the weights of ``min(E, M k)``
  experts.
* The LM head is unpruned (density 1).

A projection counts toward a kernel's least time only where the program
serves it through that kernel (``kernel``: the ``(component, tensor)``
pairs of the engine's pack manifest, ``("lm", "head")`` for the head).
"""
from __future__ import annotations

from typing import Dict, List


def _hd(model: dict) -> int:
    return model["head_dim"] or model["d_model"] // model["num_heads"]


def projections(model: dict) -> List[dict]:
    """Every weight matrix a token passes through, per layer or once."""
    d, h, kv, f = (model["d_model"], model["num_heads"],
                   model["num_kv_heads"], model["d_ff"])
    hd = _hd(model)
    out: List[dict] = []
    for blk in model["pattern"]:
        reps = model["num_layers"] // len(model["pattern"])
        if blk["mixer"] != "attn":
            raise ValueError(f"no counts for mixer {blk['mixer']!r}")
        for name, k, n in (("wq", d, h * hd), ("wk", d, kv * hd),
                           ("wv", d, kv * hd), ("wo", h * hd, d)):
            out.append(dict(comp="attn", name=name, k=k, n=n, reps=reps,
                            experts=0, pruned=True))
        if blk["ffn"] == "mlp":
            for name, k, n in (("w_gate", d, f), ("w_up", d, f),
                               ("w_down", f, d)):
                out.append(dict(comp="mlp", name=name, k=k, n=n, reps=reps,
                                experts=0, pruned=True))
        elif blk["ffn"] == "moe":
            e = model["num_experts"]
            out.append(dict(comp="moe", name="router", k=d, n=e, reps=reps,
                            experts=0, pruned=True))
            for name, k, n in (("w_gate", d, f), ("w_up", d, f),
                               ("w_down", f, d)):
                out.append(dict(comp="moe", name=name, k=k, n=n, reps=reps,
                                experts=e, pruned=True))
        else:
            raise ValueError(f"no counts for ffn {blk['ffn']!r}")
    out.append(dict(comp="lm", name="head", k=d, n=model["vocab_size"],
                    reps=1, experts=0, pruned=False))
    return out


def call_work(p: dict, m: int, model: dict, sparsity: float,
              value_bytes: int = 2, act_bytes: int = 2) -> Dict[str, float]:
    """(flops, bytes) of one call of projection ``p`` over ``m`` rows."""
    rho = (1.0 - sparsity) if p["pruned"] else 1.0
    kn = p["k"] * p["n"]
    weight = rho * kn * value_bytes + kn / 8.0
    if p["experts"]:
        rows = m * model["top_k"]
        touched = min(p["experts"], rows)
        return {"flops": 2.0 * rows * rho * kn,
                "bytes": touched * weight
                + rows * (p["k"] + p["n"]) * act_bytes}
    return {"flops": 2.0 * m * rho * kn,
            "bytes": weight + m * (p["k"] + p["n"]) * act_bytes}


def step_least_time(model: dict, sparsity: float, m: int, kernel,
                    peak: dict) -> Dict[str, float]:
    """Least time of every kernel call in one step of ``m`` rows, over
    the projections in ``kernel``: the sum over calls of
    max(flops / peak, bytes / bandwidth), and the part of it that the
    bandwidth term bounds."""
    total = hbm = flops = nbytes = 0.0
    for p in projections(model):
        if (p["comp"], p["name"]) not in kernel:
            continue
        w = call_work(p, m, model, sparsity)
        t_c = w["flops"] / peak["bf16_flops"]
        t_m = w["bytes"] / peak["hbm_bytes_s"]
        total += p["reps"] * max(t_c, t_m)
        hbm += p["reps"] * (t_m if t_m >= t_c else 0.0)
        flops += p["reps"] * w["flops"]
        nbytes += p["reps"] * w["bytes"]
    return {"least_s": total, "hbm_bound_s": hbm, "flops": flops,
            "bytes": nbytes}


def calls_least_time(model: dict, sparsity: float, rows, kernel,
                     peak: dict) -> Dict[str, float]:
    """``step_least_time`` summed over calls of ``rows`` live rows each
    (a call with no live row does no work)."""
    tot: Dict[str, float] = {}
    per: Dict[int, Dict[str, float]] = {}
    for m in rows:
        if m <= 0:
            continue
        if m not in per:
            per[m] = step_least_time(model, sparsity, m, kernel, peak)
        for k, v in per[m].items():
            tot[k] = tot.get(k, 0.0) + v
    return {k: tot.get(k, 0.0) for k in ("least_s", "hbm_bound_s", "flops",
                                         "bytes")}


def token_flops(model: dict, sparsity: float, head: bool = True) -> float:
    """Model operations per token outside attention's score and value
    products: 2 x the nonzero weights the token passes through (its top-k
    experts only) plus, with ``head``, the dense LM head."""
    total = 0.0
    for p in projections(model):
        if p["comp"] == "lm" and not head:
            continue
        rho = (1.0 - sparsity) if p["pruned"] else 1.0
        per = 2.0 * rho * p["k"] * p["n"]
        if p["experts"]:
            per *= model["top_k"]
        total += p["reps"] * per
    return total


def attn_flops_per_ctx(model: dict) -> float:
    """Score plus value operations per token per position of context:
    2 H hd for q.k and 2 H hd for p.v, in every attention layer."""
    layers = sum(1 for b in model["pattern"] if b["mixer"] == "attn") * (
        model["num_layers"] // len(model["pattern"]))
    return 4.0 * layers * model["num_heads"] * _hd(model)


def positions_flops(token: float, per_ctx: float, start: int,
                    end: int) -> float:
    """Operations to process positions start..end-1 of one sequence, given
    ``token_flops`` and ``attn_flops_per_ctx`` of the model."""
    if end <= start:
        return 0.0
    ctx = (end * (end + 1) - start * (start + 1)) / 2.0   # sum of p + 1
    return (end - start) * token + ctx * per_ctx
