"""The comparison that decides ``correct``.

For each sampled request the reference runs once over the prompt and the
tokens the engine served.  Two numbers are read over the sample:

* ``logit_gap``: at every position where the engine served a token, how
  far that token's reference logit lies below the reference's best
  there; the widest such gap.  Greedy decoding serves the engine's own
  argmax, so a gap shows where the engine's logits left the reference's
  by more than the near-ties a correct bfloat16 run flips.
* ``logit_err``: the logits the timed path itself produced, against the
  reference's at the same positions: the widest difference over a fixed
  set of vocabulary columns drawn from the seed and over the row's
  maximum (the engine's best logit against the reference's logit of the
  token served), over the spread (standard deviation) of the reference's
  logits at those columns.  It sees an error that moves the logits
  without flipping an argmax.

The control puts the same reference in the program's place, with every
matrix product's operands rounded to float8 (``CONTROL_DTYPE``), and
reads both numbers for it at the same positions: the gap of the token
its logits put first, and its logits against the float32 reference's.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.generator import rng_for
from bench.reference import model as ref_model

CONTROL_DTYPE = "float8_e4m3fn"


def columns(seed: int, vocab: int, n: int) -> np.ndarray:
    """The vocabulary columns whose logits are kept, drawn from the seed."""
    return np.sort(rng_for(seed, "columns").choice(vocab, min(n, vocab),
                                                   replace=False))


def sample(finished: Sequence[Tuple[List[int], List[int]]], seed: int,
           min_tokens: int, max_requests: int) -> List[int]:
    """Indices of finished (prompt, served) pairs to check: the longest,
    then others drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    if not finished:
        return []
    total = [len(p) + len(s) for p, s in finished]
    longest = int(np.argmax(total))
    order = [i for i in rng_for(seed, "sample").permutation(len(finished))
             if i != longest]
    pick, served = [longest], len(finished[longest][1])
    for i in order:
        if served >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(int(i))
        served += len(finished[i][1])
    return pick


@functools.lru_cache(maxsize=4)
def _ref_fn(model_json: str, control: bool):
    model = json.loads(model_json)

    @jax.jit
    def ref(params, seq, pos, served, cols):
        full = ref_model.logits(params, seq, model)[pos]        # (T, V)
        out = {"cols": full[:, cols], "best": full.max(-1),
               "served": jnp.take_along_axis(full, served[:, None], -1)[:, 0]}
        if control:
            low = ref_model.logits(params, seq, model,
                                   lower=CONTROL_DTYPE)[pos]
            first = jnp.argmax(low, -1)
            out["low_cols"] = low[:, cols]
            out["low_max"] = low.max(-1)
            out["at_low_first"] = jnp.take_along_axis(
                full, first[:, None], -1)[:, 0]
        return out

    return ref


def check(params, model: dict, items: Sequence[Tuple[List[int], List[int],
                                                    np.ndarray]],
          length: int, cols: np.ndarray, control: bool = False
          ) -> Dict[str, Dict[str, float]]:
    """Readings over ``items`` (prompt, served tokens, the engine's kept
    rows: its logits at ``cols`` then the row maximum, one row per served
    token), each run padded to ``length`` positions so that one compiled
    program serves them all.  Returns ``{"program": {...}}`` and, with
    ``control``, ``{"control": {...}}``, each holding ``logit_gap`` and
    ``logit_err``, plus the count of served tokens compared."""
    fn = _ref_fn(json.dumps(model, sort_keys=True), control)
    k = len(cols)
    got: Dict[str, List[np.ndarray]] = {}
    for prompt, served, rows in items:
        seq = list(prompt) + list(served[:-1])
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} exceeds {length}")
        t = len(served)
        if rows.shape != (t, k + 1):
            raise ValueError(f"{rows.shape} kept rows for {t} served tokens")
        pos = np.zeros(length, np.int32)
        pos[:t] = np.arange(len(prompt) - 1, len(prompt) - 1 + t)
        srv = np.zeros(length, np.int32)
        srv[:t] = served
        seq_p = np.zeros(length, np.int32)
        seq_p[:len(seq)] = seq
        out = fn(params, jnp.asarray(seq_p), jnp.asarray(pos),
                 jnp.asarray(srv), jnp.asarray(cols, jnp.int32))
        for key, v in out.items():
            got.setdefault(key, []).append(np.asarray(v)[:t])
        got.setdefault("eng_cols", []).append(rows[:, :k])
        got.setdefault("eng_max", []).append(rows[:, k])
    g = {key: np.concatenate(v) for key, v in got.items()}
    scale = float(np.std(g["cols"]))

    def err(cols_, best, at_best):
        return max(float(np.max(np.abs(cols_ - g["cols"]))),
                   float(np.max(np.abs(best - at_best)))) / scale

    res = {"program": {
        "logit_gap": float(np.max(g["best"] - g["served"])),
        "logit_err": err(g["eng_cols"], g["eng_max"], g["served"])}}
    if control:
        res["control"] = {
            "logit_gap": float(np.max(g["best"] - g["at_low_first"])),
            "logit_err": err(g["low_cols"], g["low_max"],
                             g["at_low_first"])}
    res["tokens"] = int(len(g["best"]))
    return res
