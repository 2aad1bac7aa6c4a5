"""The plain reference against the engine's own logits at smoke size, for
an attention + MLP and an attention + MoE configuration, and the float8
control against both."""
import numpy as np
import pytest

from bench import compare, harness, spec, weights
from bench.reference import model as ref_model
from bench.tests.smoke import smoke_cell


def _served_with_logits(cell, seed, prompt, max_new):
    """Serve one greedy request through the cell's engine; returns the
    served tokens and the logits row of every decode step that made one."""
    import jax
    from repro.serve import ServeEngine
    box = [weights.make(cell.config, seed)]
    with harness.served_weights(box):
        eng = ServeEngine(spec.model_config(cell.config), seed=0,
                          devices=jax.devices()[:1], **cell.engine_kwargs)
    rows = []
    decode = eng._decode

    def capture(tok, pos):
        nxt, logits, cache = decode(tok, pos)
        rows.append(np.asarray(logits))
        return nxt, logits, cache
    eng._decode = capture
    eng.warmup()
    rows.clear()
    req = eng.submit(prompt, max_new)
    while eng.scheduler.has_work:
        eng.step()
    slot = req.slot
    # a decode step's row is the request's once its prefill is done: the
    # last len(tokens) rows hold its served positions
    got = np.stack([r[slot] for r in rows[-len(req.tokens):]])
    return list(req.tokens), got


@pytest.mark.parametrize("config", ["", "granite-moe-3b-a800m-s75"])
def test_reference_matches_engine_and_control_does_not(config):
    import jax.numpy as jnp
    cell = smoke_cell("olmo-1b.gen", config=config)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cell.config["model"]["vocab_size"], 45).tolist()
    served, got = _served_with_logits(cell, 9, prompt, 30)
    assert len(served) == 30
    params = weights.make(cell.config, 9)
    seq = jnp.asarray(prompt + served[:-1], jnp.int32)
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    model = cell.config["model"]
    ref = np.asarray(ref_model.logits(params, seq, model))[pos]
    low = np.asarray(ref_model.logits(params, seq, model,
                                      lower=compare.CONTROL_DTYPE))[pos]
    scale = np.std(ref)
    err = np.max(np.abs(got - ref)) / scale
    err_low = np.max(np.abs(low - ref)) / scale
    # bfloat16 activations and weights, float32 accumulation: the widest
    # of some thousands of logits moves by a few hundredths of their
    # spread (0.04 dense, 0.07 MoE, where bf16 router logits can swap the
    # last of the top-k experts); float8 operands move it by about half
    assert err < 0.15, err
    assert err_low > 4 * err, (err, err_low)
    # the engine serves the reference's argmax up to near-ties
    gaps = ref.max(-1) - ref[np.arange(len(served)), served]
    assert gaps.max() <= 2 * err * scale + 1e-6
    cols = compare.columns(9, model["vocab_size"], 64)
    rows = np.concatenate([got[:, cols], got.max(-1, keepdims=True)], -1)
    out = compare.check(params, model, [(prompt, served, rows)],
                        cell.engine_kwargs["max_len"], cols, control=True)
    assert out["tokens"] == len(served)
    prog, ctl = out["program"], out["control"]
    assert prog["logit_gap"] == pytest.approx(gaps.max(), abs=1e-5)
    # the engine's logits at the kept columns and its best against the
    # reference's, over the reference's spread at those columns
    want = max(np.abs(got[:, cols] - ref[:, cols]).max(),
               np.abs(got.max(-1) - ref[np.arange(len(served)),
                                         served]).max()) / ref[:, cols].std()
    assert prog["logit_err"] == pytest.approx(want, rel=1e-4)
    assert ctl["logit_err"] > 3 * prog["logit_err"]
