"""HLO counter unit tests: fusion byte semantics, view-chain resolution,
trip-count extraction — the machinery the roofline numbers rest on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_counters import analyze, parse_computations


def _compiled(f, *avals):
    return jax.jit(f).lower(*avals).compile()


def test_matmul_flops_exact():
    c = _compiled(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((128, 64), jnp.float32),
                  jax.ShapeDtypeStruct((64, 32), jnp.float32))
    assert analyze(c.as_text())["flops"] == pytest.approx(2 * 128 * 64 * 32)


def test_scan_trip_multiplier():
    def f(x, w):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None,
                            length=9)[0]
    c = _compiled(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                  jax.ShapeDtypeStruct((32, 32), jnp.float32))
    assert analyze(c.as_text())["flops"] == pytest.approx(2 * 32**3 * 9)


def test_scan_sliced_xs_not_charged_full():
    """Scan over stacked weights: each iteration must charge ~one slice of
    the stacked buffer, not the whole stack (the 20× inflation bug)."""
    P, D = 16, 64

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    c = _compiled(f, jax.ShapeDtypeStruct((D, D), jnp.float32),
                  jax.ShapeDtypeStruct((P, D, D), jnp.float32))
    r = analyze(c.as_text())
    stack_bytes = P * D * D * 4
    # total traffic should be O(P * slice) ~ a few x the stack, never
    # O(P * stack) = P x stack_bytes
    assert r["bytes"] < 8 * stack_bytes, r["bytes"] / stack_bytes


def test_dus_cache_update_charged_at_update_size():
    """Decode-style cache update in a scan: traffic ~ slice, not buffer."""
    P, C, D = 8, 256, 64

    def f(cache, xs):
        def body(carry, i):
            cache = carry
            upd = jnp.full((1, D), 1.0, jnp.float32)
            cache = jax.lax.dynamic_update_slice(cache, upd, (i, 0))
            return cache, None
        out, _ = jax.lax.scan(body, cache, xs)
        return out

    c = _compiled(f, jax.ShapeDtypeStruct((C, D), jnp.float32),
                  jax.ShapeDtypeStruct((P,), jnp.int32))
    r = analyze(c.as_text())
    buffer_bytes = C * D * 4
    assert r["bytes"] < 6 * buffer_bytes, r["bytes"] / buffer_bytes


@pytest.mark.parametrize("arch", ["olmo-1b", "granite-moe-3b-a800m"])
def test_real_jitted_serve_steps_analyzable(arch):
    """The analyzer must handle the *real* serving programs — jitted
    decode and prefill steps with their while-loops, DUS cache updates,
    and donated buffers — not just the synthetic shapes above: real HLO
    parses, and the counted bytes sit at or above the packed weights
    each step reads (the whole weight stream for decode; the stack
    without the LM head for prefill)."""
    from repro.configs import get_smoke_config
    from repro.serve import ServeEngine

    cfg = get_smoke_config(arch)
    eng = ServeEngine(cfg, seed=0, num_slots=2, max_len=32, sparsity=0.5,
                      paged=True, page_len=8, prefill_chunk=8)
    stream = eng.weight_stream_report()["sparse_bytes_per_step"]
    head = (eng.lm_weight.hbm_bytes if eng.lm_weight is not None
            else cfg.d_model * cfg.vocab_size * 4)
    floors = {"decode": stream, "prefill": stream - head}
    for phase, floor in floors.items():
        r = analyze(eng.lower(phase).compile().as_text())
        assert r["flops"] > 0 and r["bytes"] > 0
        assert r["bytes"] >= floor, (phase, r["bytes"], floor)


def test_parse_handles_tuple_shapes_with_index_comments():
    """Shapes like (s32[], f32[8]{0}, /*index=5*/ f32[4]) must parse."""
    txt = """ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %t = (f32[8]{0}, s32[], /*index=2*/ f32[8]{0}) tuple(%a, %a, %a)
  ROOT %r = f32[8]{0} get-tuple-element(%t), index=0
}
"""
    comps = parse_computations(txt)
    assert "main" in comps
    ops = [i.op for i in comps["main"]]
    assert "tuple" in ops and "get-tuple-element" in ops


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v4", False), ("cpu", False)])
def test_peaks_table_refuses_unknown_chips(kind, known):
    """Peaks come from the table keyed by ``device_kind``; a chip it does
    not hold is an error, never a silent v5e default."""
    from repro.launch.hlo_analysis import peaks, roofline
    if known:
        assert peaks(kind)["hbm_bw"] == 819e9
        assert roofline(1.0, 819e9, 0.0, kind)["memory_s"] == 1.0
    else:
        with pytest.raises(ValueError, match="no published peaks"):
            roofline(1.0, 1.0, 0.0, kind)
