"""FLOP and byte counters against hand counts at smoke widths."""
import pytest

from bench import counters

DENSE = {"d_model": 64, "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": None, "d_ff": 128, "vocab_size": 256,
         "pattern": [{"mixer": "attn", "ffn": "mlp", "window": None}],
         "num_experts": 0, "top_k": 0}
MOE = {**DENSE, "d_ff": 32, "num_experts": 5, "top_k": 2,
       "pattern": [{"mixer": "attn", "ffn": "moe", "window": None}]}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_s": 1e9}


def test_dense_token_flops_by_hand():
    # per layer: wq 64x64, wk 64x32, wv 64x32, wo 64x64 -> 12288 weights;
    # mlp 3 x 64x128 = 24576; at density 0.25 that is 9216 nonzero;
    # 2 layers -> 18432; head 64x256 dense = 16384
    want = 2 * (2 * 0.25 * (12288 + 24576)) + 2 * 16384
    assert counters.token_flops(DENSE, 0.75) == pytest.approx(want)
    # attention: 4 * layers * heads * head_dim per position of context
    assert counters.attn_flops_per_ctx(DENSE) == 4 * 2 * 4 * 16


def test_moe_token_flops_counts_top_k_experts():
    # per layer: attention 12288, router 64x5 = 320, experts 3 x 64x32
    # = 6144 each, top-2 of 5 -> 12288 through the token
    per_layer = 0.25 * (12288 + 320 + 12288)
    want = 2 * 2 * per_layer + 2 * 64 * 256
    assert counters.token_flops(MOE, 0.75) == pytest.approx(want)


def test_positions_flops_sums_context():
    tf = counters.token_flops(DENSE, 0.75)
    af = counters.attn_flops_per_ctx(DENSE)
    # positions 3, 4, 5 attend over 4, 5, 6 positions
    assert counters.positions_flops(tf, af, 3, 6) == pytest.approx(
        3 * tf + (4 + 5 + 6) * af)
    assert counters.positions_flops(tf, af, 6, 6) == 0.0


def test_call_work_dense_and_expert():
    wq = next(p for p in counters.projections(DENSE) if p["name"] == "wq")
    w = counters.call_work(wq, 16, DENSE, 0.75)
    assert w["flops"] == 2 * 16 * 0.25 * 64 * 64
    # values at 2 bytes, one bit per weight, bf16 activations in and out
    assert w["bytes"] == 0.25 * 4096 * 2 + 4096 / 8 + 16 * (64 + 64) * 2
    up = next(p for p in counters.projections(MOE) if p["name"] == "w_up")
    w = counters.call_work(up, 1, MOE, 0.75)
    # one row routes to 2 experts: both experts' weights, 2 rows of work
    assert w["flops"] == 2 * 2 * 0.25 * 64 * 32
    assert w["bytes"] == 2 * (0.25 * 2048 * 2 + 2048 / 8) + 2 * 96 * 2
    w = counters.call_work(up, 16, MOE, 0.75)
    assert w["bytes"] == 5 * (0.25 * 2048 * 2 + 2048 / 8) + 32 * 96 * 2


def test_step_least_time_is_the_larger_bound_per_call():
    calls = [(p, counters.call_work(p, 16, DENSE, 0.75))
             for p in counters.projections(DENSE)]
    every = {(p["comp"], p["name"]) for p, _ in calls}
    t = counters.step_least_time(DENSE, 0.75, 16, every, PEAK)
    want = sum(p["reps"] * max(w["flops"] / 1e12, w["bytes"] / 1e9)
               for p, w in calls)
    assert t["least_s"] == pytest.approx(want)
    assert t["hbm_bound_s"] == pytest.approx(want)   # M=16: bytes bound
    # a tensor the program serves dense (here the head) drops out
    t2 = counters.step_least_time(DENSE, 0.75, 16, every - {("lm", "head")},
                                  PEAK)
    head = counters.call_work(calls[-1][0], 16, DENSE, 0.75)
    assert t["least_s"] - t2["least_s"] == pytest.approx(
        max(head["flops"] / 1e12, head["bytes"] / 1e9))
    # at prefill's 512 rows the big projections turn compute-bound
    t3 = counters.step_least_time(DENSE, 0.75, 512, every,
                                  {"bf16_flops": 1e9, "hbm_bytes_s": 1e12})
    assert t3["hbm_bound_s"] < t3["least_s"]


def test_prefill_positions_leave_the_head_out():
    head = 2 * 64 * 256
    assert counters.token_flops(DENSE, 0.75) - \
        counters.token_flops(DENSE, 0.75, head=False) == head


def test_roofline_counts_live_rows_by_hand():
    """The reader counts each call at the rows the harness saw live, and
    a prefill call without the LM head.  Bytes bound every call here.
    Per layer the pruned weights are 36864 at 0.25 x 2 B + 1/8 B = 23040
    B, and the activations (K + N summed: 1024) move 2048 B per row; the
    dense head is 16384 x 2 B + 2048 B of bitmap = 34816 B plus 640 B per
    row."""
    from types import SimpleNamespace
    from bench import harness
    decode_m3 = 2 * (23040 + 2048 * 3) + 34816 + 640 * 3
    prefill_m5 = 2 * (23040 + 2048 * 5)
    assert (decode_m3, prefill_m5) == (95104, 66560)
    least = (decode_m3 + prefill_m5) / PEAK["hbm_bytes_s"]
    every = {(p["comp"], p["name"]) for p in counters.projections(DENSE)}
    run = SimpleNamespace(
        model=DENSE, sparsity=0.75, kernel_tensors=every, peak=PEAK,
        calls=SimpleNamespace(decode_rows=[3, 0], prefill_rows=[5]),
        reduction=SimpleNamespace(kernels={"bitmap_spmm": 10 * least}))
    read = harness._reader("bitmap_spmm_roofline")
    assert read(run) == pytest.approx(10.0)
    # padding is not work: the same calls counted at a 4-slot decode and
    # a 4 x 8-row prefill would read higher
    run.calls = SimpleNamespace(decode_rows=[4, 4], prefill_rows=[32])
    assert read(run) > 10.0
