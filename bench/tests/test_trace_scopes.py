"""The split of a trace by ``serve.*`` spans and named scopes, on a small
synthetic ``.xplane.pb`` with hand-worked answers (times in microseconds
below; the file holds picoseconds), and the reading of a real trace's
HLO modules."""
import os

import pytest

from bench import trace_reduce, trace_scopes
from bench.tests import test_trace_reduce as plain
from bench.tests.test_trace_reduce import _plane

US = 1_000_000   # picoseconds per microsecond


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf wire bytes of ``(number, str | bytes)`` fields."""
    out = b""
    for num, v in fields:
        v = v.encode() if isinstance(v, str) else v
        out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _hlo_module(name, op_names):
    """An HloProto holding one computation whose instructions carry the
    given ``op_name`` metadata."""
    comp = _msg((1, "main"), *[
        (2, _msg((1, ins), (2, "op"), (7, _msg((2, op)))))
        for ins, op in op_names.items()])
    return _msg((1, _msg((1, name), (3, comp))))


def _metadata_plane(modules):
    def octal(b):
        return "".join(f"\\{c:03o}" for c in b)
    meta = " ".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: \"{name}\" "
        f"stats {{ metadata_id: 7 bytes_value: \"{octal(proto)}\" }} }} }}"
        for i, (name, proto) in enumerate(modules.items(), 1))
    return (f"planes {{ id: 9 name: \"/host:metadata\" {meta} "
            f"stat_metadata {{ key: 7 value {{ id: 7 "
            f"name: \"Hlo Proto\" }} }} }}")


ATT = "jit(serve_step)/while/body/attention/dot_general"
DEVICE = _plane(1, "/device:TPU:0", [
    ("XLA Modules", [(1, 20, 20), (2, 50, 30), (8, 85, 10), (1, 150, 10)]),
    ("XLA Ops", [(3, 20, 10), (4, 30, 8), (5, 38, 2), (6, 50, 30),
                 (7, 50, 20), (4, 70, 8), (10, 78, 2), (9, 86, 4),
                 (3, 150, 10)]),
], {1: "jit_serve_step(11)", 2: "jit_prefill_step(12)",
    3: "%bitmap_spmm.1 = bf16[16,8192] custom-call(bf16[16,2048] %p)",
    4: "%fusion.3 = bf16[16,8192] fusion(bf16[16,8192] %p)",
    5: "argmax.1", 6: "%while.4 = (s32[]) while(s32[] %p)",
    7: "convert.2", 8: "jit_keep(13)", 9: "copy.5",
    # added by the compiler after the module was kept: named through its
    # operand
    10: "%convert.9 = f32[8] convert(bf16[8] %convert.2), sharding={}"})
METADATA = _metadata_plane({
    "jit_serve_step(11)": _hlo_module("jit_serve_step", {
        "bitmap_spmm.1": ATT,
        "fusion.3": "jit(serve_step)/while/body/ffn/mul",
        "argmax.1": "jit(serve_step)/lm_head/argmax"}),
    "jit_prefill_step(12)": _hlo_module("jit_prefill_step", {
        "while.4": "jit(prefill_step)/while",
        "convert.2":
            "jit(prefill_step)/while/body/attention/while/body/convert",
        "fusion.3": "jit(prefill_step)/while/body/ffn/add"})})
HOST = _plane(2, "/host:CPU", [
    ("python", [(1, 0, 100), (2, 9, 37), (2, 46, 46), (2, 94, 20)]),
    ("engine", [
        # step 0: [10, 45)
        (10, 10, 35), (11, 10, 4), (12, 14, 4), (13, 18, 23), (14, 41, 3),
        # step 1: [46, 90)
        (10, 46, 44), (11, 46, 2), (15, 48, 4), (16, 52, 29), (12, 82, 2),
        (13, 84, 5),
        # step 2 runs past the window: neither it nor its phases count
        (10, 95, 15), (11, 95, 4)]),
], {1: "bench.window", 2: "bench.step", 10: "serve.step",
    11: "serve.schedule", 12: "serve.decode", 13: "serve.host_sync",
    14: "serve.sample", 15: "serve.prefill", 16: "serve.prefill_sync"})


def _write(tmp_path, text):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    d = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(d)
    (d / "host.xplane.pb").write_bytes(raw)
    return str(tmp_path)


@pytest.fixture
def trace_dir(tmp_path):
    return _write(tmp_path, DEVICE + HOST + METADATA)


def _split(trace_dir):
    return trace_scopes.split(
        trace_reduce.load(trace_dir),
        trace_scopes.module_op_names(trace_scopes.newest_xplane(trace_dir)))


def test_split_by_hand(trace_dir):
    s = _split(trace_dir)
    # window 0..100 us; busy [20, 40) U [50, 80) U [86, 90); idle [0, 20),
    # [40, 50), [80, 86), [90, 100)
    assert s.steps == 2
    assert {k: round(v * 1e6, 6) for k, v in s.idle_s.items()} == {
        "serve.step": 25.0,          # [10, 20) + [40, 45); [46, 50) + [80, 86)
        "serve.schedule": 6.0,       # 4 + 2
        "serve.decode": 6.0,         # [14, 18) + [82, 84)
        "serve.host_sync": 5.0,      # [18, 20) + [40, 41); [84, 86)
        "serve.sample": 3.0,         # [41, 44)
        "serve.prefill": 2.0,        # [48, 50)
        "serve.prefill_sync": 1.0}   # [80, 81)
    assert s.outside_idle_s == pytest.approx(21e-6)     # 46 - 25
    # leaf operations only (not the while around the prefill body); the
    # keep program's copy is in no module the trace holds
    assert s.scope_s == pytest.approx({"attention": 32e-6, "ffn": 16e-6,
                                       "lm_head": 2e-6, "other": 4e-6})
    assert s.inferred_s == pytest.approx(2e-6)
    assert s.unmapped_s == pytest.approx(4e-6)
    assert s.unmapped_ops == [("keep/copy (no module)", pytest.approx(4e-6))]
    assert {p: {k: round(v * 1e6, 6) for k, v in d.items()}
            for p, d in s.program_scope_s.items()} == {
        "serve_step": {"attention": 10.0, "ffn": 8.0, "lm_head": 2.0},
        "prefill_step": {"attention": 22.0, "ffn": 8.0},
        "keep": {"other": 4.0}}
    # longest first, by the innermost counted span at the midpoint:
    # [0, 20) at 10, [40, 50) at 45, [90, 100) at 95 (step 2 does not
    # count), [80, 86) at 83
    assert [(n, round(v * 1e6, 6), round(t * 1e6, 6))
            for n, v, t in s.gaps] == [
        ("serve.schedule", 20.0, 0.0), ("serve.step", 10.0, 40.0),
        ("no serve span", 10.0, 90.0), ("serve.decode", 6.0, 80.0)]
    assert s.host_gap_ms == pytest.approx(25e-3 / 2)
    assert s.attention_ms_per_step == pytest.approx(32e-3 / 2)
    lines = trace_scopes.describe(s)
    assert len(lines) == 2 and "serve.host_sync 0.0 ms" in lines[0]


def test_module_op_names_of_a_real_trace(tmp_path):
    """The field numbers read the HLO module that the profiler stores."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            return jnp.sin(x) @ x
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = trace_scopes.module_op_names(
        trace_scopes.newest_xplane(str(tmp_path)))
    (mod,) = [m for m in names if m.startswith("jit_f(")]
    assert {trace_scopes.scope_of(o) for o in names[mod].values()} \
        == {"attention", "other"}     # parameters carry no scope


def test_operands():
    assert trace_scopes._operands(
        "%copy-done = (f32[2]{0:T(8)}, u32[]) copy-done((f32[2], u32[]) "
        "%copy-start.1), calls=%f, metadata={}") == ["copy-start.1"]
    assert trace_scopes._operands("argmax.1") == []


def test_scope_of():
    assert trace_scopes.scope_of(ATT) == "attention"
    assert trace_scopes.scope_of("jit(f)/ffn/while/body/lm_head") == "ffn"
    assert trace_scopes.scope_of("jit(f)/attentions/dot") == "other"
    assert trace_scopes.scope_of("") == "other"


def test_reduction_reads_as_before(tmp_path):
    """``serve.*`` spans and the modules' metadata beside the harness's
    spans change nothing of the reduction: the existing synthetic trace
    reads the same with them added."""
    kernels = ("bitmap_spmm",)
    serve = _plane(3, "/host:CPU engine", [("engine", [
        (1, 8, 34), (2, 8, 20), (3, 30, 12), (1, 46, 54), (2, 46, 40)])],
        {1: "serve.step", 2: "serve.schedule", 3: "serve.host_sync"})
    before = trace_reduce.reduce(trace_reduce.load(
        _write(tmp_path / "a", plain.DEVICE + plain.HOST)), kernels)
    after = trace_reduce.reduce(trace_reduce.load(_write(
        tmp_path / "b", plain.DEVICE + plain.HOST + serve + METADATA)),
        kernels)
    assert after == before


def test_program_without_spans_splits_into_nothing(tmp_path):
    s = _split(_write(tmp_path, plain.DEVICE + plain.HOST))
    assert s.steps == 0 and s.idle_s == {}
    assert set(s.scope_s) == {"other"}
    assert s.host_gap_ms is None and s.attention_ms_per_step is None


def test_splitting_rides_on_the_harness_load(trace_dir):
    original = trace_reduce.load
    with trace_scopes.splitting({}) as got:
        planes = trace_reduce.load(trace_dir)
    assert trace_reduce.load is original
    assert got["steps"] == 2
    assert got["attention_ms_per_step"] == pytest.approx(0.016)
    assert planes == original(trace_dir)
