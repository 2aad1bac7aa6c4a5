"""Trace reduction on a small synthetic ``.xplane.pb`` with hand-worked
answers (times in microseconds below; the file holds picoseconds)."""
import os

import pytest

from bench import trace_reduce

US = 1_000_000   # picoseconds per microsecond


def _ev(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _plane(pid, name, lines, names):
    meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: \"{n}\" }} }}" for i, n in names.items())
    body = " ".join(f"lines {{ id: {i} name: \"{ln}\" timestamp_ns: 0 "
                    + " ".join(_ev(*e) for e in evs) + " }"
                    for i, (ln, evs) in enumerate(lines, 1))
    return f"planes {{ id: {pid} name: \"{name}\" {body} {meta} }}"


DEVICE = _plane(1, "/device:TPU:0", [
    ("XLA Modules", [(1, 10, 30), (2, 50, 30), (1, 85, 10), (1, 150, 10)]),
    ("XLA Ops", [(3, 10, 10), (4, 15, 15), (3, 50, 10), (5, 60, 20), (8, 84, 12), (7, 85, 10),
                 (3, 150, 10)]),
], {1: "jit_serve_step(11)", 2: "jit_prefill_step(12)",
    3: "%bitmap_spmm.1 = bf16[16,8192] custom-call(bf16[16,2048] %fusion.2)",
    4: "%fusion.3 = bf16[16,8192] fusion(bf16[16,8192] %bitmap_spmm.1)",
    5: "gather.7", 7: "copy.1",
    8: "%while.4 = (s32[]) while(s32[] %p)"})
HOST = _plane(2, "/host:CPU", [
    ("python", [(1, 5, 100), (2, 8, 34), (3, 42, 4), (2, 46, 54),
                (4, 100, 5)]),
], {1: "bench.window", 2: "bench.step", 3: "bench.submit",
    4: "bench.wait_arrival"})


@pytest.fixture
def trace_dir(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(DEVICE + HOST)
    d = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(d)
    (d / "host.xplane.pb").write_bytes(raw)
    return str(tmp_path)


def test_reduction_by_hand(trace_dir):
    r = trace_reduce.reduce(trace_reduce.load(trace_dir),
                            kernels=("bitmap_spmm",))
    # window: the bench.window span, 5..105 us
    assert r.window_s == pytest.approx(100e-6)
    # busy: [10, 30) U [50, 80) U [84, 96) = 62 us; idle 38 %
    assert r.busy_s == pytest.approx(62e-6)
    assert r.idle_share == pytest.approx(0.38)
    # programs wholly inside the window; the one at 150 us is not
    assert r.programs["serve_step"] == pytest.approx([30e-6, 10e-6])
    assert r.programs["prefill_step"] == pytest.approx([30e-6])
    # bitmap_spmm by its own name (10 + 10), not the fusion whose HLO
    # text reads its output, nor the one at 150 us outside the window
    assert r.kernels["bitmap_spmm"] == pytest.approx(20e-6)
    assert r.kernel_calls["bitmap_spmm"] == 2
    # operations that hold others (the while around copy.1) are left out
    ops = dict(r.device_ops)
    assert ops == pytest.approx({"bitmap_spmm": 20e-6, "gather": 20e-6,
                                 "fusion": 15e-6, "copy": 10e-6})
    # idle gaps, longest first, named by the innermost bench span at
    # their midpoint: [30, 50) in a step, [96, 105) waiting for an
    # arrival, [5, 10) before any step, [80, 84) in a step
    assert [(n, round(s * 1e6, 6)) for n, s in r.idle_gaps] == [
        ("bench.step", 20.0), ("bench.wait_arrival", 9.0),
        ("no bench span", 5.0), ("bench.step", 4.0)]


def test_window_falls_back_to_device_events(trace_dir):
    planes = trace_reduce.load(trace_dir)
    del planes["/host:CPU"]
    r = trace_reduce.reduce(planes, kernels=("bitmap_spmm",))
    assert r.window_s == pytest.approx(150e-6)      # 10 .. 160 us
    assert r.kernels["bitmap_spmm"] == pytest.approx(30e-6)


def test_no_device_plane_is_an_error(trace_dir):
    planes = trace_reduce.load(trace_dir)
    # the profiler's own custom planes are not devices
    planes["/device:CUSTOM:Megascale Trace"] = {}
    del planes["/device:TPU:0"]
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes)
