"""Device time of ``bitmap_spmm`` alone, per 128x128 tile, at olmo-1b's
projection and LM-head widths, read from a profiler trace.  Rows: the
benchmark cells' 16 decode slots, 128, and a prefill call's 16 x 32.

Each matrix is a 75 %-sparse (the head: dense) bf16 ``BitmapWeight`` at a
fixed value-row count Q; one packed 1024x1024 block is repeated over the
tile grid, since the kernel's work does not depend on the values.  For
each kernel variant every shape runs ``--reps`` times inside one trace,
and the summed device time of the ``bitmap_spmm`` operations over the
calls' tiles gives microseconds per tile.  Each variant's output is
checked against the dense reference first.

Variants: this checkout's kernel at each ``--unroll`` depth (the
module's ``STRIP_UNROLL``), and with ``--other PATH`` a second copy of
``kernels/bitmap_spmm.py`` (e.g. a parent commit's) timed beside it.

    PYTHONPATH=src python benchmarks/spmm_tile_time.py \\
        --other OLD/src/repro/kernels/bitmap_spmm.py --unroll 1,2,4,8 \\
        --out spmm_tile_time.json

Needs a TPU: the kernel runs compiled.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import bitmap_spmm as this_kernel
from repro.kernels import ref
from repro.sparse import pack_bitmap
from repro.sparse.format import BitmapWeight

#: olmo-1b's packed matrices, (K, N), and the value rows of each pack
SHAPES = {"wq": (2048, 2048), "w_up": (2048, 8192), "w_down": (8192, 2048),
          "head": (2048, 50304)}
Q_75 = 34      # max tile non-zeros at 25 % density over ~16k tiles, in rows
BASE = 1024    # side of the packed block repeated over the tile grid
#: the kernel's operation in the trace, named by its HLO text
#: (``%bitmap_spmm.63 = bf16[...] custom-call(...)``)
KERNEL_OP = re.compile(r"\s*%?bitmap_spmm[.\d]*\s*=")


def _load(path: str):
    spec = importlib.util.spec_from_file_location("other_bitmap_spmm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def weight(k: int, n: int, density: float, rng) -> BitmapWeight:
    w = rng.standard_normal((BASE, BASE)).astype(np.float32) * 0.02
    w *= rng.random((BASE, BASE)) < density
    q = 128 if density >= 1.0 else Q_75
    base = pack_bitmap(w.astype(jnp.bfloat16), block=(128, 128),
                       budget=q * 128)
    reps = (k // BASE, -(-n // BASE), 1, 1)
    nt = n // 128
    bits = jnp.tile(base.packed_bits, reps)[:, :nt]
    vals = jnp.tile(base.values, reps)[:, :nt]
    return BitmapWeight(packed_bits=bits, values=vals, shape=(k, n),
                        block=(128, 128))


def device_us(trace_dir: str) -> list:
    """Durations (us), in start order, of the device's bitmap_spmm ops."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs += [(e.start_ns, e.duration_ns / 1e3) for e in line.events
                        if KERNEL_OP.match(e.name)]
    return [d for _, d in sorted(evs)]


def time_variant(fn, cases, reps: int) -> dict:
    calls = {}
    for key, (x, w) in cases.items():
        calls[key] = jax.jit(fn)
        calls[key](x, w).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for key, (x, w) in cases.items():
                for _ in range(reps):
                    out = calls[key](x, w)
                out.block_until_ready()
        durs = device_us(d)
    if len(durs) != reps * len(cases):
        raise RuntimeError(f"{len(durs)} bitmap_spmm ops in the trace, "
                           f"expected {reps} x {len(cases)}")
    res = {}
    for c, (key, (x, w)) in enumerate(cases.items()):
        d = sorted(durs[c * reps:(c + 1) * reps])
        tiles = (w.shape[0] // 128) * (w.shape[1] // 128)
        res[key] = {"call_us_median": d[reps // 2], "call_us_min": d[0],
                    "us_per_tile": d[reps // 2] / tiles, "tiles": tiles}
    return res


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--other", default=None,
                   help="path of another bitmap_spmm.py to time beside")
    p.add_argument("--unroll", default=str(this_kernel.STRIP_UNROLL))
    p.add_argument("--rows", default="16,128,512")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    a = p.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {dev.platform!r}")
    rng = np.random.default_rng(a.seed)
    cases = {}
    for name, (k, n) in SHAPES.items():
        w = weight(k, n, 1.0 if name == "head" else 0.25, rng)
        for m in map(int, a.rows.split(",")):
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            cases[f"{name}.M{m}"] = (x, w)
    variants = {}
    if a.other:
        variants["other"] = _load(a.other).bitmap_spmm
    for u in map(int, a.unroll.split(",")):
        variants[f"unroll{u}"] = u
    out = {"device": dev.device_kind, "q": Q_75, "reps": a.reps,
           "variants": {}}
    for vname, v in variants.items():
        if isinstance(v, int):
            this_kernel.STRIP_UNROLL = v
            jax.clear_caches()
            fn = this_kernel.bitmap_spmm
        else:
            fn = v
        err = {}
        for key, (x, w) in cases.items():
            got = np.asarray(jax.jit(fn)(x, w), np.float32)
            want = np.asarray(ref.bitmap_spmm_ref(x, w), np.float32)
            err[key] = float(np.abs(got - want).max()
                             / max(np.abs(want).max(), 1e-30))
        res = time_variant(fn, cases, a.reps)
        for key in res:
            res[key]["max_rel_err"] = err[key]
        out["variants"][vname] = res
        for key, r in res.items():
            print(f"{vname:8s} {key:12s} {r['call_us_median']:10.1f} "
                  f"us/call {r['us_per_tile']:.4f} us/tile  "
                  f"err {r['max_rel_err']:.2e}", flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
