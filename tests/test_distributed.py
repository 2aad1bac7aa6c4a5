"""SPMD correctness: sharded == single-device numerics (subprocess, 8 fake
devices), sharding-rule validity, HLO counters vs analytic ground truth."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch import sharding as shd
from repro.launch.hlo_counters import analyze
from repro.models.model import cache_structs, param_structs


def test_param_specs_are_valid_everywhere():
    """Every spec must divide its dim on the production mesh — the _fit
    fallback guarantees it; verify across all 10 archs."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch in ARCHS:
        cfg = get_config(arch)
        specs = shd.param_specs(cfg, FakeMesh())
        structs = param_structs(cfg)
        for (path, spec), leaf in zip(
                jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: hasattr(x, "index"))[0],
                jax.tree.leaves(structs)):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is not None:
                    assert dim % FakeMesh.shape[ax] == 0, (arch, path, spec)


def test_matrix_params_are_model_sharded():
    """TP must actually shard the big matrices (not fall back to full
    replication): for every arch, >60 % of matrix param bytes carry a
    'model' axis."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch in ARCHS:
        cfg = get_config(arch)
        specs = shd.param_specs(cfg, FakeMesh())
        structs = param_structs(cfg)
        tot, sharded = 0, 0
        for spec, leaf in zip(
                jax.tree.leaves(specs,
                                is_leaf=lambda x: hasattr(x, "index")),
                jax.tree.leaves(structs)):
            if len(leaf.shape) < 2:
                continue
            import math
            b = math.prod(leaf.shape)
            tot += b
            if "model" in tuple(spec):
                sharded += b
        assert sharded / tot > 0.6, (arch, sharded / tot)


def test_cache_specs_cover_all_entries():
    for arch in ("gemma3-12b", "jamba-v0.1-52b", "rwkv6-3b"):
        cfg = get_config(arch)

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        specs = shd.cache_specs(cfg, FakeMesh(), batch=128, max_len=1024)
        structs = cache_structs(cfg, 128, 1024)
        assert jax.tree.structure(
            jax.tree.map(lambda x: 0, specs,
                         is_leaf=lambda x: hasattr(x, "index"))
        ) == jax.tree.structure(jax.tree.map(lambda x: 0, structs))


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp, json
    from repro.configs import get_smoke_config
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.models import init_params
    from repro.train import optimizer as opt_lib

    cfg = get_smoke_config("%ARCH%")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = opt_lib.init(params)
    r = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(r.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32),
             "targets": jnp.asarray(r.integers(0, cfg.vocab_size, (8, 16)),
                                    jnp.int32)}
    step = build_train_step(cfg, opt_lib.OptConfig(lr=1e-3, warmup_steps=1))

    # single device
    p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

    # 2x4 (data, model) mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    psh = shd.named(mesh, shd.param_specs(cfg, mesh))
    osh = shd.named(mesh, shd.opt_specs(cfg, mesh))
    bspec = shd.batch_specs(cfg, mesh, 8)
    with jax.set_mesh(mesh):
        pd = jax.device_put(params, psh)
        od = jax.device_put(opt_state, osh)
        bd = {k: jax.device_put(v, jax.NamedSharding(mesh, bspec(k)))
              for k, v in batch.items()}
        p2, o2, m2 = jax.jit(step, in_shardings=(psh, osh, None),
                             out_shardings=(psh, osh, None))(pd, od, bd)
    diff = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, p2)))
    print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                      "max_param_diff": diff}))
""")


@pytest.mark.parametrize("arch", ["olmo-1b", "moonshot-v1-16b-a3b",
                                  "jamba-v0.1-52b"])
def test_spmd_matches_single_device(arch):
    """DP2 x TP4 train step == single-device train step (numerics)."""
    script = _SPMD_SCRIPT.replace("%ARCH%", arch)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss1"] - res["loss2"]) < 1e-3, res
    assert res["max_param_diff"] < 5e-3, res


def test_hlo_counter_ground_truth():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    r = analyze(c.as_text())
    assert r["flops"] == pytest.approx(2 * 64**3 * 12)


def test_hlo_counter_collectives():
    """psum over a mesh axis shows up as all-reduce bytes x2 wire factor."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_counters import analyze
        mesh = jax.make_mesh((8,), ("x",))
        def f(a):
            return jax.lax.psum(a, "x")
        sf = jax.shard_map(f, mesh=mesh, in_specs=P("x", None),
                           out_specs=P(None))
        c = jax.jit(sf).lower(
            jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
        r = analyze(c.as_text())
        print(json.dumps(r))
    """)
    import subprocess, sys, os
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r.get("all-reduce_bytes", 0) > 0
    assert r["wire_bytes"] == pytest.approx(2 * r["all-reduce_bytes"])
