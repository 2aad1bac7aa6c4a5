import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real step
function with ShapeDtypeStruct inputs (no allocation), compiles, and
records memory_analysis / cost_analysis / collective traffic to
``results/dryrun/<arch>__<shape>__<mesh>.json`` for the roofline report.

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod|--both]
"""
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (SHAPES, cells, get_config,       # noqa: E402
                           shape_supported)
from repro.launch import sharding as shd                    # noqa: E402
from repro.launch.hlo_counters import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_tag  # noqa: E402
from repro.launch.specs import (decode_input_specs,         # noqa: E402
                                train_batch_specs)
from repro.launch.steps import (build_prefill_logits_step,  # noqa: E402
                                build_serve_step, build_train_step)
from repro.models.model import param_structs                # noqa: E402
from repro.train.optimizer import OptConfig                 # noqa: E402


def _opt_structs(cfg):
    ps = param_structs(cfg)
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jax.numpy.float32)
    return {"m": jax.tree.map(f32, ps), "v": jax.tree.map(f32, ps),
            "step": jax.ShapeDtypeStruct((), jax.numpy.int32)}


def lower_cell(arch: str, shape_name: str, mesh, *,
               sparse_override=None, accum_steps: int = 1):
    """Build and lower one cell; returns (lowered, meta)."""
    cfg = get_config(arch)
    if sparse_override is not None:
        cfg = sparse_override(cfg)
    shape = SHAPES[shape_name]
    pspecs = shd.param_specs(cfg, mesh, serve=shape.kind == "decode")
    psh = shd.named(mesh, pspecs)
    params = param_structs(cfg)

    if shape.kind == "train":
        step = build_train_step(cfg, OptConfig(), accum_steps=accum_steps)
        batch = train_batch_specs(cfg, shape)
        bspec_fn = shd.batch_specs(cfg, mesh, shape.global_batch)
        bsh = {k: NamedSharding(mesh, bspec_fn(k)) for k in batch}
        osh = shd.named(mesh, shd.opt_specs(cfg, mesh))
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(psh, osh, bsh),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1),
            ).lower(params, _opt_structs(cfg), batch)
    elif shape.kind == "prefill":
        step = build_prefill_logits_step(cfg)
        batch = train_batch_specs(cfg, shape)
        batch.pop("targets")
        bspec_fn = shd.batch_specs(cfg, mesh, shape.global_batch)
        bsh = {k: NamedSharding(mesh, bspec_fn(k)) for k in batch}
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, in_shardings=(psh, bsh), out_shardings=None,
            ).lower(params, batch)
    else:  # decode
        # NOTE (§Perf iter 5, refuted-on-CPU): storing serving weights in
        # bf16 *increases* the CPU-lowered byte count because XLA:CPU has
        # no native bf16 and re-expands every bf16 dot through f32
        # converts; on TPU bf16 storage is a strict win. Weight-store
        # dtype is therefore excluded from the CPU dry-run A/B and the
        # analytic weight term assumes 2 B/weight (EXPERIMENTS §Perf).
        step = build_serve_step(cfg)
        specs = decode_input_specs(cfg, shape)
        shard_seq = shape.global_batch == 1
        csh = shd.named(mesh, shd.cache_specs(
            cfg, mesh, shape.global_batch, shape.seq_len,
            shard_seq=shard_seq))
        bspec_fn = shd.batch_specs(cfg, mesh, shape.global_batch)
        tok_sh = NamedSharding(mesh, bspec_fn("tokens"))
        emb_sh = NamedSharding(mesh, bspec_fn("embeds"))
        pos_sh = NamedSharding(mesh, P())
        with jax.set_mesh(mesh):
            if "embeds" in specs:
                lowered = jax.jit(
                    lambda p, c, e, pos: step(p, c, None, pos, embeds=e),
                    in_shardings=(psh, csh, emb_sh, pos_sh),
                    out_shardings=None, donate_argnums=(1,),
                ).lower(params, specs["cache"], specs["embeds"],
                        specs["pos"])
            else:
                lowered = jax.jit(
                    step, in_shardings=(psh, csh, tok_sh, pos_sh),
                    out_shardings=None, donate_argnums=(1,),
                ).lower(params, specs["cache"], specs["tokens"],
                        specs["pos"])
    return lowered, cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun", verbose: bool = True,
             sparse_override=None, tag: str = "",
             accum_steps: int = 1) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, cfg = lower_cell(arch, shape_name, mesh,
                              sparse_override=sparse_override,
                              accum_steps=accum_steps)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": repr(e)}
    t0 = time.time()
    counters = hlo_analyze(compiled.as_text())
    t_analyze = time.time() - t0

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag(mesh),
        "multi_pod": multi_pod,
        "num_devices": int(mesh.devices.size),
        # while-aware per-device counters (see hlo_counters.py)
        "flops_per_device": counters["flops"],
        "hbm_bytes_per_device": counters["bytes"],
        "collectives": {k: v for k, v in counters.items()
                        if k not in ("flops", "bytes")},
        # raw XLA cost analysis kept for reference (while bodies ×1)
        "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))
                              and not k.startswith("utilization")},
        "memory_analysis": mem_d,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "analyze_s": round(t_analyze, 2),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape_name}__{mesh_tag(mesh)}{tag}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        print(f"[OK] {arch:22s} {shape_name:12s} mesh={rec['mesh']:8s} "
              f"flops/dev={rec['flops_per_device']:.3e} "
              f"hbm/dev={rec['hbm_bytes_per_device']:.3e} "
              f"wire={counters['wire_bytes']:.3e} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single- and multi-pod meshes")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    todo = []
    if args.all:
        todo = [(a, s) for a, s, _ in cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        ok, reason = shape_supported(args.arch, args.shape)
        if not ok:
            print(f"[SKIP] {args.arch} {args.shape}: {reason}")
            return
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both else [args.multi_pod]
    failures = []
    for arch, shape in todo:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, out_dir=args.out)
            except Exception:
                failures.append((arch, shape, mp))
                print(f"[FAIL] {arch} {shape} multi_pod={mp}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print("dry-run complete")


if __name__ == "__main__":
    main()
