"""Fault-tolerant distributed training driver.

Production posture (scaled down to whatever mesh the live devices allow):
  * pjit train step with full param/opt sharding (launch/sharding.py);
  * step-atomic checkpoints every ``ckpt_every`` with async write-behind,
    auto-resume from the latest committed step (crash/preemption recovery);
  * deterministic step-indexed data (restart-safe, no replay bookkeeping);
  * optional global-L1 pruning + masked sparse training (the paper's
    technique as a training feature);
  * per-step wall/loss logging with a straggler watchdog that flags steps
    slower than ``straggler_factor``× the trailing median (on real clusters
    this feeds the controller that evicts slow hosts).

Run (CPU example):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, Prefetcher
from repro.launch import sharding as shd
from repro.launch.mesh import make_elastic_mesh
from repro.launch.steps import build_train_step
from repro.models.model import init_params
from repro.sparse.pruning import global_l1_prune, sparsity_of
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig
from repro.train import optimizer as opt_lib


def train(arch: str, smoke: bool = True, steps: int = 50, batch: int = 8,
          seq: int = 128, ckpt_dir: str | None = None, ckpt_every: int = 20,
          sparsity: float = 0.0, lr: float = 3e-4, model_parallel: int = 1,
          straggler_factor: float = 3.0, log_every: int = 1,
          seed: int = 0) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh = make_elastic_mesh(model_parallel)
    opt_cfg = OptConfig(lr=lr, total_steps=max(steps, 2),
                        warmup_steps=max(steps // 10, 1))

    params = init_params(jax.random.PRNGKey(seed), cfg)
    masks = None
    if sparsity > 0:
        params = global_l1_prune(params, sparsity)
        masks = jax.tree.map(lambda p: (p != 0).astype(p.dtype), params)
        print(f"pruned to {sparsity_of(params):.2%} sparsity")
    opt_state = opt_lib.init(params)

    start_step = 0
    if ckpt_dir:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            print(f"resuming from checkpoint step {latest}")
            state = ckpt.restore(ckpt_dir, latest,
                                 {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start_step = latest

    pspecs = shd.named(mesh, shd.param_specs(cfg, mesh))
    ospecs = shd.named(mesh, shd.opt_specs(cfg, mesh))
    params = jax.device_put(params, pspecs)
    opt_state = jax.device_put(opt_state, ospecs)

    step_fn = build_train_step(cfg, opt_cfg, prune_masks=masks)
    with jax.set_mesh(mesh):
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

        data_cfg = DataConfig(global_batch=batch, seq_len=seq, seed=seed)
        loader = Prefetcher(cfg, data_cfg, start_step=start_step)
        times: list = []
        losses: list = []
        pending_ckpt = None
        try:
            for _ in range(steps - start_step):
                step_idx, batch_np = next(loader)
                t0 = time.time()
                batch_dev = jax.tree.map(jax.numpy.asarray, batch_np)
                params, opt_state, metrics = jit_step(params, opt_state,
                                                      batch_dev)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                times.append(dt)
                losses.append(loss)
                if len(times) >= 5:
                    med = statistics.median(times[-20:])
                    if dt > straggler_factor * med:
                        print(f"[straggler] step {step_idx}: {dt:.2f}s vs "
                              f"median {med:.2f}s", flush=True)
                if step_idx % log_every == 0:
                    print(f"step {step_idx:5d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms",
                          flush=True)
                if ckpt_dir and (step_idx + 1) % ckpt_every == 0:
                    if pending_ckpt is not None:
                        pending_ckpt.join()
                    pending_ckpt = ckpt.save(
                        ckpt_dir, step_idx + 1,
                        {"params": params, "opt": opt_state}, async_=True)
        finally:
            loader.close()
            if pending_ckpt is not None:
                pending_ckpt.join()
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, {"params": params, "opt": opt_state})
    return {"final_loss": losses[-1] if losses else float("nan"),
            "losses": losses, "params": params}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()
    res = train(args.arch, smoke=args.smoke, steps=args.steps,
                batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, sparsity=args.sparsity,
                lr=args.lr, model_parallel=args.model_parallel)
    print(f"final loss: {res['final_loss']:.4f}")


if __name__ == "__main__":
    main()
