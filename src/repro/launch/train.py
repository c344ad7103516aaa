"""End-to-end distributed training driver.

Usage (single host / CI):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real fleet this same driver runs under the cluster launcher with one
process per host; the mesh adapts to jax.devices() (elastic), checkpoints
are host-sharded, and restart resumes from the last atomic step.
"""
from __future__ import annotations

import argparse
import functools

import jax

from repro.configs import get_arch
from repro.data import DataConfig, Prefetcher, synthetic_lm_batch
from repro.models import get_model
from repro.optim import adamw
from repro.runtime import train_loop
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh, make_production_mesh
from .shardings import batch_shardings, opt_shardings, param_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compress", default=None, choices=[None, "int8"])
    ap.add_argument("--opt-dtype", default="float32")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    key = jax.random.PRNGKey(0)

    opt_cfg = adamw.OptConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=min(100, args.steps // 10 + 1),
                              state_dtype=args.opt_dtype,
                              grad_compress=args.grad_compress)

    def loss_fn(p, b):
        return model.loss_fn(p, cfg, b, remat=True)

    with jax.set_mesh(mesh):
        params = model.init(key, cfg)
        p_sh = param_shardings(params, mesh)
        params = jax.device_put(params, p_sh)
        opt_state = adamw.init(opt_cfg, params)
        o_sh = opt_shardings(opt_state, p_sh, mesh)
        opt_state = jax.device_put(opt_state, o_sh)

        dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                        global_batch=args.batch)
        sample = synthetic_lm_batch(dc, 0)
        b_sh = batch_shardings(sample, mesh)

        step_fn = jax.jit(train_loop.make_train_step(loss_fn, opt_cfg),
                          in_shardings=(p_sh, o_sh, b_sh),
                          donate_argnums=(0, 1))

        def make_batch(step):
            return jax.device_put(synthetic_lm_batch(dc, step), b_sh)

        pre = Prefetcher(make_batch, 0, depth=2)
        lc = train_loop.TrainLoopConfig(
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every)
        params, opt_state, hist = train_loop.run(
            lc, step_fn, params, opt_state, pre.get)
        pre.stop()
        print(f"final loss {hist[-1]['loss']:.4f} "
              f"(start {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
