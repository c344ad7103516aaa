"""End-to-end training driver: train a small LM for a few hundred steps
through the full framework stack (mesh → shardings → prefetching pipeline →
fault-tolerant loop → checkpoints), then SplitQuant-quantize the result and
compare INT4 serving logits against fp32.

Default is a ~5M-param model so CPU finishes in a couple of minutes;
``--full`` trains the ~100M-param variant (use on real accelerators).

    PYTHONPATH=src python examples/train_tiny.py --steps 200
"""
import argparse
import dataclasses
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import QuantConfig, QuantPolicy, quantize_tree  # noqa: E402
from repro.data import DataConfig, Prefetcher, synthetic_lm_batch  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.shardings import (batch_shardings, opt_shardings,  # noqa: E402
                                    param_shardings)
from repro.models import get_model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import train_loop  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true",
                    help="~100M params instead of ~5M")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get_arch("stablelm-1.6b").reduced()
    if args.full:
        cfg = dataclasses.replace(cfg, n_layers=12, d_model=768, n_heads=12,
                                  n_kv_heads=12, d_ff=2048, vocab=32768)
    model = get_model(cfg)
    mesh = make_local_mesh()
    key = jax.random.PRNGKey(0)
    opt_cfg = adamw.OptConfig(lr=1e-3, total_steps=args.steps,
                              warmup_steps=20)

    with jax.set_mesh(mesh), tempfile.TemporaryDirectory() as ckpt_dir:
        params = model.init(key, cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"model: {n/1e6:.1f}M params, mesh {dict(mesh.shape)}")
        p_sh = param_shardings(params, mesh)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(
            adamw.init(opt_cfg, params),
            opt_shardings(adamw.init(opt_cfg, params), p_sh, mesh))
        dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                        global_batch=args.batch)
        b_sh = batch_shardings(synthetic_lm_batch(dc, 0), mesh)
        step_fn = jax.jit(
            train_loop.make_train_step(
                lambda p, b: model.loss_fn(p, cfg, b, remat=True), opt_cfg),
            in_shardings=(p_sh, opt_shardings(opt_state, p_sh, mesh), b_sh),
            donate_argnums=(0, 1))
        pre = Prefetcher(lambda s: jax.device_put(
            synthetic_lm_batch(dc, s), b_sh), 0)
        lc = train_loop.TrainLoopConfig(total_steps=args.steps,
                                        ckpt_dir=ckpt_dir, ckpt_every=50,
                                        log_every=25)
        params, opt_state, hist = train_loop.run(lc, step_fn, params,
                                                 opt_state, pre.get)
        pre.stop()
        print(f"\nloss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
              f"over {len(hist)} steps")

        # quantized serving comparison
        batch = synthetic_lm_batch(dc, 999)
        ref = model.forward(params, cfg, {"tokens": batch["tokens"]})[0]
        for method in ("baseline", "splitquant"):
            qp, rep = quantize_tree(key, params, QuantPolicy(
                cfg=QuantConfig(bits=4), method=method))
            q = model.forward(qp, cfg, {"tokens": batch["tokens"]})[0]
            agree = float(jnp.mean((jnp.argmax(q, -1) ==
                                    jnp.argmax(ref, -1)).astype(jnp.float32)))
            print(f"INT4 {method:11s}: top-1 agreement with fp32 = "
                  f"{agree:.1%} (deployed {rep['deployed_bytes']/2**20:.1f} "
                  f"MiB vs {rep['orig_bytes']/2**20:.1f} MiB)")


if __name__ == "__main__":
    main()
