"""The system under test, built from a configuration file through the
program's own path: ``model.init`` from the seed's key (one compiled
call), ``quantize_tree`` with the file's policy, and an ``Engine``."""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from .reference import seed_key

#: configuration-file keys copied onto the program's ArchConfig
ARCH_KEYS = {"n_layers": "n_layers", "d_model": "d_model",
             "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
             "d_ff": "d_ff", "vocab": "vocab", "rope_variant": "rope_variant",
             "rope_theta": "rope_theta", "norm": "norm_type",
             "ffn": "ffn_type", "param_dtype": "param_dtype"}


def arch_config(conf: dict):
    """The program's ArchConfig for `conf["arch"]`, with every size the
    configuration file states."""
    from repro.configs import get_arch

    cfg = dataclasses.replace(
        get_arch(conf["arch"]),
        **{dst: conf[src] for src, dst in ARCH_KEYS.items()},
        bias=conf.get("bias", False),
        tie_embeddings=conf.get("tie_embeddings", False))
    if cfg.head_dim != conf["head_dim"]:
        cfg = dataclasses.replace(cfg, head_dim_override=conf["head_dim"])
    return cfg


def build_weights(cfg, conf: dict, seed: int):
    """Served weights: init in one compiled call, then `quantize_tree` one
    leaf at a time. Each call is given the whole tree with every other
    leaf replaced by an int8 scalar (which quantize_tree passes over), so
    leaf i gets the same key and the same program as in a single call on
    the whole tree, and its bfloat16 copy is freed as soon as it is done:
    the build never holds the bfloat16 model and the quantized one at
    once."""
    from repro.core import QuantConfig, QuantPolicy, quantize_tree
    from repro.core.splitquant import SplitQuantTensor
    from repro.models import get_model

    q = conf["quant"]
    policy = QuantPolicy(cfg=QuantConfig(bits=q["bits"]), k=q["k"],
                         method=q["method"])
    key = seed_key(seed)
    t = time.perf_counter()
    params = jax.jit(get_model(cfg).init, static_argnums=1)(key, cfg)
    jax.block_until_ready(params)
    times = [("init", time.perf_counter() - t)]
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    hole = jnp.zeros((), jnp.int8)
    is_sqt = lambda x: isinstance(x, SplitQuantTensor)
    out = []
    for i in range(len(leaves)):
        t = time.perf_counter()
        masked = [hole] * len(leaves)
        masked[i] = leaves[i]
        qtree, _ = quantize_tree(
            key, jax.tree_util.tree_unflatten(treedef, masked), policy)
        leaf = jax.tree_util.tree_flatten(qtree, is_leaf=is_sqt)[0][i]
        del masked, qtree
        if is_sqt(leaf):
            jax.block_until_ready(leaf)
            leaves[i] = None
            times.append((tuple(leaf.q.shape), time.perf_counter() - t))
        out.append(leaf)
    print("build seconds: " + ", ".join(f"{n} {s:.1f}" for n, s in times),
          file=sys.stderr, flush=True)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_engine(cfg, qparams, conf: dict, traffic: dict):
    """An Engine with the file's cache and prefill settings; the slots are
    as many as the file's KV-cache token budget holds at the mix's
    max_len."""
    from repro.engine import Engine, EngineConfig

    n_slots = conf["kv_cache_tokens"] // traffic["max_len"]
    return Engine(cfg, qparams, EngineConfig(
        n_slots=n_slots, max_len=traffic["max_len"], max_new_tokens=1,
        kv_mode=conf["kv_mode"], kv_qchunks=conf["kv_qchunks"],
        prefill_chunk=conf["prefill_chunk"],
        prefill_bucket=conf["prefill_bucket"]))
