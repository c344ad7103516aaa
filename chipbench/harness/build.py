"""The system under test, built from a configuration file through the
program's own path: ``model.init`` from the seed's key (one compiled
call), ``quantize_tree`` with the file's policy, and an ``Engine``."""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from .refquant import seed_key

#: configuration-file keys whose program field has another name
ALIASES = {"norm": "norm_type", "ffn": "ffn_type",
           "head_dim": "head_dim_override"}
#: program fields a file does not set: its "name" and "source" document
#: the configuration, and the program's registry entry keeps its own
NOT_COPIED = ("name", "source")


def program_keys() -> set[str]:
    """Configuration-file keys that reach the program's ArchConfig: its
    fields (but NOT_COPIED) under their own names, and ALIASES."""
    from repro.configs import ArchConfig

    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return (fields - set(NOT_COPIED)) | set(ALIASES)


def arch_config(conf: dict):
    """The program's ArchConfig for `conf["arch"]`, with every program
    field the configuration file states (a list as a tuple); `head_dim`
    sets `head_dim_override` where the ArchConfig's own head_dim
    differs. `bias` and `tie_embeddings` are False where the file leaves
    them out."""
    from repro.configs import get_arch

    given = {"bias": False, "tie_embeddings": False}
    keys = program_keys()
    for key, value in conf.items():
        if key in keys:
            given[ALIASES.get(key, key)] = \
                tuple(value) if isinstance(value, list) else value
    head_dim = given.pop("head_dim_override", None)
    cfg = dataclasses.replace(get_arch(conf["arch"]), **given)
    if head_dim is not None and cfg.head_dim != head_dim:
        cfg = dataclasses.replace(cfg, head_dim_override=head_dim)
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
