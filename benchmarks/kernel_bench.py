"""Kernel microbenchmark (paper §6 size/speed discussion): the fused
cluster-dequant matmul vs a dense bf16 matmul.

On this CPU container the Pallas TPU kernel only runs in interpret mode
(not representative), so wall-time is measured for the XLA-fused jnp path;
the structural metrics (deployed bytes, HBM-traffic ratio) are the
TPU-relevant output. Timings are µs/call, median of `reps`.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, splitquant_tensor
from repro.kernels import ops


def _time(fn, *args, reps=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def run(M=256, K=2048, N=2048, bits=4, verbose=True):
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (K, N), dtype=jnp.float32) * 0.05
    x = jax.random.normal(key, (M, K), dtype=jnp.float32)
    sq = splitquant_tensor(key, w, QuantConfig(bits=bits), k=3)
    qp, cp, recip, shift = ops.pack_for_kernel(sq)

    dense = jax.jit(lambda x, w: x @ w)
    fused = jax.jit(lambda x: ops.quantized_matmul(
        x, qp, cp, recip, shift, bits=bits, k=3))

    t_dense = _time(dense, x, w)
    t_fused = _time(fused, x)
    dense_bytes = w.size * 4
    packed_bytes = sq.nbytes_deployed()
    rows = [
        ("dense_matmul", t_dense, f"{dense_bytes/2**20:.1f}MiB weights"),
        (f"splitquant_int{bits}_fused", t_fused,
         f"{packed_bytes/2**20:.2f}MiB weights "
         f"({dense_bytes/packed_bytes:.1f}x smaller)"),
    ]
    if verbose:
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
