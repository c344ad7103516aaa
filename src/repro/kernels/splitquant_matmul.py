"""Pallas TPU kernel: fused SplitQuant dequant-matmul.

y[m, n] = Σ_k x[m, k] · ( q[k, n] · recip[cid[k,n], n] + shift[cid[k,n], n] )

Design (DESIGN.md §2): the paper's three split layers are realized as one
dense matmul whose weight tile is dequantized on the fly in VMEM with
cluster-indexed scales. Packed low-bit codes (2/4/8-bit) and 2-bit cluster
ids are staged HBM→VMEM as uint8, unpacked to int, scaled per cluster on the
VPU, then fed to the MXU in the input dtype with fp32 accumulation.

VMEM budget per grid step (defaults bm=bn=256, bk=512, bf16 x):
  x tile 256·512·2 = 256 KiB, packed q 512/4·256 = 32 KiB (int2),
  cid 512/4·256 = 32 KiB, w tile 512·256·2 = 256 KiB, acc 256·256·4 = 256 KiB
  → ~0.9 MiB ≪ 16 MiB VMEM; MXU dims all multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packing import unpack_cids, unpack_codes


def select_per_cluster(vals: jnp.ndarray, cid: jnp.ndarray,
                       k: int) -> jnp.ndarray:
    """vals: (k, N) per-cluster constants; cid: (K, N) → (K, N).
    k is static and tiny (≤4), so an unrolled masked sum beats a gather:
    no dynamic addressing on the VPU, and in XLA an elementwise fusion
    where a per-element gather runs orders of magnitude slower on TPU.
    The ids are widened to int32 and each mask becomes an f32 multiplier:
    Mosaic cannot relayout the i1 mask a uint8 compare yields into a
    `where` against a broadcast row."""
    cid = cid.astype(jnp.int32)
    out = jnp.zeros(cid.shape, jnp.float32)
    for c in range(k):
        out = out + (cid == c).astype(jnp.float32) * vals[c:c + 1, :]
    return out


def _kernel(x_ref, qp_ref, cp_ref, recip_ref, shift_ref, o_ref, acc_ref,
            *, bits: int, k: int, n_ksteps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = unpack_codes(qp_ref[...], bits).astype(jnp.float32)      # (bk, bn)
    cid = unpack_cids(cp_ref[...])                                # (bk, bn)
    recip = select_per_cluster(recip_ref[...], cid, k)
    shift = select_per_cluster(shift_ref[...], cid, k)
    w = (q * recip + shift).astype(x_ref.dtype)                  # dequant in VMEM
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_ksteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "block_m", "block_n", "block_k", "interpret"))
def splitquant_matmul(x: jnp.ndarray, q_packed: jnp.ndarray,
                      cid_packed: jnp.ndarray, recip: jnp.ndarray,
                      shift: jnp.ndarray, *, bits: int, k: int = 3,
                      block_m: int = 256, block_n: int = 256,
                      block_k: int = 512, interpret: bool = False
                      ) -> jnp.ndarray:
    """x: (M, K); q_packed: (K·bits/8, N) uint8; cid_packed: (K/4, N) uint8;
    recip/shift: (k, N) fp32. Returns (M, N) in x.dtype.

    M, N, K must be multiples of the block sizes (ops.py pads).
    """
    M, K = x.shape
    N = q_packed.shape[1]
    per_q = 8 // bits
    per_c = 4
    assert q_packed.shape[0] * per_q == K, (q_packed.shape, K, bits)
    assert cid_packed.shape[0] * per_c == K
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        (M, N, K), (block_m, block_n, block_k))
    n_ksteps = K // block_k
    grid = (M // block_m, N // block_n, n_ksteps)

    kernel = functools.partial(_kernel, bits=bits, k=k, n_ksteps=n_ksteps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // per_q, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // per_c, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((recip.shape[0], block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((shift.shape[0], block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        # fp32 accumulator tile, persistent across the K loop
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, q_packed, cid_packed, recip, shift)
