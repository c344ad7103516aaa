"""Public jit'd wrappers around the SplitQuant kernels.

`linear()` is the single entry point models use: it dispatches on the weight
leaf type (dense array vs SplitQuantTensor) and on the backend (Pallas TPU
kernel vs an XLA-fused jnp dequant-matmul — the latter also serves
CPU/dry-run, and is what serving runs today).
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp

from repro.core.splitquant import SplitQuantTensor
from .packing import pack_cids, pack_codes, unpack_cids, unpack_codes
from .splitquant_matmul import select_per_cluster, splitquant_matmul


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _xla_matmul(x, q_packed, cid_packed, recip, shift, bits: int, k: int):
    """The XLA serving path: dequantize the packed weight to x's dtype,
    selecting each element's cluster constants by a masked sum over the k
    clusters (a gather of one constant per weight element runs orders of
    magnitude slower on TPU), then one dense matmul accumulated in fp32.
    Checked against the plain-gather oracle in `ref`."""
    q = unpack_codes(q_packed, bits).astype(jnp.float32)          # (K, N)
    cid = unpack_cids(cid_packed)                                 # (K, N)
    w = (q * select_per_cluster(recip, cid, k)
         + select_per_cluster(shift, cid, k)).astype(x.dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def dequant_constants(sqt: SplitQuantTensor) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Affine dequant constants broadcast to (k, N):
    recip = 1/scale, shift = -zero/scale, so  ŵ = q·recip + shift."""
    N = sqt.q.shape[-1]
    scale = sqt.scale
    zero = sqt.zero
    if scale.ndim == 1:
        scale = jnp.broadcast_to(scale[:, None], (sqt.k, N))
        zero = jnp.broadcast_to(zero[:, None], (sqt.k, N))
    recip = 1.0 / scale
    shift = -zero / scale
    return recip.astype(jnp.float32), shift.astype(jnp.float32)


def pack_for_kernel(sqt: SplitQuantTensor):
    """(q_packed, cid_packed, recip, shift) in the kernel's layout.
    Weight must be 2-D (K, N) at runtime (in-scan slices of stacked
    tensors qualify)."""
    assert sqt.q.ndim == 2, sqt.q.shape
    qp = pack_codes(sqt.q, sqt.bits)
    cp = pack_cids(sqt.cid)
    recip, shift = dequant_constants(sqt)
    return qp, cp, recip, shift


@functools.partial(jax.jit, static_argnames=("bits", "k", "use_pallas",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def quantized_matmul(x, q_packed, cid_packed, recip, shift, *, bits: int,
                     k: int = 3, use_pallas: bool = False,
                     block_m: int = 256, block_n: int = 256,
                     block_k: int = 512, interpret: bool = False):
    """y = x · Ŵ for a packed SplitQuant weight. x: (..., K) → (..., N)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = q_packed.shape[1]
    x2 = x.reshape(-1, K)
    if not use_pallas:
        y = _xla_matmul(x2, q_packed, cid_packed, recip, shift, bits, k)
        return y.reshape(*lead, N)

    M = x2.shape[0]
    bm = min(block_m, _round_up(M, 128))
    Mp = _round_up(M, bm)
    Np = _round_up(N, block_n)
    Kp = _round_up(K, block_k)
    per_q, per_c = 8 // bits, 4
    x2 = jnp.pad(x2, ((0, Mp - M), (0, Kp - K)))
    q_packed = jnp.pad(q_packed, ((0, (Kp - K) // per_q), (0, Np - N)))
    cid_packed = jnp.pad(cid_packed, ((0, (Kp - K) // per_c), (0, Np - N)))
    # padded columns get recip=1/shift=0; padded rows contribute q=qmin codes
    # times x=0 rows — but K-padding adds x zeros, so products vanish anyway.
    recip = jnp.pad(recip, ((0, 0), (0, Np - N)), constant_values=1.0)
    shift = jnp.pad(shift, ((0, 0), (0, Np - N)))
    y = splitquant_matmul(x2, q_packed, cid_packed, recip, shift, bits=bits,
                          k=k, block_m=bm, block_n=block_n, block_k=block_k,
                          interpret=interpret)
    return y[:M, :N].reshape(*lead, N)


def linear(x: jnp.ndarray, w: Union[jnp.ndarray, SplitQuantTensor],
           b=None, *, use_pallas: bool = False, interpret: bool = False):
    """Dense layer with transparent SplitQuant dispatch.

    NOTE (K-padding correctness): with use_pallas, padded K rows of the
    packed weight dequantize to  qmin·recip + shift ≠ 0, but the matching x
    columns are zero-padded so the extra products are exactly 0.

    A SplitQuantTensor's whole product, the per-call packing included,
    runs under the name ``dequant_matmul`` (op_name metadata only), so a
    profiler trace gives its device time.
    """
    if isinstance(w, SplitQuantTensor):
        with jax.named_scope("dequant_matmul"):
            if w.q.ndim != 2:
                wx = w.dequantize()
                y = jnp.dot(x, wx.astype(x.dtype))
            else:
                qp, cp, recip, shift = pack_for_kernel(w)
                y = quantized_matmul(x, qp, cp, recip, shift, bits=w.bits,
                                     k=w.k, use_pallas=use_pallas,
                                     interpret=interpret)
    else:
        y = jnp.dot(x, w)
    if b is not None:
        bb = b.dequantize() if isinstance(b, SplitQuantTensor) else b
        y = y + bb
    return y
