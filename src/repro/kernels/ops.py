"""Public jit'd wrappers around the SplitQuant kernels.

`linear()` is the single entry point models use: it dispatches on the weight
leaf type (dense array, SplitQuantTensor, or a PackedSplitQuantTensor built
once by `pack_weights`) and on the backend (Pallas TPU kernel vs an
XLA-fused jnp dequant-matmul — the latter also serves CPU/dry-run, and is
what serving runs today).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Union

import jax
import jax.numpy as jnp

from repro.core.splitquant import SplitQuantTensor
from .packing import pack_cids, pack_codes, unpack_cids, unpack_codes
from .splitquant_matmul import select_per_cluster, splitquant_matmul


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("q_packed", "cid_packed", "recip", "shift"),
                   meta_fields=("bits", "k", "orig_shape", "orig_dtype"))
@dataclasses.dataclass
class PackedSplitQuantTensor:
    """A 2-D-per-matrix SplitQuantTensor in the layout the dequant-matmul
    reads (`pack_for_kernel`), with the stack axes leading: a (*stack, K, N)
    weight holds (*stack, K·bits/8, N) packed codes, (*stack, K/4, N)
    packed cluster ids and (*stack, k, N) fp32 ``recip``/``shift``, so a
    layer scan's slice is exactly the operand `quantized_matmul` takes."""

    q_packed: jnp.ndarray
    cid_packed: jnp.ndarray
    recip: jnp.ndarray
    shift: jnp.ndarray
    bits: int
    k: int
    orig_shape: tuple
    orig_dtype: object

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in (self.q_packed, self.cid_packed,
                                      self.recip, self.shift))

    def dequantize(self) -> jnp.ndarray:
        """The dense ŵ the dequant-matmul multiplies by, in orig_dtype."""
        fn = functools.partial(_dequant_packed, bits=self.bits, k=self.k,
                               dtype=self.orig_dtype)
        for _ in range(self.q_packed.ndim - 2):
            fn = jax.vmap(fn)
        return fn(self.q_packed, self.cid_packed, self.recip, self.shift)


#: the weight leaves `linear` runs through the dequant-matmul
QUANTIZED = (SplitQuantTensor, PackedSplitQuantTensor)


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _dequant_packed(q_packed, cid_packed, recip, shift, *, bits: int,
                    k: int, dtype):
    """(K, N) ŵ = q·recip[cid] + shift[cid] in ``dtype``, each element's
    cluster constants selected by a masked sum over the k clusters (a
    gather of one constant per weight element runs orders of magnitude
    slower on TPU)."""
    q = unpack_codes(q_packed, bits).astype(jnp.float32)          # (K, N)
    cid = unpack_cids(cid_packed)                                 # (K, N)
    return (q * select_per_cluster(recip, cid, k)
            + select_per_cluster(shift, cid, k)).astype(dtype)


def _xla_matmul(x, q_packed, cid_packed, recip, shift, bits: int, k: int):
    """The XLA serving path: dequantize the packed weight to x's dtype,
    then one dense matmul accumulated in fp32. Checked against the
    plain-gather oracle in `ref`."""
    w = _dequant_packed(q_packed, cid_packed, recip, shift, bits=bits, k=k,
                        dtype=x.dtype)
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


def pack_weight(sqt: SplitQuantTensor) -> PackedSplitQuantTensor:
    """`pack_for_kernel` over every matrix of a (possibly stacked)
    SplitQuantTensor whose per-matrix shape is 2-D."""
    assert len(sqt.orig_shape) == 2, sqt.orig_shape
    fn = pack_for_kernel
    for _ in range(sqt.stack_dims):
        fn = jax.vmap(fn)
    qp, cp, recip, shift = fn(sqt)
    return PackedSplitQuantTensor(qp, cp, recip, shift, bits=sqt.bits,
                                  k=sqt.k, orig_shape=sqt.orig_shape,
                                  orig_dtype=sqt.orig_dtype)


_pack_weight_jit = jax.jit(pack_weight)


def pack_weights(params):
    """Pack every SplitQuantTensor of ``params`` whose per-matrix shape is
    2-D, once, into the layout the dequant-matmul reads (a serving step
    then reads 0.5 B a weight element at INT2 and repacks nothing); every
    other leaf is left as it is. Returns the tree and (packed leaves,
    their bytes, quantized leaves left unpacked)."""
    is_sqt = lambda leaf: isinstance(leaf, SplitQuantTensor)
    leaves, treedef = jax.tree_util.tree_flatten(params, is_leaf=is_sqt)
    n_packed = n_bytes = n_left = 0
    for i, leaf in enumerate(leaves):
        if not is_sqt(leaf):
            continue
        if len(leaf.orig_shape) != 2:
            n_left += 1
            continue
        leaves[i] = _pack_weight_jit(leaf)
        n_packed += 1
        n_bytes += leaves[i].nbytes
    return (jax.tree_util.tree_unflatten(treedef, leaves),
            (n_packed, n_bytes, n_left))


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


def linear(x: jnp.ndarray,
           w: Union[jnp.ndarray, SplitQuantTensor, PackedSplitQuantTensor],
           b=None, *, use_pallas: bool = False, interpret: bool = False):
    """Dense layer with transparent SplitQuant dispatch.

    NOTE (K-padding correctness): with use_pallas, padded K rows of the
    packed weight dequantize to  qmin·recip + shift ≠ 0, but the matching x
    columns are zero-padded so the extra products are exactly 0.

    A 2-D SplitQuantTensor is packed on every call; a
    PackedSplitQuantTensor (`pack_weights`) goes straight to the matmul.
    A quantized weight's whole product, any packing included, runs under
    the name ``dequant_matmul`` (op_name metadata only), so a profiler
    trace gives its device time.
    """
    if isinstance(w, QUANTIZED):
        with jax.named_scope("dequant_matmul"):
            if isinstance(w, SplitQuantTensor) and \
                    w.q.ndim == 2 == len(w.orig_shape):
                w = pack_weight(w)
            if isinstance(w, PackedSplitQuantTensor) and \
                    w.q_packed.ndim == 2:
                y = quantized_matmul(x, w.q_packed, w.cid_packed, w.recip,
                                     w.shift, bits=w.bits, k=w.k,
                                     use_pallas=use_pallas,
                                     interpret=interpret)
            else:
                y = jnp.dot(x, w.dequantize().astype(x.dtype))
    else:
        y = jnp.dot(x, w)
    if b is not None:
        bb = b.dequantize() if isinstance(b, QUANTIZED) else b
        y = y + bb
    return y
