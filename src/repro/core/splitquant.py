"""SplitQuant (paper §4): split each quantizable tensor into k=3
mathematically-equivalent parts with separate quantization parameters.

TPU-native representation (see DESIGN.md §2): instead of materializing the
three mostly-zero split layers, we store

  * ``q``     — low-bit codes, one per weight element (int8 storage; the
                logical width is ``bits``; the Pallas path packs them),
  * ``cid``   — the k-means cluster id per element (2 bits logically),
  * ``scale``/``zero`` — per-cluster (optionally × per-output-channel)
                quantization parameters.

Dequantization selects scale[cid] per element, so

    Ŵ = Σ_c  mask_c · dequant(q; scale_c, zero_c)

is *exactly* the paper's sum of three split layers, fused into one dense
tensor. ``split_layers`` materializes the literal paper form for the
equivalence tests.

Stacked quantization (``stack_dims``): scan-over-layers models carry
parameters with leading (L,) or (L, E) axes. Each trailing matrix is
quantized independently (vmap), giving leaves ``q/cid: (L, ..., *mat)`` and
``scale/zero: (L, ..., k[, out])`` whose *leading axes slice consistently
under jax.lax.scan* — the meta ``orig_shape`` stays the per-matrix shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kmeans import kmeans_1d
from .quantize import QuantConfig, dequantize, qparams, quantize


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("q", "cid", "scale", "zero"),
                   meta_fields=("bits", "k", "orig_shape", "orig_dtype"))
@dataclasses.dataclass
class SplitQuantTensor:
    """A tensor quantized with per-cluster scales (k=1 ⇒ plain baseline PTQ).

    orig_shape is the PER-MATRIX shape; leading stack axes (q.ndim -
    len(orig_shape) of them) are batch dims shared by q/cid/scale/zero.
    """

    q: jnp.ndarray        # int8 codes, (*stack, *orig_shape)
    cid: jnp.ndarray      # uint8 cluster ids, same shape as q
    scale: jnp.ndarray    # (*stack, k) or (*stack, k, out) fp32
    zero: jnp.ndarray     # like scale
    bits: int
    k: int
    orig_shape: tuple
    orig_dtype: object

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def stack_dims(self) -> int:
        return self.q.ndim - len(self.orig_shape)

    @property
    def per_channel(self) -> bool:
        return self.scale.ndim - self.stack_dims == 2

    def _select(self, vals: jnp.ndarray) -> jnp.ndarray:
        """vals: (*stack, k[, out]) → per-element (*stack, *orig_shape).
        A masked sum over the k clusters: elementwise, where a gather of
        one value per element runs orders of magnitude slower on TPU."""
        b = self.stack_dims
        m = len(self.orig_shape)
        stack = vals.shape[:b]
        if self.per_channel:
            v = jnp.moveaxis(vals, -2, -1)                 # (*stack, out, k)
            v = v.reshape(stack + (1,) * (m - 1) + v.shape[-2:])
        else:
            v = vals.reshape(stack + (1,) * m + (self.k,))
        cid = self.cid.astype(jnp.int32)
        return sum(jnp.where(cid == c, v[..., c], 0.0)
                   for c in range(self.k))

    def dequantize(self) -> jnp.ndarray:
        s = self._select(self.scale)
        z = self._select(self.zero)
        return dequantize(self.q, s, z, self.orig_dtype)

    def split_layers(self) -> list[jnp.ndarray]:
        """The paper's literal k split tensors: Ŵ_c = Ŵ ⊙ [cid == c]."""
        w_hat = self.dequantize()
        return [jnp.where(self.cid == c, w_hat, 0).astype(self.orig_dtype)
                for c in range(self.k)]

    def nbytes_deployed(self) -> int:
        """Deployed footprint: packed codes + 2-bit cids + scales."""
        n = self.q.size
        code_bits = self.bits * n
        cid_bits = (2 * n) if self.k > 1 else 0
        return (code_bits + cid_bits) // 8 + self.scale.nbytes + self.zero.nbytes


def _masked_range(x: jnp.ndarray, mask: jnp.ndarray, axis=None):
    """min/max of x over elements where mask, else a degenerate [0,0] range."""
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    lo = jnp.min(jnp.where(mask, x, big), axis=axis)
    hi = jnp.max(jnp.where(mask, x, -big), axis=axis)
    empty = ~jnp.any(mask, axis=axis)
    beta = jnp.where(empty, 0.0, lo)
    alpha = jnp.where(empty, 0.0, hi)
    return beta, alpha


def _splitquant_single(key, w, cfg: QuantConfig, k: int, sample_size: int,
                       kmeans_iters: int):
    """Quantize ONE matrix/vector. Returns (q, cid, scale, zero)."""
    wf = w.astype(jnp.float32)
    flat = wf.reshape(-1)

    if k == 1:
        if cfg.percentile is not None:
            # percentile clipping: range from the clipped distribution
            if cfg.per_channel and w.ndim >= 2:
                red = tuple(range(w.ndim - 1))
                beta = jnp.percentile(wf, (1 - cfg.percentile) * 100, axis=red)
                alpha = jnp.percentile(wf, cfg.percentile * 100, axis=red)
                beta, alpha = beta[None], alpha[None]          # (1, out)
            else:
                beta = jnp.percentile(wf, (1 - cfg.percentile) * 100).reshape(1)
                alpha = jnp.percentile(wf, cfg.percentile * 100).reshape(1)
            scale, zero = qparams(beta, alpha, cfg)
            cid = jnp.zeros(w.shape, jnp.uint8)
            q = quantize(wf, scale[0], zero[0], cfg)
            return q, cid, scale, zero
        cid = jnp.zeros(w.shape, jnp.uint8)
    else:
        n = flat.shape[0]
        if n > sample_size:
            stride = n // sample_size
            sample = flat[::stride][:sample_size]
        else:
            sample = flat
        centroids, _, _ = kmeans_1d(key, sample, k=k, iters=kmeans_iters)
        cid = jnp.argmin((wf[..., None] - centroids) ** 2,
                         axis=-1).astype(jnp.uint8)

    if cfg.per_channel and w.ndim >= 2:
        red = tuple(range(w.ndim - 1))
        beta, alpha = jax.vmap(
            lambda c: _masked_range(wf, cid == c, axis=red))(jnp.arange(k))
    else:
        beta, alpha = jax.vmap(
            lambda c: _masked_range(flat, cid.reshape(-1) == c))(jnp.arange(k))
    scale, zero = qparams(beta, alpha, cfg)                 # (k,) or (k, out)

    if scale.ndim == 1:
        s_el, z_el = scale[cid], zero[cid]
    else:
        out_idx = jnp.arange(w.shape[-1])
        s_el = scale[cid, out_idx]
        z_el = zero[cid, out_idx]
    q = quantize(wf, s_el, z_el, cfg)
    return q, cid, scale, zero


def splitquant_tensor(key: jax.Array, w: jnp.ndarray, cfg: QuantConfig,
                      k: int = 3, sample_size: int = 1 << 18,
                      kmeans_iters: int = 25,
                      stack_dims: int = 0) -> SplitQuantTensor:
    """Cluster ``w``'s values into k groups and quantize each with its own
    scale (paper §4.1). ``k=1`` degenerates to baseline per-tensor PTQ.

    ``stack_dims``: number of leading axes to quantize independently (vmap)
    — one matrix per layer / per expert, see class docstring.

    Large matrices: centroids are fit on ≤``sample_size`` strided samples,
    then every element is assigned to its nearest centroid — assignment (not
    the centroid fit) is what the mathematical equivalence relies on.
    """
    fn = functools.partial(_splitquant_single, cfg=cfg, k=k,
                           sample_size=sample_size, kmeans_iters=kmeans_iters)
    for _ in range(stack_dims):
        fn = jax.vmap(fn)
    lead = w.shape[:stack_dims]
    keys = jax.random.split(key, lead) if stack_dims else key
    q, cid, scale, zero = fn(keys, w)
    return SplitQuantTensor(q=q, cid=cid, scale=scale, zero=zero,
                            bits=cfg.bits, k=k,
                            orig_shape=tuple(w.shape[stack_dims:]),
                            orig_dtype=w.dtype)


def baseline_quant_tensor(w: jnp.ndarray, cfg: QuantConfig,
                          stack_dims: int = 0) -> SplitQuantTensor:
    """Plain PTQ (one scale set; percentile clip if cfg.percentile) as k=1."""
    return splitquant_tensor(jax.random.PRNGKey(0), w, cfg, k=1,
                             stack_dims=stack_dims)


def activation_chunk_bounds(n: int, n_chunks: int) -> list[int]:
    """§4.2 chunk boundaries along an axis of width ``n``: the
    ``jnp.array_split`` partition (first ``n % n_chunks`` chunks one element
    wider), so indivisible widths still split into ``n_chunks`` parts."""
    n_chunks = max(1, min(n_chunks, n))
    base, rem = divmod(n, n_chunks)
    bounds = [0]
    for c in range(n_chunks):
        bounds.append(bounds[-1] + base + (1 if c < rem else 0))
    return bounds


def split_activation_fake_quant(x: jnp.ndarray, cfg: QuantConfig,
                                n_chunks: int = 3, axis: int = -1) -> jnp.ndarray:
    """Paper §4.2: split an activation vector into ``n_chunks`` chunks,
    quantize each with its own dynamic range, concatenate. Indivisible
    widths use uneven chunks (``jnp.array_split`` semantics) so the split
    never silently degrades to a single range.

    This is simulated (fake) quantization — ranges are computed at runtime,
    exactly as an int inference engine would calibrate dynamic activations.
    """
    axis = axis % x.ndim
    parts = jnp.array_split(x, max(1, min(n_chunks, x.shape[axis])),
                            axis=axis)
    outs = []
    for p in parts:
        beta = jnp.min(p)
        alpha = jnp.max(p)
        scale, zero = qparams(beta, alpha, cfg)
        outs.append(dequantize(quantize(p, scale, zero, cfg), scale, zero,
                               x.dtype))
    return jnp.concatenate(outs, axis=axis)


def effective_scales(sqt: SplitQuantTensor) -> jnp.ndarray:
    """Per-cluster scale factors — the paper's resolution metric (§4: larger
    S ⇒ finer resolution). Useful for the range-narrowing benchmark."""
    return sqt.scale
