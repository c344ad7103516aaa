"""Pallas TPU kernel: fused decode attention over the quantized slot cache.

One decode step reads the whole per-layer slot cache — this is THE
bandwidth-bound op of serving (DESIGN.md §6). Before this kernel the int8
cache was dequantized into a full-precision (N, T, Hkv, D) copy every step
and handed to dense `attend`, so HBM traffic was fp32-serving traffic PLUS
the dequant pass. Here the INT8 codes and per-chunk (scale, zero) stream
HBM→VMEM once, dequantize per sub-channel chunk in VMEM right next to the
dot product (SplitQuant §4.2 ranges finally pay for themselves at ~1.5
B/elt moved), and a flash-style online softmax accumulates across KV
chunks — no full-precision copy of the cache ever exists.

Shapes (one layer, decode S=1 per slot):
  q       (N, Hq, D)    post-RoPE queries, one token per slot
  k, v    (N, T, Hkv, D) int8 codes (mode="int8") or float (mode="fp")
  kv_pos  (N, T) int32  absolute position per time index, -1 = empty
  q_pos   (N,)   int32  per-slot current absolute position
  scales  per-entry (N, T, Hkv, C) fp32, or per-layer static (1, 1, Hkv, C)

Grid: (N slots, T / Tc chunks) — chunk index fastest, so the (m, l, acc)
online-softmax state for one slot lives in VMEM scratch across its chunk
sweep and the output block is written once at the final chunk. The kernel
sees the cache time-minor (`to_kernel_layout`: (N, Hkv, D, T)), so a block
never pads a D or C below 128 out to a lane tile. Blocks per program: q
(1, Hkv, G, D), K/V (1, Hkv, D, Tc), scales (1, Hkv, C, Tc) dynamic /
(1, Hkv, C, 1) static, kv_pos (1, 1, Tc); q_pos is a scalar-prefetch
operand in SMEM. GQA (Hq = G·Hkv) is accumulated in the grouped
(Hkv, G, ·) layout — K/V are never broadcast to Hq — and both dots batch
over Hkv with the batch axis leading in both operands. Dot operands are
fp32, as in the jnp lowering. Chunks whose kv_pos entries are all -1
(dead slots, unwritten tail) are skipped under `pl.when`: past the
validity mask they cost no flops, so a 512-deep cache with 100-deep
occupants does ~1/4 of the work. Fully-empty slots return
exact 0 (the materialized reference returns a meaningless mean-V row
there; the engine discards both).

VMEM per program (Tc=128, Hkv=32, D=64, C=4): K+V codes 2·32·64·128 =
512 KiB int8, scales 4·32·8·128·4 = 512 KiB (C pads to 8 sublanes),
q/acc ≪ 1 MiB — under budget double-buffered; Tc is the knob if D grows.

The same math ships as a pure-jnp chunked path (`use_pallas=False`, the
CPU lowering — `jax.lax.cond` gives it the same dead-chunk skip) and the
kernel itself runs under `interpret=True` as the reference fallback in
tests. Numerics match the materialize-then-`attend` path to reduction
order (same masked softmax: invalid entries get exactly-zero weight).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dequant_chunk(codes, scale, zero):
    """codes (..., H, D) int, scale/zero (..., H, C) → fp32 (..., H, D).
    Per-sub-channel-chunk affine dequant, entirely in registers/VMEM."""
    *lead, H, D = codes.shape
    C = scale.shape[-1]
    qc = codes.astype(jnp.float32).reshape(*lead, H, C, D // C)
    x = (qc - zero[..., None]) / scale[..., None]
    return x.reshape(*lead, H, D)


def _pick_kv_chunk(T: int, kv_chunk) -> int:
    """Largest divisor of T that is ≤ the requested chunk (default 128).

    T with no usable divisor (prime / awkward max_len) falls back to ONE
    chunk of T rather than a degenerate Tc=1 sweep — a T-iteration grid
    would be orders of magnitude slower than the materialized path."""
    want = min(T, 128 if kv_chunk is None else kv_chunk)
    for c in range(want, 0, -1):
        if T % c == 0:
            return c if c >= max(2, want // 8) else T
    return T


# ------------------------------------------------------------- kernel ---
def to_kernel_layout(x):
    """(..., T, H, X) → (..., H, X, T): the kernels' time-minor view.

    Time rides the 128-wide lane axis and the head dim the sublanes, so a
    head_dim or qchunks below 128 never pads a lane tile, a cache chunk is
    one (H, X, Tc) block, and both attention dots are batched over H with
    the batch axis leading in both operands (the form Mosaic lowers)."""
    return jnp.moveaxis(x, -3, -1)


def dequant_block(codes, scale, zero):
    """Kernel-layout dequant: codes (H, D, Tc) int, scale/zero (H, C, Tc)
    per entry or (H, C, 1) static → fp32 (H, D, Tc).

    The C sub-channel chunks split the SUBLANE axis (D/C rows each, a
    whole number of f32 tiles whenever D/C % 8 == 0), so the per-chunk
    constants broadcast along sublanes and time stays on the lanes — no
    lane-dim reshape. Multiplies by the reciprocal scale: one divide per
    (H, C, Tc) chunk constant instead of one per element."""
    H, D, Tc = codes.shape
    C = scale.shape[1]
    x = codes.astype(jnp.int32).astype(jnp.float32)
    x = x.reshape(H, C, D // C, Tc)
    x = (x - zero[:, :, None, :]) * (1.0 / scale)[:, :, None, :]
    return x.reshape(H, D, Tc)


def _fused_kernel(qpos_ref, q_ref, kpos_ref, k_ref, v_ref, *rest,
                  mode: str, n_chunks: int):
    if mode == "int8":
        ks_ref, kz_ref, vs_ref, vz_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    n, j = pl.program_id(0), pl.program_id(1)
    D = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kpos = kpos_ref[0]                                     # (1, Tc)
    valid = (kpos >= 0) & (kpos <= qpos_ref[n])            # causal

    @pl.when(jnp.max(valid.astype(jnp.int32)) > 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * (D ** -0.5)    # (Hkv, G, D)
        if mode == "int8":
            kc = dequant_block(k_ref[0], ks_ref[0], kz_ref[0])
            vc = dequant_block(v_ref[0], vs_ref[0], vz_ref[0])
        else:
            kc = k_ref[0].astype(jnp.float32)              # (Hkv, D, Tc)
            vc = v_ref[0].astype(jnp.float32)
        # scores (Hkv, G, Tc): batch Hkv, contract D — K never expands to Hq
        s = jax.lax.dot_general(q, kc,
                                (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        msk = valid[None]                                  # (1, 1, Tc)
        s = jnp.where(msk, s, NEG_INF)
        m_prev = m_ref[...]                                # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exactly-zero weight on invalid entries (matches the reference:
        # exp(NEG_INF - m) underflows to 0 whenever any valid entry exists)
        p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, vc,
                                 (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv            # (Hkv, G, D)
        m_ref[...] = m_new

    @pl.when(j == n_chunks - 1)
    def _flush():
        l = l_ref[...]
        o = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = o.astype(o_ref.dtype)


def _decode_attention_pallas(q, k, v, kv_pos, q_pos, scales, *, mode,
                             per_entry, kv_chunk, interpret):
    N, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    Tc = _pick_kv_chunk(T, kv_chunk)
    nc = T // Tc
    kernel = functools.partial(_fused_kernel, mode=mode, n_chunks=nc)
    kv_spec = pl.BlockSpec((1, Hkv, D, Tc), lambda n, j, qp: (n, 0, 0, j))
    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lambda n, j, qp: (n, 0, 0, 0)),
        pl.BlockSpec((1, 1, Tc), lambda n, j, qp: (n, 0, j)),
        kv_spec, kv_spec,
    ]
    args = [q.reshape(N, Hkv, G, D), kv_pos.reshape(N, 1, T),
            to_kernel_layout(k), to_kernel_layout(v)]
    if mode == "int8":
        C = scales[0].shape[-1]
        if per_entry:
            sspec = pl.BlockSpec((1, Hkv, C, Tc),
                                 lambda n, j, qp: (n, 0, 0, j))
            args += [to_kernel_layout(s) for s in scales]
        else:
            # per-layer constants (1, 1, Hkv, C) broadcast over time
            sspec = pl.BlockSpec((1, Hkv, C, 1),
                                 lambda n, j, qp: (0, 0, 0, 0))
            args += [s.reshape(1, Hkv, C, 1) for s in scales]
        in_specs += [sspec] * 4
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, nc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv, G, D),
                                   lambda n, j, qp: (n, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running max
                pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running sum
                pltpu.VMEM((Hkv, G, D), jnp.float32),      # output acc
            ]),
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(q_pos.astype(jnp.int32), *args)
    return out.reshape(N, Hq, D)


# ------------------------------------------------- jnp chunked lowering ---
def _decode_attention_jnp(q, k, v, kv_pos, q_pos, scales, *, mode,
                          per_entry, kv_chunk):
    """Same online-softmax chunk sweep in pure jnp — the CPU path. Only a
    (N, Tc, Hkv, D) chunk is ever dequantized (transient, register-sized);
    `lax.cond` skips chunks with no valid entry, mirroring the kernel's
    `pl.when` dead-chunk skip. Chunks are carved out lazily with
    `dynamic_slice` INSIDE the cond branch — only the per-chunk kv_pos row
    (N·Tc int32) is read unconditionally, so a skipped chunk's codes and
    scales never move at all (a pre-chunked scan input would copy the
    whole cache into transposed scan leaves every step)."""
    N, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    Tc = _pick_kv_chunk(T, kv_chunk)
    nc = T // Tc
    qs = (q.astype(jnp.float32) * (D ** -0.5)).reshape(N, Hkv, G, D)
    qp = q_pos.astype(jnp.int32)[:, None]                  # (N, 1)

    def step(carry, j):
        m, l, acc = carry
        t0 = j * Tc
        pos_c = jax.lax.dynamic_slice_in_dim(kv_pos, t0, Tc, 1)  # (N, Tc)
        valid = (pos_c >= 0) & (pos_c <= qp)               # (N, Tc)

        def compute(carry):
            m, l, acc = carry

            def chunk(x):                                  # (N, T, ...) →
                return jax.lax.dynamic_slice_in_dim(x, t0, Tc, 1)

            if mode == "int8":
                ks, kz = ((chunk(scales[0]), chunk(scales[1])) if per_entry
                          else (scales[0], scales[1]))
                vs, vz = ((chunk(scales[2]), chunk(scales[3])) if per_entry
                          else (scales[2], scales[3]))
                kc = _dequant_chunk(chunk(k), ks, kz)      # (N, Tc, Hkv, D)
                vc = _dequant_chunk(chunk(v), vs, vz)
            else:
                kc = chunk(k).astype(jnp.float32)
                vc = chunk(v).astype(jnp.float32)
            s = jnp.einsum("nkgd,ntkd->nkgt", qs, kc,
                           preferred_element_type=jnp.float32)
            msk = valid[:, None, None, :]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "nkgt,ntkd->nkgd", p, vc,
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        carry = jax.lax.cond(jnp.any(valid), compute, lambda c: c, carry)
        return carry, None

    m0 = jnp.full((N, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((N, Hkv, G), jnp.float32)
    a0 = jnp.zeros((N, Hkv, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  jnp.arange(nc, dtype=jnp.int32))
    o = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None],
                  0.0)
    return o.reshape(N, Hq, D).astype(q.dtype)


# ---------------------------------------------------------- entry point ---
def decode_attention(q, k, v, kv_pos, q_pos, *, k_scale=None, k_zero=None,
                     v_scale=None, v_zero=None, mode: str = "fp",
                     per_entry_scales: bool = True, kv_chunk=None,
                     use_pallas=None, interpret: bool = False):
    """Fused decode attention over one layer's slot cache (see module doc).

    mode="fp":   k/v are float; scale/zero args are ignored.
    mode="int8": k/v are int8 codes; scales are per-entry
                 (per_entry_scales=True, (N, T, Hkv, C)) or per-layer
                 static constants ((1, 1, Hkv, C)).
    use_pallas:  None = auto (Pallas on TPU, jnp chunk sweep elsewhere);
                 True with interpret=True is the reference fallback.
    Returns (N, Hq, D) in q.dtype.
    """
    if mode not in ("fp", "int8"):
        raise ValueError(f"unknown mode {mode!r}")
    N, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    scales = None
    if mode == "int8":
        scales = (k_scale, k_zero, v_scale, v_zero)
        if any(s is None for s in scales):
            raise ValueError("mode='int8' requires all four scale arrays")
        if D % k_scale.shape[-1]:
            raise ValueError(f"head_dim {D} not divisible by "
                             f"qchunks {k_scale.shape[-1]}")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return _decode_attention_pallas(
            q, k, v, kv_pos, q_pos, scales, mode=mode,
            per_entry=per_entry_scales, kv_chunk=kv_chunk,
            interpret=interpret)
    return _decode_attention_jnp(
        q, k, v, kv_pos, q_pos, scales, mode=mode,
        per_entry=per_entry_scales, kv_chunk=kv_chunk)
