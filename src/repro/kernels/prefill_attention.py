"""Pallas TPU kernel: fused chunked-prefill attention over the quantized
slot cache, with the chunk's K/V written as codes.

This is the prefill-side twin of `decode_attention`. Before it, every
admitted request ran the pre-engine prefill: a dense full-precision
(L, S, Hkv, D) KV cache was materialized (`models/transformer.py:prefill`),
padded to a bucket, re-quantized, and copied into the slot cache
(`engine/kvcache.py:write_prefill`) — the last full-precision KV
materialization in serving, and the engine blocked all decoding for the
whole prompt length while it happened. Here a prompt is prefilled in
chunks: one call computes causal self-attention for a chunk of Sq prompt
tokens of ONE slot against (a) the slot's already-written cache rows
(INT8 codes dequantized per sub-channel chunk in VMEM, exactly like the
decode kernel) and (b) the chunk's own full-precision K/V. Beside the
call the chunk's K/V are quantized (SplitQuant §4.2 per-chunk ranges —
dynamic per-entry, or static per-layer scales from a calibration recipe)
so the caller scatters the CODES straight into the slot cache's storage
layout. No (L, S, Hkv, D) fp cache ever exists, and `write_prefill`'s
pad + requantize + copy disappears.

Shapes (one layer, one slot, one chunk):
  q             (Sq, Hq, D)   post-RoPE chunk queries (Sq = padded chunk)
  k_new, v_new  (Sq, Hkv, D)  post-RoPE chunk K/V, full precision
  cache_k/v     (T, Hkv, D)   the slot's rows: int8 codes or float
  kv_pos        (T,) int32    absolute position per row, -1 = empty
  pos_start     scalar        absolute position of chunk token 0
  length        scalar        valid tokens in the chunk (rest is padding)
  scales        per-entry (T, Hkv, C) fp32, or static per-layer (Hkv, C)

Grid: (Sq/Bq query blocks, T/Tc cache chunks + 1). The kernel sees the
decode kernel's time-minor layout (`decode_attention.to_kernel_layout`):
cache blocks (Hkv, D, Tc), queries as (Hkv, Bq·G, D) rows of (query,
group) pairs, the chunk's K/V as (Hkv, Sq, D), so every dot batches over
Hkv. The KV sweep (j) is fastest: each query block's (m, l, acc)
online-softmax state lives in VMEM scratch across the sweep. Iterations
j < nc stream the slot's CACHE rows — valid entries are exactly those
with 0 <= kv_pos < pos_start (everything earlier than the chunk; rows at
>= pos_start are stale or decode-parking garbage by the engine's
invariants), so no per-query causal test is needed and chunks with no
valid entry are skipped under `pl.when` (a chunk at pos_start=0 skips the
whole sweep). The final iteration j == nc attends
the chunk's own fp K/V under the intra-chunk causal mask
(key_idx <= query_idx, key_idx < length) and flushes the output block.
The chunk's codes come from the same jnp the one-shot path uses: dynamic
mode computes per-(token, head, sub-channel-chunk) (β, α) → (S, Z) with
the exact `core.quantize` eq. (1)-(3) arithmetic (codes are bit-identical
to `engine.kvcache.quantize_kv`, so chunked and one-shot prefill fill the
cache with the same bytes); static mode applies recipe constants expanded
to per-column rows through `act_quant.chunk_id_map` with the exact
fractional zero-point fold of `quantize_kv_static`.

Bytes moved per prefill token per layer (C=4, D=64, fp32 compute; see
DESIGN.md §6 for the table): the legacy path materializes 2·Hkv·D·4 B of
fp cache, re-reads it for write_prefill's quantize and writes codes
(~8 B/elt of K/V traffic plus the bucket-pad copy); the fused path moves
the chunk once into VMEM and writes 1 B/elt codes + amortized scales
(~1.5 B/elt), with prior-chunk reads scaling with the written prefix, not
with max_len.

The same math ships as a pure-jnp chunked sweep (`use_pallas=False`, the
CPU lowering, `lax.cond` dead-chunk skip) and the kernel runs under
`interpret=True` as the reference fallback in tests
(`tests/test_prefill_attention.py`).

Speculative VERIFY mode (``verify=True``, DESIGN.md §9): the same kernel
doubles as the multi-token scorer of the self-speculative decoder — a
draft window *is* a prefill chunk. The one difference is what the final
iteration attends: plain prefill attends the chunk's own K/V at full
precision (matching the legacy one-shot prefill, where the whole prompt
is scored in fp), but a verify window must reproduce PLAIN DECODE, and a
decode step writes its quantized K/V first and then attends the cache —
i.e. every token sees itself and its in-window predecessors through the
quantization round-trip. Verify mode therefore quantizes the window K/V
*first* (the identical codes that are stored) and attends the
dequantized codes under the intra-chunk causal mask; for a float cache
it round-trips through the cache dtype. Without this, int8 verify logits
would see fp intra-window K/V that plain decode never sees, and the
accept rule's token-identity guarantee would quietly break.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QuantConfig, qparams, quantize, value_range

from .decode_attention import (NEG_INF, _dequant_chunk, _pick_kv_chunk,
                               dequant_block, to_kernel_layout)

KV_QCFG = QuantConfig(bits=8, symmetric=False)


# ----------------------------------------------------------- quant math ---
def _dyn_quantize(x, C):
    """x (S, H, D) fp → (codes int8 (S, H, D), scale/zero fp32 (S, H, C)).

    The `engine.kvcache.quantize_kv` composition (value_range → qparams →
    quantize) — ONE implementation shared by both lowerings, keeping chunk
    codes bit-identical to what the one-shot `write_prefill` path stores
    by construction."""
    S, H, D = x.shape
    xc = x.astype(jnp.float32).reshape(S, H, C, D // C)
    beta, alpha = value_range(xc, axis=-1)
    scale, zero = qparams(beta, alpha, KV_QCFG)
    q = quantize(xc, scale[..., None], zero[..., None], KV_QCFG)
    return q.reshape(S, H, D), scale, zero


def _static_quantize_cols(x, scale_col, zero_col):
    """x (S, H, D) fp, scale/zero per-column (H, D) → int8 codes. The
    fractional zero-point is folded into the rounding, matching
    `quantize_kv_static` exactly (per-column expansion of even chunks is
    the identical scalar per element)."""
    q = jnp.clip(jnp.rint(scale_col * x.astype(jnp.float32) + zero_col),
                 -128, 127)
    return q.astype(jnp.int8)


def _dequant_cols(codes, scale_col, zero_col):
    """Static per-column affine dequant: (codes - Z) / S elementwise."""
    return (codes.astype(jnp.float32) - zero_col) / scale_col


# ------------------------------------------------------------- kernel ---
def _prefill_kernel(info_ref, q_ref, kpos_ref, ck_ref, cv_ref, kn_ref,
                    vn_ref, *rest, mode: str, n_cache_chunks: int,
                    groups: int):
    if mode == "int8":
        ks_ref, kz_ref, vs_ref, vz_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)
    nc = n_cache_chunks
    Hkv, M, D = q_ref.shape                  # M = Bq·G rows, (query, group)
    G = groups
    pos_start = info_ref[0]
    length = info_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # grouped (Hkv, Bq·G, ·) layout throughout — K/V never expand to Hq
    q = q_ref[...].astype(jnp.float32) * (D ** -0.5)

    def online_update(s, valid, pv_of):
        """s (Hkv, M, Tk) scores, valid broadcastable to s, pv_of(p) the
        (Hkv, M, D) probability-weighted values → scratch update."""
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + pv_of(p)
        m_ref[...] = m_new

    kpos = kpos_ref[...]                                   # (1, Tc)
    # cache rows are valid iff written AND strictly before the chunk: rows
    # at >= pos_start are stale previous-occupant data or the engine's
    # decode-parking garbage, and the chunk's own K/V arrive via kn/vn
    cache_valid = (kpos >= 0) & (kpos < pos_start)

    @pl.when((j < nc) & (jnp.max(cache_valid.astype(jnp.int32)) > 0))
    def _cache_chunk():
        if mode == "int8":
            kc = dequant_block(ck_ref[...], ks_ref[...], kz_ref[...])
            vc = dequant_block(cv_ref[...], vs_ref[...], vz_ref[...])
        else:
            kc = ck_ref[...].astype(jnp.float32)           # (Hkv, D, Tc)
            vc = cv_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, kc,
                                (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        online_update(s, cache_valid[None], lambda p: jax.lax.dot_general(
            p, vc, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))

    @pl.when(j == nc)
    def _chunk_and_flush():
        Sq = kn_ref.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (M, Sq), 0)
        if G > 1:                            # rows are (query, group)
            row = jax.lax.div(row, G)
        qidx = i * (M // G) + row
        cidx = jax.lax.broadcasted_iota(jnp.int32, (M, Sq), 1)
        valid = (cidx <= qidx) & (cidx < length)           # causal
        kn = kn_ref[...].astype(jnp.float32)               # (Hkv, Sq, D)
        vn = vn_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, kn, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        online_update(s, valid[None], lambda p: jax.lax.dot_general(
            p, vn, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
        l = l_ref[...]
        o = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[...] = o.astype(o_ref.dtype)


def _prefill_attention_pallas(q, k_new, v_new, cache_k, cache_v, kv_pos,
                              pos_start, length, scales, *, mode, per_entry,
                              kv_chunk, q_block, interpret):
    Sq, Hq, D = q.shape
    T, Hkv = cache_k.shape[0], cache_k.shape[1]
    Tc = _pick_kv_chunk(T, kv_chunk)
    nc = T // Tc
    Bq = _pick_kv_chunk(Sq, 128 if q_block is None else q_block)
    nq = Sq // Bq
    G = Hq // Hkv
    M = Bq * G
    jc = lambda j: jnp.minimum(j, nc - 1)      # clamp: block unused at j=nc
    info = jnp.stack([jnp.asarray(pos_start, jnp.int32),
                      jnp.asarray(length, jnp.int32)])
    # heads lead, time on lanes (decode_attention.to_kernel_layout); query
    # rows are (query, group) pairs so every dot batches over Hkv
    qh = q.reshape(Sq, Hkv, G, D).transpose(1, 0, 2, 3).reshape(
        Hkv, Sq * G, D)
    new_spec = pl.BlockSpec((Hkv, Sq, D), lambda i, j, info: (0, 0, 0))
    kv_spec = pl.BlockSpec((Hkv, D, Tc), lambda i, j, info: (0, 0, jc(j)))
    in_specs = [
        pl.BlockSpec((Hkv, M, D), lambda i, j, info: (0, i, 0)),
        pl.BlockSpec((1, Tc), lambda i, j, info: (0, jc(j))),
        kv_spec, kv_spec, new_spec, new_spec,
    ]
    args = [qh, kv_pos.reshape(1, T).astype(jnp.int32),
            to_kernel_layout(cache_k), to_kernel_layout(cache_v),
            k_new.transpose(1, 0, 2), v_new.transpose(1, 0, 2)]
    if mode == "int8":
        C = scales[0].shape[-1]
        if per_entry:
            in_specs += [pl.BlockSpec((Hkv, C, Tc),
                                      lambda i, j, info: (0, 0, jc(j)))] * 4
            args += [to_kernel_layout(s) for s in scales]
        else:
            # per-layer constants (Hkv, C) broadcast over time
            in_specs += [pl.BlockSpec((Hkv, C, 1),
                                      lambda i, j, info: (0, 0, 0))] * 4
            args += [s.reshape(Hkv, C, 1) for s in scales]
    kernel = functools.partial(_prefill_kernel, mode=mode,
                               n_cache_chunks=nc, groups=G)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq, nc + 1),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((Hkv, M, D), lambda i, j, info: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, M, 1), jnp.float32),      # running max
                pltpu.VMEM((Hkv, M, 1), jnp.float32),      # running sum
                pltpu.VMEM((Hkv, M, D), jnp.float32),      # output acc
            ]),
        out_shape=jax.ShapeDtypeStruct((Hkv, Sq * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="prefill_attention",
    )(info, *args)
    return o.reshape(Hkv, Sq, G, D).transpose(1, 0, 2, 3).reshape(Sq, Hq, D)


# ------------------------------------------------- jnp chunked lowering ---
def _prefill_attention_jnp(q, k_new, v_new, cache_k, cache_v, kv_pos,
                           pos_start, length, scales, *, mode, per_entry,
                           kv_chunk):
    """Same online-softmax sweep in pure jnp — the CPU path. `lax.cond`
    skips cache chunks with no valid entry (lazy `dynamic_slice` inside
    the branch, so skipped codes never move), then a final step attends
    the chunk's own fp K/V under the intra-chunk causal mask."""
    Sq, Hq, D = q.shape
    T, Hkv = cache_k.shape[0], cache_k.shape[1]
    G = Hq // Hkv
    Tc = _pick_kv_chunk(T, kv_chunk)
    nc = T // Tc
    qs = (q.astype(jnp.float32) * (D ** -0.5)).reshape(Sq, Hkv, G, D)
    pos_start = jnp.asarray(pos_start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)

    def update(carry, kc, vc, valid):
        m, l, acc = carry
        s = jnp.einsum("skgd,tkd->skgt", qs, kc,
                       preferred_element_type=jnp.float32)
        msk = valid[:, None, None, :] if valid.ndim == 2 \
            else valid[None, None, None, :]
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "skgt,tkd->skgd", p, vc, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def cache_step(carry, j):
        t0 = j * Tc
        pos_c = jax.lax.dynamic_slice_in_dim(kv_pos, t0, Tc, 0)    # (Tc,)
        valid = (pos_c >= 0) & (pos_c < pos_start)

        def compute(carry):
            def chunk(x):
                return jax.lax.dynamic_slice_in_dim(x, t0, Tc, 0)

            if mode == "int8":
                if per_entry:
                    kc = _dequant_chunk(chunk(cache_k), chunk(scales[0]),
                                        chunk(scales[1]))
                    vc = _dequant_chunk(chunk(cache_v), chunk(scales[2]),
                                        chunk(scales[3]))
                else:
                    kc = _dequant_cols(chunk(cache_k), scales[0], scales[1])
                    vc = _dequant_cols(chunk(cache_v), scales[2], scales[3])
            else:
                kc = chunk(cache_k).astype(jnp.float32)
                vc = chunk(cache_v).astype(jnp.float32)
            return update(carry, kc, vc, valid)

        return jax.lax.cond(jnp.any(valid), compute, lambda c: c, carry), \
            None

    m0 = jnp.full((Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((Sq, Hkv, G, D), jnp.float32)
    carry, _ = jax.lax.scan(cache_step, (m0, l0, a0),
                            jnp.arange(nc, dtype=jnp.int32))
    qidx = jnp.arange(Sq, dtype=jnp.int32)
    cidx = jnp.arange(Sq, dtype=jnp.int32)
    valid = (cidx[None, :] <= qidx[:, None]) & (cidx[None, :] < length)
    m, l, acc = update(carry, k_new.astype(jnp.float32),
                       v_new.astype(jnp.float32), valid)
    o = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None],
                  0.0)
    return o.reshape(Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------- entry point ---
def prefill_attention(q, k_new, v_new, cache_k, cache_v, kv_pos, pos_start,
                      length, *, k_scale=None, k_zero=None, v_scale=None,
                      v_zero=None, mode: str = "fp",
                      per_entry_scales: bool = True, kv_chunk=None,
                      q_block=None, use_pallas=None,
                      interpret: bool = False, verify: bool = False):
    """Fused chunked-prefill attention for one layer / one slot / one
    prompt chunk (see module doc).

    mode="fp":   cache is float; scale args ignored; returns (o, ()).
    mode="int8": cache is int8 codes. per_entry_scales=True: scales are
                 per-entry (T, Hkv, C); returns (o, (qk, qv, ks, kz, vs,
                 vz)) with the chunk's codes + fresh dynamic scales.
                 per_entry_scales=False: scales are static per-layer
                 (Hkv, C) recipe constants; returns (o, (qk, qv)).
    use_pallas:  None = auto (Pallas on TPU, jnp sweep elsewhere);
                 True with interpret=True is the reference fallback.
    verify:      speculative-verify scoring (module doc): the chunk
                 attends its OWN K/V through the storage round-trip
                 (quantize→dequantize, or the cache-dtype cast) instead
                 of at full precision, so each window row's logits match
                 a plain decode step of that token. Written codes are
                 unchanged.
    """
    if mode not in ("fp", "int8"):
        raise ValueError(f"unknown mode {mode!r}")
    Sq, Hq, D = q.shape
    Hkv = cache_k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    scales = None
    if mode == "int8":
        scales = (k_scale, k_zero, v_scale, v_zero)
        if any(s is None for s in scales):
            raise ValueError("mode='int8' requires all four scale arrays")
        C = k_scale.shape[-1]
        if D % C:
            raise ValueError(f"head_dim {D} not divisible by qchunks {C}")
        if not per_entry_scales:
            # static (Hkv, C) recipe constants expanded to per-column rows
            # (act_quant's chunk-id map at head-dim granularity) for the
            # chunk's quantization and the jnp sweep's cache dequant
            from .act_quant import chunk_id_map
            cid = jnp.asarray(chunk_id_map(D, C))
            scales = tuple(s.astype(jnp.float32) for s in scales)
            cols = tuple(jnp.take(s, cid, axis=-1) for s in scales)
    # the chunk's storage form — codes (+ fresh dynamic scales) for an
    # int8 cache — is computed once here for both lowerings; a verify
    # window attends its own K/V through that same round-trip
    codes = ()
    kn, vn = k_new, v_new
    if mode == "int8" and per_entry_scales:
        qk, ks, kz = _dyn_quantize(k_new, C)
        qv, vs, vz = _dyn_quantize(v_new, C)
        codes = (qk, qv, ks, kz, vs, vz)
        if verify:
            kn, vn = _dequant_chunk(qk, ks, kz), _dequant_chunk(qv, vs, vz)
    elif mode == "int8":
        qk = _static_quantize_cols(k_new, cols[0], cols[1])
        qv = _static_quantize_cols(v_new, cols[2], cols[3])
        codes = (qk, qv)
        if verify:
            kn = _dequant_cols(qk, cols[0], cols[1])
            vn = _dequant_cols(qv, cols[2], cols[3])
    elif verify:
        kn, vn = k_new.astype(cache_k.dtype), v_new.astype(cache_v.dtype)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        o = _prefill_attention_pallas(
            q, kn, vn, cache_k, cache_v, kv_pos, pos_start, length, scales,
            mode=mode, per_entry=per_entry_scales, kv_chunk=kv_chunk,
            q_block=q_block, interpret=interpret)
    else:
        o = _prefill_attention_jnp(
            q, kn, vn, cache_k, cache_v, kv_pos, pos_start, length,
            cols if (mode == "int8" and not per_entry_scales) else scales,
            mode=mode, per_entry=per_entry_scales, kv_chunk=kv_chunk)
    return o, codes
