"""Slot-indexed KV cache for the continuous-batching engine.

Layout (DESIGN.md §6): all serving state lives in preallocated arrays of
shape (L, N, T, Hkv, D) — N fixed slots, T = max sequence length. A slot
holds one request for its whole lifetime; `kv_pos[l, n, t]` records the
absolute position stored at time-index t (-1 = empty), so slots with
different prompt lengths coexist in one batched decode step and padding
never enters attention (invalid entries are masked by position, exactly
like the ring-buffer windows in `models/attention.py`).

Quantized storage (``mode="int8"``): SplitQuant §4.2 applied to
activations-at-rest. Each written K/V head-vector is split into
``qchunks`` sub-channel chunks and every chunk is quantized INT8 with its
own dynamic range (β, α) → (scale, zero) via the paper's eqs. (1)-(3).
Separate per-chunk ranges are the paper's mechanism for keeping outlier
channels from inflating everyone else's quantization step; unlike the
weight path (k-means cid per element, offline) the serving write sits on
the decode critical path, so chunk membership is fixed (contiguous
sub-channels) rather than value-clustered — no cid tensor, and dequant is
a reshape + broadcast. On read, the fused decode-attention kernel
(`repro.kernels.decode_attention`, via `fused_slot_attention`) streams
the codes + scales and dequantizes per chunk in VMEM next to the dot
product — no full-precision copy of the cache is materialized; the
legacy materialize-then-attend path (`slot_layer_update`) remains as the
cross-checked reference.

Storage cost per element: 1 byte of codes + 8·qchunks/D bytes of fp32
(scale, zero) — for D=64, qchunks=4 that is 1.5 B/elt vs 2 B (bf16) or
4 B (fp32).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import QuantConfig, dequantize, qparams, quantize, \
    value_range

KV_QCFG = QuantConfig(bits=8, symmetric=False)

#: Data leaves of SlotKVCache in declaration order — the serialization
#: contract used by engine snapshot/restore (engine/recovery.py): these
#: and only these arrays are persisted; mode/qchunks/static are manifest
#: metadata.
CACHE_DATA_FIELDS = ("k", "v", "kv_pos", "k_scale", "k_zero",
                     "v_scale", "v_zero")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=CACHE_DATA_FIELDS,
                   meta_fields=("mode", "qchunks", "static"))
@dataclasses.dataclass
class SlotKVCache:
    """Slot-indexed decode cache (one layer stack, or one layer inside
    `jax.lax.scan` — every data leaf carries the same leading axes, so
    scanning the dataclass over L yields per-layer `SlotKVCache` slices).

    mode="fp":   k/v (L, N, T, Hkv, D) in a float dtype; scales are
                 zero-size placeholders (shape (L, N, T, Hkv, 0)).
    mode="int8": k/v int8 codes; {k,v}_{scale,zero} fp32 with C = qchunks
                 contiguous sub-channel chunks per head. Dynamic scales
                 (static=False) are per-entry, shape (L, N, T, Hkv, C);
                 static scales (static=True, from an offline calibration
                 recipe) are per-layer constants, shape (L, 1, 1, Hkv, C) —
                 writes skip the runtime min/max reduce entirely and the
                 scale arrays are never updated.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    kv_pos: jnp.ndarray          # (L, N, T) int32, -1 = empty
    k_scale: jnp.ndarray
    k_zero: jnp.ndarray
    v_scale: jnp.ndarray
    v_zero: jnp.ndarray
    mode: str = "fp"
    qchunks: int = 4
    static: bool = False

    @property
    def n_slots(self) -> int:
        return self.k.shape[-4]

    @property
    def max_len(self) -> int:
        return self.k.shape[-3]

    def bytes_per_token(self) -> float:
        """Storage bytes per cached token per layer (both K and V).
        Static scales are per-layer constants — amortized to ~0/token."""
        Hkv, D = self.k.shape[-2], self.k.shape[-1]
        per_elt = self.k.dtype.itemsize
        per_chunk = (0 if self.static
                     else 2 * 4 * self.k_scale.shape[-1])   # scale+zero fp32
        return 2 * (Hkv * D * per_elt + Hkv * per_chunk)


def init_slot_cache(cfg, n_slots: int, max_len: int, *, mode: str = "fp",
                    dtype=jnp.float32, qchunks: int = 4,
                    kv_scales: Optional[dict] = None) -> SlotKVCache:
    """Preallocate the engine cache for a transformer-family config.

    ``kv_scales`` (int8 mode only): precomputed static quantization
    parameters from an offline calibration recipe — a dict with keys
    ``k_scale / k_zero / v_scale / v_zero``, each (L, Hkv, C) fp32. When
    given, decode writes quantize with these constants instead of running
    the per-step min/max reduce (dynamic ranges stay the default).
    """
    if mode not in ("fp", "int8"):
        raise ValueError(f"unknown KV cache mode {mode!r}")
    if kv_scales is not None and mode != "int8":
        raise ValueError("static kv_scales require mode='int8'")
    L, Hkv, D = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    if mode == "int8" and D % qchunks:
        raise ValueError(f"head_dim {D} not divisible by qchunks {qchunks}")
    shape = (L, n_slots, max_len, Hkv, D)
    C = qchunks if mode == "int8" else 0
    kv_dtype = jnp.int8 if mode == "int8" else dtype
    kv = dict(k=jnp.zeros(shape, kv_dtype), v=jnp.zeros(shape, kv_dtype),
              kv_pos=jnp.full((L, n_slots, max_len), -1, jnp.int32))
    if kv_scales is not None:
        got = check_static_scales(kv_scales, L, Hkv, qchunks)
        return SlotKVCache(**kv, **got, mode=mode, qchunks=qchunks,
                           static=True)
    sshape = (L, n_slots, max_len, Hkv, C)
    # scales init to 1 (not 0): unwritten entries must dequantize to a
    # finite 0, because masked-out attention rows still flow through the
    # p·V einsum where 0·NaN would poison the output.
    one = functools.partial(jnp.ones, dtype=jnp.float32)
    zero = functools.partial(jnp.zeros, dtype=jnp.float32)
    return SlotKVCache(
        **kv,
        k_scale=one(sshape), k_zero=zero(sshape),
        v_scale=one(sshape), v_zero=zero(sshape),
        mode=mode, qchunks=qchunks)


def check_static_scales(kv_scales: dict, L: int, Hkv: int,
                        qchunks: int) -> dict:
    """Validate recipe kv_scales ((L, Hkv, C) each) and reshape to the
    per-layer-constant cache layout (L, 1, 1, Hkv, C)."""
    expect = (L, Hkv, qchunks)
    got = {}
    for kk in ("k_scale", "k_zero", "v_scale", "v_zero"):
        arr = jnp.asarray(kv_scales[kk], jnp.float32)
        if tuple(arr.shape) != expect:
            raise ValueError(
                f"static kv_scales[{kk!r}] has shape {tuple(arr.shape)}"
                f", expected (L, Hkv, qchunks) = {expect} — was the "
                f"recipe calibrated with a different qchunks or arch?")
        got[kk] = arr.reshape(L, 1, 1, Hkv, qchunks)
    return got


# ----------------------------------------------------------- quant core ---
def quantize_kv(x: jnp.ndarray, qchunks: int):
    """x (..., Hkv, D) → (codes int8 (..., Hkv, D), scale, zero (..., Hkv, C)).

    Per-chunk dynamic ranges: split D into C contiguous chunks, each gets
    its own (β, α) → (S, Z).
    """
    *lead, H, D = x.shape
    xc = x.reshape(*lead, H, qchunks, D // qchunks)
    beta, alpha = value_range(xc, axis=-1)
    scale, zero = qparams(beta, alpha, KV_QCFG)
    q = quantize(xc, scale[..., None], zero[..., None], KV_QCFG)
    return q.reshape(x.shape), scale, zero


def quantize_kv_static(x: jnp.ndarray, scale: jnp.ndarray,
                       zero: jnp.ndarray) -> jnp.ndarray:
    """x (..., Hkv, D), scale/zero broadcastable (..., Hkv, C) → int8 codes.

    Static-scale write: no range pass at all — a single fused
    scale+round+clip over the activation (the decode-critical-path win a
    calibration recipe buys; cf. the dynamic `quantize_kv` above).

    Unlike the runtime path (paper eq. 3 rounds the zero-point to an
    integer), offline scales carry an EXACT fractional zero-point folded
    into the rounding — ``q = rint(S·x + Z)`` — which removes the
    zero-rounding error term entirely; dequantization ``(q - Z)/S`` is
    unchanged (fractional Z is just another float).
    """
    *lead, H, D = x.shape
    C = scale.shape[-1]
    xc = x.reshape(*lead, H, C, D // C).astype(jnp.float32)
    q = jnp.clip(jnp.rint(scale[..., None] * xc + zero[..., None]),
                 KV_QCFG.qmin, KV_QCFG.qmax)
    return q.astype(jnp.int8).reshape(x.shape)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """codes (..., Hkv, D), scale/zero (..., Hkv, C) → x̂ (..., Hkv, D)."""
    *lead, H, D = q.shape
    C = scale.shape[-1]
    qc = q.reshape(*lead, H, C, D // C)
    x = dequantize(qc, scale[..., None], zero[..., None], dtype)
    return x.reshape(q.shape)


# ----------------------------------------------- per-layer decode update ---
def slot_layer_write(cl: SlotKVCache, k_new, v_new, positions
                     ) -> SlotKVCache:
    """One decode-step cache WRITE for ONE layer: quantize-in (int8 modes)
    and scatter the new token — nothing is read back or dequantized.

    cl: per-layer slice — leaves (N, T, Hkv, D) / (N, T, Hkv, C) / (N, T).
    k_new/v_new: (N, 1, Hkv, D) post-RoPE. positions: (N, 1) int32 absolute
    per-slot positions (the time-index written is positions % T, though the
    engine never wraps — it retires at max_len). Runs under the name
    ``kv_write`` (op_name metadata), as does the chunk write's scatter.
    """
    with jax.named_scope("kv_write"):
        return _slot_layer_write(cl, k_new, v_new, positions)


def _slot_layer_write(cl, k_new, v_new, positions):
    T = cl.k.shape[-3]
    slot_t = (positions[:, 0] % T).astype(jnp.int32)       # (N,)

    def upd(buf, new, t):
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (t,) + (0,) * (buf.ndim - 1))

    pos_upd = dict(kv_pos=jax.vmap(upd)(cl.kv_pos,
                                        positions.astype(jnp.int32), slot_t))
    if cl.mode == "int8" and cl.static:
        # static scales: quantize with the calibrated per-layer constants —
        # no min/max reduce, and the scale arrays are never written
        qk = quantize_kv_static(k_new, cl.k_scale, cl.k_zero)
        qv = quantize_kv_static(v_new, cl.v_scale, cl.v_zero)
        return dataclasses.replace(
            cl, k=jax.vmap(upd)(cl.k, qk, slot_t),
            v=jax.vmap(upd)(cl.v, qv, slot_t), **pos_upd)
    if cl.mode == "int8":
        qk, ks, kz = quantize_kv(k_new, cl.qchunks)        # (N,1,H,D)/(N,1,H,C)
        qv, vs, vz = quantize_kv(v_new, cl.qchunks)
        return dataclasses.replace(
            cl,
            k=jax.vmap(upd)(cl.k, qk, slot_t),
            v=jax.vmap(upd)(cl.v, qv, slot_t),
            k_scale=jax.vmap(upd)(cl.k_scale, ks, slot_t),
            k_zero=jax.vmap(upd)(cl.k_zero, kz, slot_t),
            v_scale=jax.vmap(upd)(cl.v_scale, vs, slot_t),
            v_zero=jax.vmap(upd)(cl.v_zero, vz, slot_t), **pos_upd)
    return dataclasses.replace(
        cl, k=jax.vmap(upd)(cl.k, k_new, slot_t),
        v=jax.vmap(upd)(cl.v, v_new, slot_t), **pos_upd)


def materialize_layer(cl: SlotKVCache, dtype=jnp.float32):
    """Full-precision (k, v) view of one layer's slot cache — the LEGACY
    read path (and the oracle the fused kernel is tested against). Costs a
    full dequant pass + a (N, T, Hkv, D) fp copy per call."""
    if cl.mode == "int8":
        return (dequantize_kv(cl.k, cl.k_scale, cl.k_zero, dtype),
                dequantize_kv(cl.v, cl.v_scale, cl.v_zero, dtype))
    return cl.k.astype(dtype), cl.v.astype(dtype)


def slot_layer_update(cl: SlotKVCache, k_new, v_new, positions):
    """Legacy combined write + materialize: returns (k_full, v_full,
    kv_pos, new_cl) with k_full/v_full (N, T, Hkv, D) in compute precision.
    The fused decode path (`fused_slot_attention`) replaces this read —
    use `slot_layer_write` there so no full-precision copy ever exists."""
    new_cl = slot_layer_write(cl, k_new, v_new, positions)
    k_full, v_full = materialize_layer(new_cl, k_new.dtype)
    return k_full, v_full, new_cl.kv_pos, new_cl


def fused_slot_attention(cl: SlotKVCache, q, q_pos, *, use_pallas=None,
                         interpret: bool = False, kv_chunk=None):
    """Decode attention for one layer straight off the (possibly INT8)
    slot cache — dequant-in-kernel, no full-cache materialization.

    cl: per-layer slice AFTER `slot_layer_write`; q (N, Hq, D) post-RoPE;
    q_pos (N,) int32 current positions. Returns (N, Hq, D).
    """
    from repro.kernels.decode_attention import decode_attention
    if cl.mode == "int8":
        return decode_attention(
            q, cl.k, cl.v, cl.kv_pos, q_pos,
            k_scale=cl.k_scale, k_zero=cl.k_zero,
            v_scale=cl.v_scale, v_zero=cl.v_zero, mode="int8",
            per_entry_scales=not cl.static, kv_chunk=kv_chunk,
            use_pallas=use_pallas, interpret=interpret)
    return decode_attention(q, cl.k, cl.v, cl.kv_pos, q_pos, mode="fp",
                            kv_chunk=kv_chunk, use_pallas=use_pallas,
                            interpret=interpret)


def slot_chunk_prefill(cl: SlotKVCache, q, k_new, v_new, slot, pos_start,
                       length, *, kv_chunk=None, use_pallas=None,
                       interpret: bool = False, verify: bool = False):
    """One CHUNKED-PREFILL step for ONE layer and ONE slot: fused causal
    attention of the chunk's queries over [the slot's already-written
    rows] + [the chunk's own fp K/V], with the chunk quantized in-kernel
    and the codes scattered straight into rows [pos_start, pos_start+Sq)
    of the slot — the prefill-side twin of `slot_layer_write` +
    `fused_slot_attention`. No full-precision copy of the cache (and no
    dense per-request prefill cache at all) ever exists.

    cl: per-layer slice; q (Sq, Hq, D), k_new/v_new (Sq, Hkv, D) post-RoPE;
    slot/pos_start/length are traced scalars. Only the first `length` rows
    become visible (`kv_pos` = absolute position; the padded tail is
    re-marked -1, which is a no-op on rows the next chunk will overwrite
    and drops rows past max_len). Returns (o (Sq, Hq, D), new_cl).

    ``verify``: speculative-verify scoring (DESIGN.md §9) — the chunk is
    a DRAFT WINDOW and must attend its own K/V through the storage
    round-trip so every row's logits match a plain decode step of that
    token; the codes scattered into the slot are identical either way
    (accepted rows land as final slot bytes, rejected rows are undone by
    `rollback_slot`).
    """
    from repro.kernels.prefill_attention import prefill_attention

    Sq = q.shape[0]
    take = functools.partial(jax.lax.dynamic_index_in_dim, index=slot,
                             axis=0, keepdims=False)
    ck, cv, kpos = take(cl.k), take(cl.v), take(cl.kv_pos)
    kw = dict(kv_chunk=kv_chunk, use_pallas=use_pallas, interpret=interpret,
              verify=verify)
    if cl.mode == "int8" and cl.static:
        o, (qk, qv) = prefill_attention(
            q, k_new, v_new, ck, cv, kpos, pos_start, length,
            k_scale=cl.k_scale[0, 0], k_zero=cl.k_zero[0, 0],
            v_scale=cl.v_scale[0, 0], v_zero=cl.v_zero[0, 0],
            mode="int8", per_entry_scales=False, **kw)
        scale_upd = {}
    elif cl.mode == "int8":
        o, (qk, qv, ks, kz, vs, vz) = prefill_attention(
            q, k_new, v_new, ck, cv, kpos, pos_start, length,
            k_scale=take(cl.k_scale), k_zero=take(cl.k_zero),
            v_scale=take(cl.v_scale), v_zero=take(cl.v_zero),
            mode="int8", per_entry_scales=True, **kw)
        scale_upd = dict(k_scale=(cl.k_scale, ks), k_zero=(cl.k_zero, kz),
                         v_scale=(cl.v_scale, vs), v_zero=(cl.v_zero, vz))
    else:
        o, _ = prefill_attention(q, k_new, v_new, ck, cv, kpos, pos_start,
                                 length, mode="fp", **kw)
        qk, qv = k_new, v_new
        scale_upd = {}

    with jax.named_scope("kv_write"):
        return o, _scatter_chunk(cl, qk, qv, scale_upd, slot, pos_start,
                                 length, Sq)


def _scatter_chunk(cl, qk, qv, scale_upd, slot, pos_start, length, Sq):
    """The chunk's codes (quantized in-kernel) into the slot's rows."""
    rows = pos_start + jnp.arange(Sq, dtype=jnp.int32)
    posv = jnp.where(jnp.arange(Sq) < length, rows, jnp.int32(-1))

    def put(buf, upd):
        # scatter with OOB drop: a bucket-padded final chunk may stick out
        # past max_len — those rows carry no valid tokens by construction
        return buf.at[slot, rows].set(upd.astype(buf.dtype), mode="drop")

    return dataclasses.replace(
        cl, k=put(cl.k, qk), v=put(cl.v, qv),
        kv_pos=cl.kv_pos.at[slot, rows].set(posv, mode="drop"),
        **{f: put(buf, upd) for f, (buf, upd) in scale_upd.items()})


def hotswap_static_scales(cache: SlotKVCache, kv_scales: dict
                          ) -> SlotKVCache:
    """Switch a DYNAMIC int8 cache to static recipe scales mid-flight —
    no slot drain (ROADMAP item). Existing codes are requantized under the
    new constants (dequant with their per-entry scales, requantize with
    the per-layer constants — a one-time migration pass; invalid entries
    carry garbage but stay masked by kv_pos). From then on the `static`
    flag routes writes through `quantize_kv_static`: the per-step min/max
    reduce and the scale-array scatter both disappear, and the (L, N, T,
    Hkv, C) per-entry scale arrays are dropped for (L, 1, 1, Hkv, C)
    constants."""
    if cache.mode != "int8":
        raise ValueError("hot-swap requires an int8 cache")
    if cache.static:
        raise ValueError("cache already serves static scales")
    L, Hkv = cache.k.shape[0], cache.k.shape[-2]
    got = check_static_scales(kv_scales, L, Hkv, cache.qchunks)
    k = quantize_kv_static(
        dequantize_kv(cache.k, cache.k_scale, cache.k_zero),
        got["k_scale"], got["k_zero"])
    v = quantize_kv_static(
        dequantize_kv(cache.v, cache.v_scale, cache.v_zero),
        got["v_scale"], got["v_zero"])
    return dataclasses.replace(cache, k=k, v=v, static=True, **got)


# ------------------------------------------------------ slot management ---
def write_prefill(cache: SlotKVCache, slot: int, prefill_cache,
                  length: int) -> SlotKVCache:
    """Insert a single request's prefill KV (a standard `models.KVCache`
    with batch 1, k/v (L, 1, S, Hkv, D)) into slot `slot`.

    Only positions [0, length) become visible; the slot's whole kv_pos row
    is rewritten, so stale state from the slot's previous occupant (and any
    right-padding the prefill bucket added) is invalidated in one write.
    """
    k, v = prefill_cache.k[:, 0], prefill_cache.v[:, 0]    # (L, S, Hkv, D)
    L, S, H, D = k.shape
    T = cache.max_len
    if S > T:
        raise ValueError(f"prefill length {S} exceeds cache max_len {T}")
    if S < T:
        pad = [(0, 0), (0, T - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    t = jnp.arange(T, dtype=jnp.int32)
    pos_row = jnp.where(t < length, t, -1)                 # (T,)
    pos_row = jnp.broadcast_to(pos_row, (L, T))

    def put(buf, row):
        idx = (0, slot) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(
            buf, row[:, None].astype(buf.dtype), idx)

    if cache.mode == "int8" and cache.static:
        # per-layer static constants: index as (L, Hkv, C) for the (L, S,
        # Hkv, D) prefill block, then write codes only
        ks, kz = cache.k_scale[:, 0], cache.k_zero[:, 0]   # (L, 1, Hkv, C)
        vs, vz = cache.v_scale[:, 0], cache.v_zero[:, 0]
        qk = quantize_kv_static(k, ks, kz)
        qv = quantize_kv_static(v, vs, vz)
        return dataclasses.replace(
            cache, k=put(cache.k, qk), v=put(cache.v, qv),
            kv_pos=put(cache.kv_pos, pos_row))
    if cache.mode == "int8":
        qk, ks, kz = quantize_kv(k, cache.qchunks)
        qv, vs, vz = quantize_kv(v, cache.qchunks)
        return dataclasses.replace(
            cache, k=put(cache.k, qk), v=put(cache.v, qv),
            k_scale=put(cache.k_scale, ks), k_zero=put(cache.k_zero, kz),
            v_scale=put(cache.v_scale, vs), v_zero=put(cache.v_zero, vz),
            kv_pos=put(cache.kv_pos, pos_row))
    return dataclasses.replace(
        cache, k=put(cache.k, k), v=put(cache.v, v),
        kv_pos=put(cache.kv_pos, pos_row))


def clear_slot(cache: SlotKVCache, slot: int) -> SlotKVCache:
    """Mark a slot empty (retire). K/V bytes are left in place — kv_pos=-1
    masks them, and the next write_prefill overwrites the row."""
    row = jnp.full((cache.kv_pos.shape[0], cache.max_len), -1, jnp.int32)
    return dataclasses.replace(
        cache, kv_pos=jax.lax.dynamic_update_slice(
            cache.kv_pos, row[:, None], (0, slot, 0)))


def rollback_slot(cache: SlotKVCache, slot: int, accept_len: int
                  ) -> SlotKVCache:
    """Undo speculative writes past the accepted point: after this call
    the slot's valid content is exactly positions [0, accept_len).

    Validity-by-position makes this the WHOLE rollback (DESIGN.md §9):
    every read path masks rows by ``kv_pos``, so flipping the rejected
    rows to -1 removes them from all attention, and the codes/scales left
    behind are indistinguishable from the stale bytes any retired slot
    leaves — the next write at those positions overwrites them, which is
    why a rolled-back slot re-decoded over the accepted prefix is
    bit-identical to a slot that never speculated (hypothesis property in
    tests/test_spec.py). ``slot`` / ``accept_len`` may be traced scalars.
    """
    L, _, T = cache.kv_pos.shape
    row = jax.lax.dynamic_slice(cache.kv_pos, (0, slot, 0), (L, 1, T))
    row = jnp.where(row >= accept_len, jnp.int32(-1), row)
    return dataclasses.replace(
        cache, kv_pos=jax.lax.dynamic_update_slice(
            cache.kv_pos, row, (0, slot, 0)))


def slice_layers(cache: SlotKVCache, lo: int, hi: int) -> SlotKVCache:
    """Layer-range view, mirroring `forward`'s dense/MoE stack split."""
    return jax.tree_util.tree_map(lambda x: x[lo:hi], cache)


def occupied_slots(cache: SlotKVCache) -> list[int]:
    """Slots with ANY valid (kv_pos >= 0) row — the slot-pool leak
    check. After a full drain every request has retired and `clear_slot`
    flipped its rows to -1, so a non-empty result means a retire path
    forgot the cache half of the slot (asserted over target AND draft
    caches by the chaos harness, tests/test_faults.py). One bounded
    host transfer of the position plane; diagnostics, not hot path."""
    import numpy as np
    pos = np.asarray(cache.kv_pos)                    # (L, N, T)
    return np.unique(np.nonzero((pos >= 0).any(axis=(0, 2)))[0]).tolist()


# -------------------------------------------------- quality counters ---
def kv_quality_counters(cache: SlotKVCache, max_rows: int = 4096,
                        ref_scales: Optional[dict] = None) -> dict:
    """Sample quantization-quality counters from a live int8 slot cache
    (host-side numpy; see `repro.obs.quality` and DESIGN.md §10).

    Reads only rows kv_pos marks valid (stale retired/rolled-back bytes
    would poison the statistics), subsampling evenly to ``max_rows``
    (token, slot) rows per array so the transfer stays bounded on big
    caches. Returns a flat dict of numbers/lists — the shape the tracer's
    ``counter`` records and the Chrome exporter expect:

    * ``{k,v}_clip_frac`` / ``{k,v}_occupancy`` — code saturation and
      code-range use (`quality.code_stats`); the static-scale drift
      signals (clipping up = recipe too narrow, occupancy down = too
      wide).
    * dynamic scales only: ``{k,v}_span_median`` / ``_span_outlier_hist``
      — per-chunk range spread and the OCS outlier histogram, plus
      ``_occupancy_vs_ref`` when a recipe's ``ref_scales`` dict
      ((L, Hkv, C) arrays, same layout as `init_slot_cache`) is given to
      compare live ranges against.
    """
    import numpy as np

    from repro.obs.quality import code_stats, scale_to_span, span_stats

    if cache.mode != "int8":
        raise ValueError("KV quality counters require an int8 cache")
    valid = np.asarray(cache.kv_pos) >= 0                  # (L, N, T)
    n_valid = int(valid.sum())
    out: dict = {"valid_rows": n_valid, "static": int(cache.static),
                 "qchunks": cache.qchunks}
    if not n_valid:
        return out
    lidx, nidx, tidx = np.nonzero(valid)
    if lidx.size > max_rows:                    # even, deterministic
        keep = np.linspace(0, lidx.size - 1, max_rows).astype(np.int64)
        lidx, nidx, tidx = lidx[keep], nidx[keep], tidx[keep]
    out["sampled_rows"] = int(lidx.size)
    for name, codes in (("k", cache.k), ("v", cache.v)):
        cs = code_stats(np.asarray(codes)[lidx, nidx, tidx],
                        bits=8)
        out[f"{name}_clip_frac"] = cs["clip_frac"]
        out[f"{name}_occupancy"] = cs["occupancy"]
    if not cache.static:
        for name, scale in (("k", cache.k_scale), ("v", cache.v_scale)):
            spans = scale_to_span(np.asarray(scale)[lidx, nidx, tidx])
            ref = None
            if ref_scales is not None:
                # recipe scales are per-layer constants (L, Hkv, C):
                # broadcast to the sampled rows through lidx
                ref = scale_to_span(
                    np.asarray(ref_scales[f"{name}_scale"],
                               np.float64)[lidx])
            st = span_stats(spans, ref)
            out[f"{name}_span_median"] = st["span_median"]
            out[f"{name}_span_outlier_hist"] = st["outlier_hist"]
            if ref is not None:
                out[f"{name}_occupancy_vs_ref"] = st["occupancy_vs_ref"]
    return out
