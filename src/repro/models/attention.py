"""GQA attention with chunked (flash-style) online softmax, local windows,
RoPE, and ring-buffer KV caches for decode.

Memory note: full S×T score materialization at 32k prefill is ~O(S·T·H)
and would dominate the memory roofline, so prefill/training use an online
softmax scanned over KV chunks (O(S·chunk·H) transient) — the same scheme a
TPU flash kernel implements, expressed in jnp so the identical code path
lowers for the CPU dry-run and for TPU.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .common import apply_rope, dense, shard_hint, tp_dense

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Per-layer-stack KV cache. For windowed attention the buffer is a ring
    of size `window`; `slot_pos[t]` records the absolute position stored in
    slot t (-1 = empty)."""
    k: jnp.ndarray          # (L, B, T, Hkv, D)
    v: jnp.ndarray          # (L, B, T, Hkv, D)
    slot_pos: jnp.ndarray   # (L, T) int32 — or (L, B, T) when positions
                            # are per-request (padded prefill)


def _is_slot_cache(cache_layer) -> bool:
    """Duck-typed check for a per-layer `engine.kvcache.SlotKVCache` slice
    (imported lazily in the hot path to keep models ← engine acyclic)."""
    return hasattr(cache_layer, "kv_pos") and hasattr(cache_layer, "mode")


def _mask(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """Boolean validity, always (B|1, S, T). kv_pos may contain -1 (empty
    ring slots / padding). q_pos (S,) or (B, S); kv_pos (T,) or (B, T) —
    the batched forms carry per-request positions (engine slots, pad
    masks)."""
    q = jnp.atleast_2d(q_pos)            # (Bq, S)
    kv = jnp.atleast_2d(kv_pos)          # (Bk, T)
    m = kv[:, None, :] >= 0
    if causal:
        m = m & (kv[:, None, :] <= q[:, :, None])
    if window is not None:
        m = m & (kv[:, None, :] > q[:, :, None] - window)
    return m


def attend(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
           kv_chunk: Optional[int] = None):
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D). Returns (B, S, Hq, D).

    ``kv_chunk`` switches to the online-softmax scanned form (required for
    long T); None does a single dense pass.

    GQA layout (perf note, EXPERIMENTS.md §Perf iter 1): K/V are broadcast
    to Hq heads *before* the score einsum instead of reshaping Q into
    (Hkv, G) groups. With TP=16 and Hkv=8, neither the Hkv nor the G dim is
    divisible by the mesh axis, so the grouped form forces GSPMD to
    replicate the whole attention computation per device (~5-16× redundant
    FLOPs in the baseline). The broadcast form keeps a single Hq dim that
    shards cleanly; the expanded K/V tile per device is G× *smaller* than a
    fully-replicated K/V.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv

    def expand(t):
        """(B, c, Hkv, D) → (B, c, Hq, D) head broadcast (per chunk, so the
        expanded tile stays VMEM-sized and shards over the single Hq dim)."""
        if G == 1:
            return t
        Bc, c = t.shape[0], t.shape[1]
        t = jnp.broadcast_to(t[:, :, :, None, :], (Bc, c, Hkv, G, D))
        return t.reshape(Bc, c, Hq, D)

    q = shard_hint(q, "dp", None, "tp", None)
    qs = (q * (D ** -0.5)).astype(q.dtype)

    if kv_chunk is None or T <= kv_chunk:
        k = shard_hint(expand(k), "dp", None, "tp", None)
        v = shard_hint(expand(v), "dp", None, "tp", None)
        s = jnp.einsum("bshd,bthd->bsht", qs, k,
                       preferred_element_type=jnp.float32)
        m = _mask(q_pos, kv_pos, causal, window)           # (B|1, S, T)
        s = jnp.where(m[:, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bsht,bthd->bshd", p, v,
                       preferred_element_type=jnp.float32)
        return shard_hint(o.astype(q.dtype), "dp", None, "tp", None)

    n_chunks = T // kv_chunk
    assert T % kv_chunk == 0, (T, kv_chunk)
    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    if kv_pos.ndim == 1:
        pc = kv_pos.reshape(n_chunks, kv_chunk)
    else:                                # batched positions (B, T)
        pc = kv_pos.reshape(B, n_chunks, kv_chunk).transpose(1, 0, 2)

    def step(carry, xs):
        m_run, l_run, acc = carry
        k_i, v_i, p_i = xs
        k_i = shard_hint(expand(k_i), "dp", None, "tp", None)
        v_i = shard_hint(expand(v_i), "dp", None, "tp", None)
        s = jnp.einsum("bshd,bthd->bsht", qs, k_i,
                       preferred_element_type=jnp.float32)   # (B,S,Hq,c)
        s = shard_hint(s, "dp", None, "tp", None)
        msk = _mask(q_pos, p_i, causal, window)
        s = jnp.where(msk[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bsht,bthd->bshd", p.astype(q.dtype), v_i,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = shard_hint(jnp.full((B, S, Hq), NEG_INF, jnp.float32),
                    "dp", None, "tp")
    l0 = shard_hint(jnp.zeros((B, S, Hq), jnp.float32), "dp", None, "tp")
    a0 = shard_hint(jnp.zeros((B, S, Hq, D), jnp.float32),
                    "dp", None, "tp", None)
    (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return shard_hint(o.astype(q.dtype), "dp", None, "tp", None)


def tshard_decode_attend(q, k, v, q_pos, kv_pos, *, window=None):
    """Decode attention over a TIME-sharded KV cache (ring-attention-style):
    each model shard attends over its local cache slice; shards merge via a
    log-sum-exp reduction of (m, l, acc) — per layer the cross-shard bytes
    are O(B·Hq·D), not O(cache). Used when kv_heads < TP so head-sharding
    the cache is impossible (EXPERIMENTS.md §Perf cell C iter 3).

    q: (B, 1, Hq, D) — heads REPLICATED over "model" (q is tiny at decode);
    k, v: (B, T, Hkv, D) with T sharded over "model"; kv_pos: (T,).
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names or kv_pos.ndim > 1:
        # batched (per-request) kv_pos carries no single time shard; the
        # engine path never runs time-sharded, so fall back
        return attend(q, k, v, q_pos, kv_pos, causal=True, window=window)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import math
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    dpn = math.prod(dict(mesh.shape)[a] for a in dp) if dp else 1
    bspec = dp if (dp and B % dpn == 0 and B >= dpn) else None

    def body(qb, kb, vb, pb, qp):
        # qb: (Bl, 1, Hq, D); kb/vb: (Bl, Tl, Hkv, D); pb: (Tl,)
        if G > 1:
            Bl, Tl = kb.shape[0], kb.shape[1]
            kb = jnp.broadcast_to(kb[:, :, :, None, :],
                                  (Bl, Tl, Hkv, G, D)).reshape(Bl, Tl, Hq, D)
            vb = jnp.broadcast_to(vb[:, :, :, None, :],
                                  (Bl, Tl, Hkv, G, D)).reshape(Bl, Tl, Hq, D)
        s = jnp.einsum("bshd,bthd->bsht", (qb * D ** -0.5).astype(qb.dtype),
                       kb, preferred_element_type=jnp.float32)
        msk = _mask(qp, pb, True, window)                  # (1, 1, Tl)
        s = jnp.where(msk[:, :, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                            # (Bl,1,Hq)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bsht,bthd->bshd", p.astype(qb.dtype), vb,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(acc * corr[..., None], "model")
        return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(qb.dtype)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(bspec, None, None, None),
                                 P(bspec, "model", None, None),
                                 P(bspec, "model", None, None),
                                 P("model"), P(None)),
                       out_specs=P(bspec, None, None, None),
                       check_vma=False)
    return fn(q, k, v, kv_pos, q_pos)


def attention_block(p, x, cfg, positions, cache_layer=None, *,
                    causal=True, window=None, kv_chunk=None,
                    cross_kv=None, want_kv=False, tshard_decode=False,
                    kv_pos_override=None, fused_attn=False,
                    slot_chunk=None, spec_verify=False):
    """Full attention sub-layer: projections + RoPE + (cache) + attend + out.

    p: {"wq","wk","wv","wo"(,biases)}; x: (B, S, d).
    cache_layer: (k, v, slot_pos) for this layer (decode), a per-layer
    `engine.kvcache.SlotKVCache` slice (slot decode with per-request
    positions — `positions` is then (B, 1)), or None.
    cross_kv: precomputed (k, v, kv_pos) for encoder-decoder cross-attention
    (projections wk/wv already applied by the caller).
    want_kv: with no cache, also return this call's post-RoPE (k, v) so the
    caller can assemble a prefill cache.
    kv_pos_override: (B, S) per-request KV validity positions for prefill
    with padding (-1 = pad token; masked out of attention).
    fused_attn: slot-cache decode only — read attention straight off the
    (possibly INT8) cache via the fused Pallas/jnp kernel instead of
    materializing a full-precision copy for `attend`.
    slot_chunk: (slot, pos_start, length) traced scalars — CHUNKED PREFILL
    over a slot cache: x is one slot's prompt chunk (B=1, S=chunk),
    `positions` its absolute positions; the chunk's K/V are quantized
    in-kernel and written straight into the slot's rows (no dense prefill
    cache is assembled). Requires a slot cache, causal, no window.
    spec_verify: with slot_chunk — the chunk is a speculative DRAFT
    WINDOW; it attends its own K/V through the cache's storage round-trip
    so each row scores exactly like a plain decode step (DESIGN.md §9).
    Returns (out, new_cache_layer | (k, v) | None).
    """
    B, S, _ = x.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], p.get("bq")).reshape(B, S, Hq, D)
    q = shard_hint(q, "dp", None, "tp", None)
    if cross_kv is None:
        k = dense(x, p["wk"], p.get("bk")).reshape(B, S, Hkv, D)
        v = dense(x, p["wv"], p.get("bv")).reshape(B, S, Hkv, D)
        k = shard_hint(k, "dp", None, "tp", None)
        v = shard_hint(v, "dp", None, "tp", None)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_variant)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_variant)

    new_cache = None
    if cross_kv is not None:
        k, v, kv_pos = cross_kv
    elif _is_slot_cache(cache_layer):
        # engine slot cache: per-request positions (B, 1), quant-aware
        from repro.engine.kvcache import (fused_slot_attention,
                                          slot_chunk_prefill,
                                          slot_layer_update,
                                          slot_layer_write)
        if slot_chunk is not None:
            # chunked prefill of ONE slot: fused attention over prior rows
            # + this chunk, codes scattered into the slot in one pass
            assert causal and window is None and B == 1, (causal, window, B)
            slot, pos_start, length = slot_chunk
            o, new_cache = slot_chunk_prefill(
                cache_layer, q[0], k[0], v[0], slot, pos_start, length,
                kv_chunk=kv_chunk, verify=spec_verify)
            o = o[None]
        elif fused_attn and S == 1 and causal and window is None:
            # fused decode read: write-only cache update, then dequant-in-
            # kernel attention — no full-precision cache copy exists
            new_cache = slot_layer_write(cache_layer, k, v, positions)
            o = fused_slot_attention(new_cache, q[:, 0], positions[:, 0],
                                     kv_chunk=kv_chunk)[:, None]
        else:
            k, v, kv_pos, new_cache = slot_layer_update(
                cache_layer, k, v, positions)
            o = attend(q, k, v, positions, kv_pos, causal=causal,
                       window=window, kv_chunk=kv_chunk)
        out = dense(o.reshape(B, S, Hq * D), p["wo"], p.get("bo"))
        return shard_hint(out, "dp", None, None), new_cache
    elif cache_layer is not None:
        ck, cv, slot_pos = cache_layer
        T = ck.shape[1]
        slot = positions[0] % T                     # ring slot (window) or abs
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        if slot_pos.ndim == 1:                      # shared positions (T,)
            slot_pos = jax.lax.dynamic_update_slice(
                slot_pos, positions.astype(jnp.int32), (slot,))
        else:                                       # per-request (B, T)
            upd = jnp.broadcast_to(positions.astype(jnp.int32),
                                   (slot_pos.shape[0], 1))
            slot_pos = jax.lax.dynamic_update_slice(slot_pos, upd, (0, slot))
        k, v, kv_pos = ck.astype(x.dtype), cv.astype(x.dtype), slot_pos
        new_cache = (ck, cv, slot_pos)
        if tshard_decode and S == 1:
            o = tshard_decode_attend(q, k, v, positions, kv_pos,
                                     window=window)
            out = dense(o.reshape(B, S, Hq * D), p["wo"], p.get("bo"))
            return shard_hint(out, "dp", None, None), new_cache
    else:
        kv_pos = positions if kv_pos_override is None else kv_pos_override
        if want_kv:
            new_cache = (k, v)

    o = attend(q, k, v, positions, kv_pos, causal=causal, window=window,
               kv_chunk=kv_chunk)
    out = dense(o.reshape(B, S, Hq * D), p["wo"], p.get("bo"))
    return shard_hint(out, "dp", None, None), new_cache
