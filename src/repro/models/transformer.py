"""Decoder-only LM covering the dense / MoE / VLM families.

Layers are stacked (leading L axis) and executed with ``jax.lax.scan`` +
``jax.checkpoint`` so HLO size and compile time are depth-independent (a
126-layer llama3-405b compiles as one scanned block). Heterogeneous stacks
(DeepSeek-style leading dense layers before MoE) are two scans.

The parts of a forward pass carry names (``jax.named_scope``, which sets
each op's ``op_name`` metadata and changes nothing else), so a profiler
trace splits the device time by part: ``embed``; ``layers``, the scan,
whose own slicing of each layer's weights and cache and stacking of the
new cache lies outside ``layer``, the scan body; and ``lm_head``, the
final norm and the head projection.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import KVCache, attention_block
from .common import (apply_norm, dense, dtype_of, embed_init, embed_lookup,
                     he_init, init_norm, shard_hint, stack_layer_init)
from .ffn import apply_ffn, apply_moe, init_ffn, init_moe

VLM_PATCH_DIM = 1152          # SigLIP-so400m embedding width (stub frontend)


def _init_layer(key, cfg, dtype, moe: bool):
    ka, kf = jax.random.split(key)
    d, Hq, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(ka, 4)
    p = {
        "attn": {
            "wq": he_init(kq, (d, Hq * D), dtype),
            "wk": he_init(kk, (d, Hkv * D), dtype),
            "wv": he_init(kv, (d, Hkv * D), dtype),
            "wo": he_init(ko, (Hq * D, d), dtype, fan_in=Hq * D),
        },
        "ln1": init_norm(d, cfg.norm_type, dtype),
        "ln2": init_norm(d, cfg.norm_type, dtype),
    }
    if moe:
        p["moe"] = init_moe(kf, cfg, dtype)
    else:
        ff = cfg.dense_d_ff or cfg.d_ff
        if cfg.n_experts and not cfg.dense_d_ff:
            ff = cfg.d_ff * max(cfg.top_k, 1)   # dense prelude matches act. width
        p["ffn"] = init_ffn(kf, cfg.d_model, ff, cfg.ffn_type, dtype,
                            bias=cfg.bias)
    return p


def init(key, cfg):
    dtype = dtype_of(cfg.param_dtype)
    ke, kl, kd, kh, kp = jax.random.split(key, 5)
    n_moe = cfg.n_layers - cfg.first_k_dense if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    params = {"embed": embed_init(ke, (cfg.vocab, cfg.d_model), dtype),
              "final_norm": init_norm(cfg.d_model, cfg.norm_type, dtype)}
    if n_dense:
        params["layers"] = stack_layer_init(
            lambda k: _init_layer(k, cfg, dtype, moe=False), kl, n_dense)
    if n_moe:
        params["moe_layers"] = stack_layer_init(
            lambda k: _init_layer(k, cfg, dtype, moe=True), kd, n_moe)
    if not cfg.tie_embeddings:
        params["lm_head"] = he_init(kh, (cfg.d_model, cfg.vocab), dtype)
    if cfg.family == "vlm":
        params["patch_proj"] = he_init(kp, (VLM_PATCH_DIM, cfg.d_model), dtype)
    return params


def _layer_apply(cfg, p, x, positions, cache_layer, *, moe: bool,
                 kv_chunk, want_kv: bool, moe_blocks: int = 1,
                 tshard_decode: bool = False, kv_pos_override=None,
                 fused_attn: bool = False, slot_chunk=None,
                 spec_verify: bool = False):
    x = shard_hint(x, "dp", None, None)
    h = apply_norm(x, p["ln1"], cfg.norm_type)
    attn_out, kv = attention_block(
        p["attn"], h, cfg, positions, cache_layer,
        causal=cfg.family != "encoder", window=cfg.window,
        kv_chunk=kv_chunk, want_kv=want_kv, tshard_decode=tshard_decode,
        kv_pos_override=kv_pos_override, fused_attn=fused_attn,
        slot_chunk=slot_chunk, spec_verify=spec_verify)
    x = x + attn_out
    h = apply_norm(x, p["ln2"], cfg.norm_type)
    if moe:
        ffn_out, aux = apply_moe(p["moe"], h, cfg, n_blocks=moe_blocks)
    else:
        ffn_out, aux = apply_ffn(p["ffn"], h, cfg.ffn_type), jnp.float32(0)
    return x + ffn_out, kv, aux


def _scan_stack(cfg, stacked, x, positions, cache, *, moe, kv_chunk,
                want_kv, remat, moe_blocks=1, tshard_decode=False,
                kv_pos_override=None, fused_attn=False, slot_chunk=None,
                spec_verify=False):
    """Scan a homogeneous stacked layer group. cache: per-stack KVCache,
    engine SlotKVCache, or None. Returns (x, new_cache_or_kv, aux_sum)."""
    fn = functools.partial(_layer_apply, cfg, moe=moe, kv_chunk=kv_chunk,
                           want_kv=want_kv, moe_blocks=moe_blocks,
                           tshard_decode=tshard_decode,
                           kv_pos_override=kv_pos_override,
                           fused_attn=fused_attn, slot_chunk=slot_chunk,
                           spec_verify=spec_verify)
    if remat:
        fn = jax.checkpoint(fn, static_argnums=())
    with jax.named_scope("layers"):
        return _scan_layers(fn, stacked, x, positions, cache, want_kv)


def _scan_layers(fn, stacked, x, positions, cache, want_kv):
    """The scan itself; ``layer`` names its body."""
    def fn_scoped(*a):
        with jax.named_scope("layer"):
            return fn(*a)

    if cache is not None and not isinstance(cache, KVCache):
        # engine slot cache: scan the dataclass itself — every data leaf
        # has leading L, so each step sees a per-layer SlotKVCache slice
        def step(carry, xs):
            x, aux = carry
            lp, cl = xs
            x, new_cl, a = fn_scoped(lp, x, positions, cl)
            return (x, aux + a), new_cl
        (x, aux), new_cache = jax.lax.scan(step, (x, jnp.float32(0)),
                                           (stacked, cache))
        return x, new_cache, aux

    if cache is not None:
        def step(carry, xs):
            x, aux = carry
            lp, ck, cv, sp = xs
            x, new_c, a = fn_scoped(lp, x, positions, (ck, cv, sp))
            return (x, aux + a), new_c
        (x, aux), ys = jax.lax.scan(step, (x, jnp.float32(0)),
                                    (stacked, cache.k, cache.v, cache.slot_pos))
        new_cache = KVCache(k=ys[0], v=ys[1], slot_pos=ys[2])
        return x, new_cache, aux

    def step(carry, lp):
        x, aux = carry
        x, kv, a = fn_scoped(lp, x, positions, None)
        return (x, aux + a), kv if want_kv else None
    (x, aux), ys = jax.lax.scan(step, (x, jnp.float32(0)), stacked)
    return x, ys, aux


def embed_inputs(params, cfg, batch):
    """tokens (+ optional VLM patch embeds) → (B, S, d), positions (S,)."""
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        patches = dense(batch["patch_embeds"].astype(x.dtype),
                        params["patch_proj"])
        x = jnp.concatenate([patches, x], axis=1)
    S = x.shape[1]
    return x, jnp.arange(S, dtype=jnp.int32)


def forward(params, cfg, batch, cache: Optional[KVCache] = None,
            positions=None, *, kv_chunk=None, want_cache=False, remat=False,
            cache_len: Optional[int] = None, moe_blocks: int = 1,
            tshard_decode: bool = False, pad_mask=None,
            fused_attn: bool = False, slot_chunk=None,
            spec_verify: bool = False):
    """Returns (logits, new_cache, aux). cache ⇒ decode step (a KVCache, or
    an engine SlotKVCache with per-request positions); want_cache ⇒ prefill
    (assembles a fresh cache from the computed K/V). pad_mask (B, S) marks
    True=padding tokens whose K/V must never be attended to (left- or
    right-padded batched prefill). fused_attn routes slot-cache decode
    through the fused dequant-in-kernel attention. slot_chunk (slot,
    pos_start, length) + a SlotKVCache ⇒ chunked prefill of one slot:
    `positions` are the chunk's absolute positions and each layer's K/V is
    quantized in-kernel and written straight into the slot cache instead
    of assembling a dense prefill cache. spec_verify (with slot_chunk) ⇒
    the chunk is a speculative DRAFT WINDOW: attention round-trips the
    window's own K/V through cache storage so each row scores like a plain
    decode step, and logits for EVERY window row are returned (the accept
    rule compares per-position argmax)."""
    with jax.named_scope("embed"):
        if cache is not None:
            x = embed_lookup(params["embed"], batch["tokens"])  # (B, 1)
        else:
            x, positions = (
                embed_inputs(params, cfg, batch) if positions is None else
                (embed_lookup(params["embed"], batch["tokens"]), positions))

    kv_pos_override = None
    if pad_mask is not None and cache is None:
        kv_pos_override = jnp.where(pad_mask, jnp.int32(-1),
                                    positions[None, :].astype(jnp.int32))

    n_moe = (cfg.n_layers - cfg.first_k_dense) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    aux = jnp.float32(0)
    caches, kvs = [], []
    want_kv = want_cache

    def split_cache(cache, lo, hi):
        if cache is None:
            return None
        # every cache leaf carries leading L (KVCache and SlotKVCache alike)
        return jax.tree_util.tree_map(lambda a: a[lo:hi], cache)

    if n_dense:
        x, c, a = _scan_stack(cfg, params["layers"], x, positions,
                              split_cache(cache, 0, n_dense), moe=False,
                              kv_chunk=kv_chunk, want_kv=want_kv, remat=remat,
                              tshard_decode=tshard_decode,
                              kv_pos_override=kv_pos_override,
                              fused_attn=fused_attn, slot_chunk=slot_chunk,
                              spec_verify=spec_verify)
        aux += a
        (caches if cache is not None else kvs).append(c)
    if n_moe:
        x, c, a = _scan_stack(cfg, params["moe_layers"], x, positions,
                              split_cache(cache, n_dense, cfg.n_layers),
                              moe=True, kv_chunk=kv_chunk, want_kv=want_kv,
                              remat=remat, moe_blocks=moe_blocks,
                              tshard_decode=tshard_decode,
                              kv_pos_override=kv_pos_override,
                              fused_attn=fused_attn, slot_chunk=slot_chunk,
                              spec_verify=spec_verify)
        aux += a
        (caches if cache is not None else kvs).append(c)

    with jax.named_scope("lm_head"):
        logits = _lm_head(params, cfg, x, slot_chunk, spec_verify)

    new_cache = None
    if cache is not None:
        new_cache = (caches[0] if len(caches) == 1 else
                     jax.tree_util.tree_map(
                         lambda *xs: jnp.concatenate(xs, 0), *caches))
    elif want_cache:
        new_cache = assemble_cache(cfg, kvs, positions, max_len=cache_len,
                                   pad_mask=pad_mask)
    return logits, new_cache, aux


def _lm_head(params, cfg, x, slot_chunk, spec_verify):
    if slot_chunk is not None and not spec_verify:
        # chunk prefill consumes ONLY the last valid token's logits (the
        # first-generated-token sample on the prompt's final chunk) —
        # slice before the head so the vocab projection is (1, 1, V)
        # instead of (1, Sc, V) per chunk. A verify window keeps every
        # row: the accept rule needs the target's argmax per position.
        x = jax.lax.dynamic_slice_in_dim(x, slot_chunk[2] - 1, 1, axis=1)
    x = apply_norm(x, params["final_norm"], cfg.norm_type)
    head = params.get("lm_head", None)
    if head is None:
        table = params["embed"]
        if hasattr(table, "dequantize"):
            table = table.dequantize()
        logits = jnp.dot(x, table.T.astype(x.dtype))
    else:
        logits = dense(x, head)
    return shard_hint(logits.astype(jnp.float32), "dp", None, "tp")


def assemble_cache(cfg, kvs, positions, max_len: Optional[int] = None,
                   pad_mask=None):
    """Build a decode cache from prefill K/V. Windowed attention keeps a
    ring of the last `window` positions; global keeps everything (padded to
    max_len if given). With pad_mask (B, S), slot_pos becomes per-request
    (L, B, T) and padded entries are marked -1 (never attended)."""
    k = jnp.concatenate([kv[0] for kv in kvs], axis=0)   # (L, B, S, Hkv, D)
    v = jnp.concatenate([kv[1] for kv in kvs], axis=0)
    L, B, S = k.shape[0], k.shape[1], k.shape[2]
    if cfg.window is not None and S > cfg.window:
        W = cfg.window
        k, v = k[:, :, -W:], v[:, :, -W:]
        pos = positions[-W:]
        # ring layout: slot = pos % W
        slot = pos % W
        inv = jnp.argsort(slot)
        k, v, pos = k[:, :, inv], v[:, :, inv], pos[inv]
        if pad_mask is not None:
            padb = pad_mask[:, -W:][:, inv]              # (B, W) ring order
            sp = jnp.where(padb, -1, pos[None, :]).astype(jnp.int32)
            return KVCache(k, v, jnp.broadcast_to(sp, (L, B, W)))
        slot_pos = jnp.broadcast_to(pos, (L, W)).astype(jnp.int32)
        return KVCache(k, v, slot_pos)
    T = max_len or S
    pad = T - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    sp = jnp.concatenate([positions.astype(jnp.int32),
                          jnp.full((pad,), -1, jnp.int32)])
    if pad_mask is not None:
        padb = jnp.pad(pad_mask, ((0, 0), (0, pad)), constant_values=True)
        sp = jnp.where(padb, -1, sp[None, :]).astype(jnp.int32)  # (B, T)
        return KVCache(k, v, jnp.broadcast_to(sp, (L, B, T)))
    return KVCache(k, v, jnp.broadcast_to(sp, (L, T)))


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    T = min(cfg.window, max_len) if cfg.window else max_len
    shape = (cfg.n_layers, batch_size, T, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   slot_pos=jnp.full((cfg.n_layers, T), -1, jnp.int32))


def loss_fn(params, cfg, batch, *, kv_chunk=None, remat=True,
            aux_weight=0.01, moe_blocks=1):
    logits, _, aux = forward(params, cfg, batch, kv_chunk=kv_chunk,
                             remat=remat, moe_blocks=moe_blocks)
    labels = batch["labels"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        logits = logits[:, -labels.shape[1]:]          # loss on text tokens
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss + aux_weight * aux, {"loss": loss, "aux": aux}


def decode_step(params, cfg, cache: KVCache, tokens, pos, *, kv_chunk=None,
                tshard=False):
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 position.
    ``tshard``: use the time-sharded ring decode attention (TP-resident
    cache when kv_heads < TP)."""
    positions = jnp.reshape(pos, (1,)).astype(jnp.int32)
    logits, cache, _ = forward(params, cfg, {"tokens": tokens}, cache=cache,
                               positions=positions, kv_chunk=kv_chunk,
                               tshard_decode=tshard)
    return logits, cache


def decode_step_slots(params, cfg, cache, tokens, pos, *, kv_chunk=None,
                      fused=False):
    """One decode step over an engine slot cache. tokens: (N, 1) int32;
    pos: (N,) int32 per-slot absolute positions (one per request — slots
    at different depths decode together). ``fused``: attention reads the
    (possibly INT8) cache through the fused dequant-in-kernel path instead
    of materializing a full-precision copy."""
    positions = jnp.reshape(pos, (-1, 1)).astype(jnp.int32)
    logits, cache, _ = forward(params, cfg, {"tokens": tokens}, cache=cache,
                               positions=positions, kv_chunk=kv_chunk,
                               fused_attn=fused)
    return logits, cache


def prefill_chunk_slots(params, cfg, cache, tokens, slot, pos_start,
                        length, *, kv_chunk=None):
    """CHUNKED prefill of ONE slot straight into the engine slot cache:
    process a chunk of prompt tokens at absolute positions
    [pos_start, pos_start + Sc), quantize each layer's K/V in-kernel and
    scatter the codes into the slot's rows — no dense (L, S, Hkv, D)
    prefill cache is ever assembled (contrast `prefill` +
    `engine.kvcache.write_prefill`, the legacy one-shot path).

    tokens: (1, Sc) int32 (right-padded to a chunk bucket); slot /
    pos_start / length are traced scalars, `length` <= Sc the number of
    real prompt tokens. Returns (last_logits (1, V), cache) where
    last_logits is the logits row of the chunk's FINAL valid token — the
    engine samples the first generated token from it on the prompt's last
    chunk and ignores it otherwise.
    """
    Sc = tokens.shape[1]
    positions = (jnp.asarray(pos_start, jnp.int32)
                 + jnp.arange(Sc, dtype=jnp.int32))
    logits, cache, _ = forward(
        params, cfg, {"tokens": tokens}, cache=cache, positions=positions,
        kv_chunk=kv_chunk, slot_chunk=(slot, pos_start, length))
    return logits[:, 0], cache                 # head already sliced to the
    # chunk's last valid token (see forward's slot_chunk branch)


def verify_step_slots(params, cfg, cache, tokens, slot, pos_start, length,
                      *, kv_chunk=None):
    """Speculative-decoding VERIFY: score a draft window of ONE slot in a
    single fused pass (DESIGN.md §9). A draft window *is* a prefill chunk
    — the window's queries attend the slot's already-committed (possibly
    INT8) prefix plus the window's own K/V, each layer's window K/V is
    quantized in-kernel and scattered into rows
    [pos_start, pos_start + Sq), and — unlike plain chunked prefill —
    every row attends the window THROUGH the storage round-trip and every
    row's logits are returned, so row j's argmax equals the token a plain
    decode step would have produced after window token j. The engine's
    accept rule then keeps the longest matching draft prefix plus the
    target's own correction token; rejected rows are undone with
    `engine.kvcache.rollback_slot`.

    tokens: (1, Sq) int32 — [last committed token, draft tokens...],
    right-padded to the spec window bucket; slot / pos_start / length are
    traced scalars, `length` <= Sq the real window size. Returns
    (logits (1, Sq, V), cache); rows at >= length are padding garbage the
    caller ignores.
    """
    Sq = tokens.shape[1]
    positions = (jnp.asarray(pos_start, jnp.int32)
                 + jnp.arange(Sq, dtype=jnp.int32))
    logits, cache, _ = forward(
        params, cfg, {"tokens": tokens}, cache=cache, positions=positions,
        kv_chunk=kv_chunk, slot_chunk=(slot, pos_start, length),
        spec_verify=True)
    return logits, cache


def prefill(params, cfg, batch, max_len: Optional[int] = None, *,
            kv_chunk=None, moe_blocks: int = 1, pad_mask=None):
    logits, cache, _ = forward(params, cfg, batch, kv_chunk=kv_chunk,
                               want_cache=True, cache_len=max_len,
                               moe_blocks=moe_blocks, pad_mask=pad_mask)
    return logits, cache
