"""The dense decoder: the plain float32 reference of the served model, its
lower-precision control, and the operations and bytes its steps need.
The architecture of every configuration file that names no other
(``harness/arch.py``).

The reference imports nothing of the program and takes nothing the
program made: the weights are derived again from the seed. The served
weights are the program's `model.init` from the seed's key, quantized by
SplitQuant with keys split from the same key, one per leaf of the
parameter tree in its flattened order. This module draws each leaf as
the program's dense decoder with an untied head draws it and hands it to
``harness/refquant.py``, which keeps the codes, cluster ids and
per-cluster constants; each layer is dequantized to float32 only while
it runs.

The forward pass is the decoder of the configuration file: RMS norm
(with the program's 1 + scale gain, scale initialised to 0) or LayerNorm
(gain 1 and bias 0 as initialised, eps 1e-5), q/k/v/o projections
without bias, RoPE on the first ``rotary`` share of each
head's channels (rotate-half form), causal softmax attention with grouped
K/V heads, SwiGLU feed-forward, final norm and head. Every matmul runs at
``precision=HIGHEST``. With ``control=True`` every matmul's two operands
are first rounded to float8 (e4m3, one scale per tensor): the nearest
precision below the bfloat16 the configurations state.

The counts (below the reference) are what the algorithm needs, from
shapes, as ``harness/counts.py`` states: a SplitQuant weight element is
its code plus its cluster id, bits + ceil(log2 k) bits; the int8 KV
cache stores one byte per element and an fp32 scale and zero per
sub-channel chunk.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.counts import _chunk_keys
from harness.refquant import dequant, mm, quantize_leaf, seed_key

#: the parameters of one norm, by the configuration's norm type, in
#: flattened (sorted-key) order
NORM_LEAVES = {"rms": ("norm_scale",), "layer": ("norm_bias", "norm_scale")}
NORM_EPS = {"rms": 1e-6, "layer": 1e-5}
QUANTIZED = ("wk", "wo", "wq", "wv", "w_down", "w_gate", "w_up", "lm_head")
QUERY_BLOCK = 256
#: fields of the program's ArchConfig that this reference and its counts
#: do not model, at the values that leave the program a dense decoder;
#: fields a dense decoder never reads (capacity_factor, block_pattern,
#: conv_width, lru_width, n_enc_layers, ...) may take any value
ASSUMED = {"family": "dense", "n_experts": 0, "top_k": 0,
           "n_shared_experts": 0, "first_k_dense": 0, "dense_d_ff": 0,
           "window": None, "ffn_type": "swiglu", "bias": False,
           "tie_embeddings": False}
#: share of each head's channels that RoPE rotates, by the program's name
ROPE_FRACTION = {"full": 1.0, "half": 0.5}


def arch(conf: dict) -> tuple:
    """The hashable numbers of a configuration file this module uses."""
    if (conf["norm"] not in NORM_LEAVES or (
            conf["ffn"], conf.get("bias", False),
            conf.get("tie_embeddings", False)) != ("swiglu", False, False)):
        raise NotImplementedError("the reference covers RMS-norm or "
                                  "LayerNorm SwiGLU decoders without biases "
                                  "or tied head")
    return (conf["n_layers"], conf["d_model"], conf["n_heads"],
            conf["n_kv_heads"], conf["head_dim"], conf["d_ff"],
            conf["vocab"], float(conf["rope_theta"]),
            ROPE_FRACTION[conf["rope_variant"]], conf["param_dtype"],
            conf["norm"])


def leaf_order(norm: str) -> list[str]:
    """Leaves of the served parameter tree in flattened (sorted-key) order;
    quantize_tree hands leaf i the i-th of this many keys split from the
    build key."""
    def n(group):
        return [f"{group}.{p}" for p in NORM_LEAVES[norm]]
    return (["embed", *n("final_norm"), "wk", "wo", "wq", "wv", "w_down",
             "w_gate", "w_up", *n("ln1"), *n("ln2"), "lm_head"])


# --------------------------------------------------------------- weights --
def _shapes(a):
    L, d, Hq, Hkv, D, ff, V = a[:7]
    return {"wq": (d, Hq * D, d), "wk": (d, Hkv * D, d),
            "wv": (d, Hkv * D, d), "wo": (Hq * D, d, Hq * D),
            "w_gate": (d, ff, d), "w_up": (d, ff, d),
            "w_down": (ff, d, ff)}


@functools.partial(jax.jit, static_argnames=("a", "name"))
def _init_leaf(key, a, name):
    """One leaf of the served tree, drawn as the program's init draws it:
    ke, kl, _, kh, _ = split(key, 5); layer i of a stacked leaf from
    split(kl, L)[i] -> (ka, kf); ka -> (kq, kk, kv, ko); kf -> (k1, k2,
    k3); weights normal * sqrt(2 / fan_in), the embedding normal * 0.02."""
    L, d, V = a[0], a[1], a[6]
    dtype = jnp.dtype(a[9])
    ke, kl, _, kh, _ = jax.random.split(key, 5)
    if name == "embed":
        return (jax.random.normal(ke, (V, d)) * 0.02).astype(dtype)
    if name == "lm_head":
        return (jax.random.normal(kh, (d, V)) * (2.0 / d) ** 0.5
                ).astype(dtype)
    rows, cols, fan = _shapes(a)[name]

    def one(k):
        ka, kf = jax.random.split(k)
        kq, kk, kv, ko = jax.random.split(ka, 4)
        k1, k2, k3 = jax.random.split(kf, 3)
        kw = {"wq": kq, "wk": kk, "wv": kv, "wo": ko,
              "w_gate": k1, "w_up": k2, "w_down": k3}[name]
        return (jax.random.normal(kw, (rows, cols)) * (2.0 / fan) ** 0.5
                ).astype(dtype)

    return jax.vmap(one)(jax.random.split(kl, L))


def derive_weights(conf: dict, seed: int) -> dict:
    """{leaf: (codes, cluster ids, scales, zeros)} for the quantized
    leaves and the embedding table as served, one leaf live at a time."""
    a = arch(conf)
    q = conf["quant"]
    key = seed_key(seed)
    order = leaf_order(conf["norm"])
    keys = jax.random.split(key, len(order))
    out = {"embed": _init_leaf(key, a, "embed")}
    for name in QUANTIZED:
        w = _init_leaf(key, a, name)
        out[name] = quantize_leaf(keys[order.index(name)], w,
                                  bits=q["bits"], k=q["k"],
                                  stacked=name != "lm_head")
        jax.block_until_ready(out[name])
        del w
    return out


# --------------------------------------------------------------- forward --
def _norm(x, a):
    """The norm at its initial parameters: RMS (gain 1 + 0) or LayerNorm
    (gain 1, bias 0)."""
    norm = a[10]
    if norm == "layer":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + NORM_EPS[norm])


def _rope(x, a):
    """x (B, T, H, D); rotate-half RoPE on the first rd = fraction * D
    channels with theta ** (-2i / rd) frequencies."""
    D, theta, frac = a[4], a[7], a[8]
    rd = int(D * frac)
    T = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv    # (T, rd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], axis=-1)


def _attention(q, k, v, a, control):
    """Causal attention of one sequence: q (T, Hq, D), k/v (T, Hkv, D);
    query blocks of QUERY_BLOCK rows bound the score matrix."""
    Hq, Hkv, D = a[2], a[3], a[4]
    T = q.shape[0]
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    outs = []
    for s in range(0, T, QUERY_BLOCK):
        qb = q[s:s + QUERY_BLOCK] * D ** -0.5
        sc = mm("qhd,thd->hqt", qb, k, control)
        qi = s + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(T)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("hqt,thd->qhd", p, v, control))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _layer(x, wts, layer, a, control):
    """One decoder layer over x (B, T, d), dequantizing layer `layer` of
    each stacked leaf inside the program."""
    B, T, d = x.shape
    Hq, Hkv, D = a[2], a[3], a[4]
    w = {n: dequant(tuple(jax.lax.dynamic_index_in_dim(t, layer, 0, False)
                          for t in wts[n]), a[9])
         for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    h = _norm(x, a)
    q = _rope(mm("btd,de->bte", h, w["wq"], control).reshape(B, T, Hq, D), a)
    k = _rope(mm("btd,de->bte", h, w["wk"], control).reshape(B, T, Hkv, D),
              a)
    v = mm("btd,de->bte", h, w["wv"], control).reshape(B, T, Hkv, D)
    o = jax.lax.map(lambda qkv: _attention(*qkv, a, control), (q, k, v))
    x = x + mm("bte,ed->btd", o.reshape(B, T, Hq * D), w["wo"], control)
    h = _norm(x, a)
    g = jax.nn.silu(mm("btd,df->btf", h, w["w_gate"], control))
    u = mm("btd,df->btf", h, w["w_up"], control)
    return x + mm("btf,fd->btd", g * u, w["w_down"], control)


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _head(h, head, a, control, tok, ctl):
    """Logit statistics of rows h (R, d): with control, each row's argmax;
    else each row's best logit minus the logit of `tok` and of `ctl`, in
    units of the row's standard deviation."""
    logits = mm("rd,dv->rv", _norm(h, a), dequant(head, a[9]), control)
    if control:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    best = jnp.max(logits, axis=-1)
    sd = jnp.std(logits, axis=-1)
    at = lambda t: jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return (best - at(tok)) / sd, (best - at(ctl)) / sd


def _hidden(conf, wts, seqs, control, shape=None):
    """Final hidden states of the sequences, right-padded into a (B, T)
    batch: `shape` if given (one compiled program for every run of a
    cell), else the least that holds them."""
    a = arch(conf)
    B, T = shape or (len(seqs), -(-max(len(s) for s in seqs)
                                  // QUERY_BLOCK) * QUERY_BLOCK)
    toks = np.zeros((B, T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = jnp.take(wts["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    stacked = {n: wts[n] for n in QUANTIZED if n != "lm_head"}
    for layer in range(a[0]):
        x = _layer(x, stacked, jnp.int32(layer), a, control)
    return x


ROW_BLOCK = 128


def _rows(conf, wts, x, rows, control, tok=None, ctl=None):
    """_head over the rows in blocks of ROW_BLOCK (the last one padded)."""
    a = arch(conf)
    R = len(rows[0])
    pad = -R % ROW_BLOCK
    seq, pos = (np.concatenate([r, np.zeros(pad, r.dtype)]) for r in rows)
    tok, ctl = (None if v is None else
                np.concatenate([v, np.zeros(pad, np.int32)])
                for v in (tok, ctl))
    out = []
    for s in range(0, R + pad, ROW_BLOCK):
        sl = slice(s, s + ROW_BLOCK)
        h = x[jnp.asarray(seq[sl]), jnp.asarray(pos[sl])]
        t = None if tok is None else jnp.asarray(tok[sl])
        c = None if ctl is None else jnp.asarray(ctl[sl])
        out.append(jax.device_get(_head(h, wts["lm_head"], a, control, t,
                                        c)))
    if control:
        return np.concatenate(out)[:R]
    return (np.concatenate([o[0] for o in out])[:R],
            np.concatenate([o[1] for o in out])[:R])


def gaps(conf: dict, wts: dict, seqs, rows, served, control: bool,
         shape=None):
    """For each row (sequence index, position) the gap, in the reference
    row's standard deviations, between the reference's best logit and its
    logit of the served token at the next position; with ``control`` also
    the gap of the token that the float8 control ranks first there.
    Returns (served gaps, control gaps or None)."""
    ctl = None
    if control:
        xc = _hidden(conf, wts, seqs, True, shape)
        ctl = _rows(conf, wts, xc, rows, True)
        del xc
    x = _hidden(conf, wts, seqs, False, shape)
    g, gc = _rows(conf, wts, x, rows, False, np.asarray(served, np.int32),
                  ctl if ctl is not None else np.asarray(served, np.int32))
    return g, (gc if control else None)


def logits(conf: dict, wts: dict, seqs) -> np.ndarray:
    """Full logits (B, T, V) of right-padded sequences: for checking the
    reference itself at small sizes."""
    a = arch(conf)
    x = _hidden(conf, wts, seqs, False)
    return np.asarray(mm("btd,dv->btv", _norm(x, a),
                         dequant(wts["lm_head"], a[9]), False))


# ---------------------------------------------------------------- counts --
ACT_BYTES = 2        # bfloat16 activations (q, k, v, attention output)


def layer_matrix_elements(c: dict) -> int:
    d, Hq, Hkv, D, ff = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                         c["head_dim"], c["d_ff"])
    return d * Hq * D + 2 * d * Hkv * D + Hq * D * d + 3 * d * ff


def weight_elements(c: dict) -> int:
    """Matmul weight elements of the model: every layer and the head."""
    return c["n_layers"] * layer_matrix_elements(c) \
        + c["d_model"] * c["vocab"]


def weight_bytes(c: dict) -> float:
    """Least bytes to read every quantized matrix once: codes and cluster
    ids, plus each matrix's per-cluster scale and zero (fp32)."""
    q = c["quant"]
    per_elt = (q["bits"] + math.ceil(math.log2(q["k"]))) / 8
    n_mats = 7 * c["n_layers"] + 1
    return weight_elements(c) * per_elt + n_mats * q["k"] * 2 * 4


def kv_bytes_per_token_layer(c: dict) -> int:
    """Stored bytes of one token's K and V in one layer."""
    Hkv, D = c["n_kv_heads"], c["head_dim"]
    if c["kv_mode"] == "int8":
        return 2 * (Hkv * D + Hkv * c["kv_qchunks"] * 2 * 4)
    return 2 * Hkv * D * ACT_BYTES


def kv_bytes_per_token(c: dict) -> int:
    return c["n_layers"] * kv_bytes_per_token_layer(c)


def attn_flops(c: dict, keys: int) -> float:
    """QK^T and PV of one query over `keys` keys, every layer."""
    return 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * keys


def token_flops(c: dict, keys: int) -> float:
    """Model FLOPs of one token that attends `keys` keys: 2 per matmul
    weight element plus attention."""
    return 2.0 * weight_elements(c) + attn_flops(c, keys)


# ----------------------------------------------------------- decode step --
def decode_step_bytes(c: dict, positions) -> float:
    """Least bytes of one decode step whose active slots sit at
    `positions`: the weights once, the embedding rows and norm vectors
    (a gain, and a bias under LayerNorm), each active slot's live KV rows
    (positions 0..p) read and its new row written."""
    n = len(positions)
    d, L = c["d_model"], c["n_layers"]
    per_norm = 2 if c["norm"] == "layer" else 1
    unquantized = n * d * 2 + (2 * L + 1) * per_norm * d * 2
    kv = kv_bytes_per_token(c)
    return weight_bytes(c) + unquantized \
        + sum(p + 1 for p in positions) * kv + n * kv


def decode_step_flops(c: dict, positions) -> float:
    return sum(token_flops(c, p + 1) for p in positions)


def decode_attn_cost(c: dict, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of the decode-attention kernel over every layer of
    one step: live rows' codes and scales, q and the output."""
    L, Hq, D = c["n_layers"], c["n_heads"], c["head_dim"]
    keys = sum(p + 1 for p in positions)
    flops = attn_flops(c, keys)
    bytes_ = keys * kv_bytes_per_token(c) \
        + len(positions) * L * 2 * Hq * D * ACT_BYTES
    return flops, bytes_


# ---------------------------------------------------------- chunk prefill --
def chunk_flops(c: dict, pos_start: int, n: int) -> float:
    """n prompt tokens through every layer, and the head for the chunk's
    last token (the only logits row a chunk needs)."""
    d, V = c["d_model"], c["vocab"]
    return 2.0 * n * (weight_elements(c) - d * V) + 2.0 * d * V \
        + attn_flops(c, _chunk_keys(pos_start, n))


def chunk_bytes(c: dict, pos_start: int, n: int) -> float:
    """The weights once, n embedding rows, the slot's prefix rows read and
    the chunk's rows written."""
    d = c["d_model"]
    kv = kv_bytes_per_token(c)
    return weight_bytes(c) + n * d * 2 + pos_start * kv + n * kv


def prefill_attn_cost(c: dict, pos_start: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the prefill-attention kernel over every layer of
    one chunk: the prefix rows read, the chunk's q, k, v in and output
    out, its codes and scales written."""
    L, Hq, Hkv, D = (c["n_layers"], c["n_heads"], c["n_kv_heads"],
                     c["head_dim"])
    flops = attn_flops(c, _chunk_keys(pos_start, n))
    kv = kv_bytes_per_token(c)
    bytes_ = pos_start * kv + n * kv \
        + L * n * (2 * Hq + 2 * Hkv) * D * ACT_BYTES
    return flops, bytes_
