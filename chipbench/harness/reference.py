"""Plain float32 reference of the served decoder, and its lower-precision
control. It imports nothing of the program and takes nothing the program
made: the weights are derived again from the seed.

The served weights are the program's `model.init` from the seed's key,
quantized by SplitQuant (k-means into k clusters, one affine INTb
quantizer per cluster) with keys split from the same key, one per leaf of
the parameter tree in its flattened order. This module repeats those
steps as plain jnp, leaf by leaf, and keeps the codes, cluster ids and
per-cluster constants; each layer is dequantized to float32 only while
it runs. The leaf order and the key splits are those of the program's
dense decoder with an untied head, the only architecture this reference
covers.

The forward pass is the decoder of the configuration file: RMS norm
(with the program's 1 + scale gain, scale initialised to 0) or LayerNorm
(gain 1 and bias 0 as initialised, eps 1e-5), q/k/v/o projections
without bias, RoPE on the first ``rotary`` share of each
head's channels (rotate-half form), causal softmax attention with grouped
K/V heads, SwiGLU feed-forward, final norm and head. Every matmul runs at
``precision=HIGHEST``. With ``control=True`` every matmul's two operands
are first rounded to float8 (e4m3, one scale per tensor): the nearest
precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the parameters of one norm, by the configuration's norm type, in
#: flattened (sorted-key) order
NORM_LEAVES = {"rms": ("norm_scale",), "layer": ("norm_bias", "norm_scale")}
NORM_EPS = {"rms": 1e-6, "layer": 1e-5}
QUANTIZED = ("wk", "wo", "wq", "wv", "w_down", "w_gate", "w_up", "lm_head")
SAMPLE_SIZE = 1 << 18
KMEANS_ITERS = 25
KMEANS_CANDIDATES = 4
QUERY_BLOCK = 256
#: share of each head's channels that RoPE rotates, by the program's name
ROPE_FRACTION = {"full": 1.0, "half": 0.5}


def seed_key(seed: int) -> jax.Array:
    """The build key of a run: a typed "rbg" key (the TPU's own random
    bit generator; threefry spends half a minute on 1.6e9 normals) from
    31 bits drawn from the seed, so any whole-number seed (also past 32
    bits) gives a valid key."""
    state = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)
    return jax.random.key(int(state[0]) >> 1, impl="rbg")


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


def _kmeans_centroids(key, x, k):
    """Lloyd's k-means on 1-D points after greedy k-means++ seeding
    (candidates drawn in proportion to the squared distance, the one that
    lowers the cost most kept); centroids sorted ascending."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = x[jax.random.randint(k0, (), 0, n)]
    centers = jnp.full((k,), first, dtype=x.dtype)
    d2 = (x - first) ** 2

    def pick(carry, key_i):
        centers, d2, i = carry
        total = jnp.sum(d2)
        logits = jnp.where(total > 0, jnp.log(jnp.maximum(d2, 1e-30)),
                           jnp.zeros_like(d2))
        cand = x[jax.random.categorical(key_i, logits,
                                        shape=(KMEANS_CANDIDATES,))]
        cost = jnp.sum(jnp.minimum(d2[:, None],
                                   (x[:, None] - cand[None, :]) ** 2), axis=0)
        chosen = cand[jnp.argmin(cost)]
        return (centers.at[i].set(chosen),
                jnp.minimum(d2, (x - chosen) ** 2), i + 1), None

    (centers, _, _), _ = jax.lax.scan(pick, (centers, d2, 1),
                                      jax.random.split(key, k - 1))

    def lloyd(centers, _):
        assign = jnp.argmin((x[:, None] - centers[None, :]) ** 2, axis=1)
        one_hot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
        counts = one_hot.sum(axis=0)
        sums = one_hot.T @ x
        return jnp.where(counts > 0, sums / jnp.maximum(counts, 1),
                         centers), None

    centers, _ = jax.lax.scan(lloyd, centers, None, length=KMEANS_ITERS)
    return jnp.sort(centers)


def _quantize_one(key, w, bits, k):
    """SplitQuant of one matrix: cluster ids by nearest centroid (fit on at
    most SAMPLE_SIZE strided samples), each cluster's range [min, max]
    mapped affinely onto the 2^bits codes (S = (2^b - 1) / span, Z =
    -2^(b-1) - rint(S * min); a single-valued cluster takes S = 1 / |v|)."""
    wf = w.astype(jnp.float32)
    flat = wf.reshape(-1)
    n = flat.shape[0]
    sample = flat[::n // SAMPLE_SIZE][:SAMPLE_SIZE] if n > SAMPLE_SIZE \
        else flat
    centroids = jax.jit(_kmeans_centroids, static_argnums=2)(key, sample, k)
    cid = jnp.argmin((wf[..., None] - centroids) ** 2,
                     axis=-1).astype(jnp.uint8)
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)

    def cluster_range(c):
        m = cid.reshape(-1) == c
        empty = ~jnp.any(m)
        lo = jnp.min(jnp.where(m, flat, big))
        hi = jnp.max(jnp.where(m, flat, -big))
        return jnp.where(empty, 0.0, lo), jnp.where(empty, 0.0, hi)

    beta, alpha = jax.vmap(cluster_range)(jnp.arange(k))
    span = alpha - beta
    amax = jnp.maximum(jnp.abs(beta), jnp.abs(alpha))
    single = jnp.where(amax > 0, 1.0 / jnp.where(amax > 0, amax, 1.0), 1.0)
    scale = jnp.where(span > 0, (2 ** bits - 1) / jnp.where(span > 0, span,
                                                            1.0), single)
    zero = -(2 ** (bits - 1)) - jnp.rint(scale * beta)
    q = jnp.rint(_select(scale, cid) * wf) + _select(zero, cid)
    q = jnp.clip(q, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1).astype(jnp.int8)
    return q, cid, scale, zero


@functools.partial(jax.jit, static_argnames=("bits", "k", "stacked"))
def _quantize_leaf(key, w, bits, k, stacked):
    fn = functools.partial(_quantize_one, bits=bits, k=k)
    if stacked:
        return jax.vmap(fn)(jax.random.split(key, w.shape[0]), w)
    return fn(key, w)


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
        out[name] = _quantize_leaf(keys[order.index(name)], w,
                                   bits=q["bits"], k=q["k"],
                                   stacked=name != "lm_head")
        jax.block_until_ready(out[name])
        del w
    return out


def _select(vals, cid):
    """vals[cid] per element, as a masked sum over the clusters: an
    elementwise pass, where a gather of one value per element is slow on
    a TPU."""
    return sum(jnp.where(cid == c, vals[..., c], 0.0)
               for c in range(vals.shape[-1]))


def _dequant(leaf, dtype):
    q, cid, scale, zero = leaf
    w = (q.astype(jnp.float32) - _select(zero, cid)) / _select(scale, cid)
    return w.astype(dtype).astype(jnp.float32)


# --------------------------------------------------------------- forward --
def _fp8(x):
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, control):
    if control:
        return jnp.einsum(eq, _fp8(a).astype(jnp.bfloat16),
                          _fp8(b).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


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
        sc = _mm("qhd,thd->hqt", qb, k, control)
        qi = s + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(T)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("hqt,thd->qhd", p, v, control))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _layer(x, wts, layer, a, control):
    """One decoder layer over x (B, T, d), dequantizing layer `layer` of
    each stacked leaf inside the program."""
    B, T, d = x.shape
    Hq, Hkv, D = a[2], a[3], a[4]
    w = {n: _dequant(tuple(jax.lax.dynamic_index_in_dim(t, layer, 0, False)
                           for t in wts[n]), a[9])
         for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    h = _norm(x, a)
    q = _rope(_mm("btd,de->bte", h, w["wq"], control).reshape(B, T, Hq, D), a)
    k = _rope(_mm("btd,de->bte", h, w["wk"], control).reshape(B, T, Hkv, D),
              a)
    v = _mm("btd,de->bte", h, w["wv"], control).reshape(B, T, Hkv, D)
    o = jax.lax.map(lambda qkv: _attention(*qkv, a, control), (q, k, v))
    x = x + _mm("bte,ed->btd", o.reshape(B, T, Hq * D), w["wo"], control)
    h = _norm(x, a)
    g = jax.nn.silu(_mm("btd,df->btf", h, w["w_gate"], control))
    u = _mm("btd,df->btf", h, w["w_up"], control)
    return x + _mm("btf,fd->btd", g * u, w["w_down"], control)


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _head(h, head, a, control, tok, ctl):
    """Logit statistics of rows h (R, d): with control, each row's argmax;
    else each row's best logit minus the logit of `tok` and of `ctl`, in
    units of the row's standard deviation."""
    logits = _mm("rd,dv->rv", _norm(h, a), _dequant(head, a[9]), control)
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
    return np.asarray(_mm("btd,dv->btv", _norm(x, a),
                          _dequant(wts["lm_head"], a[9]), False))
