"""The plain replica of the program's weight path that every
architecture's reference shares: the build key from the seed, SplitQuant
of one leaf (k-means into k clusters, one affine INTb quantizer per
cluster) as plain jnp, dequantization, and the reference's matmul, at
``precision=HIGHEST`` or, for the control, through float8. It imports
nothing of the program and takes nothing the program made; an
architecture module (``architectures/<name>.py``) draws its leaves as the
program's ``model.init`` draws them and hands each to ``quantize_leaf``
with the key ``quantize_tree`` gives it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_SIZE = 1 << 18
KMEANS_ITERS = 25
KMEANS_CANDIDATES = 4


def seed_key(seed: int) -> jax.Array:
    """The build key of a run: a typed "rbg" key (the TPU's own random
    bit generator; threefry spends half a minute on 1.6e9 normals) from
    31 bits drawn from the seed, so any whole-number seed (also past 32
    bits) gives a valid key."""
    state = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)
    return jax.random.key(int(state[0]) >> 1, impl="rbg")


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
def quantize_leaf(key, w, bits, k, stacked):
    """(codes, cluster ids, scales, zeros) of one leaf; a `stacked` leaf's
    matrices take keys split from `key`, one each, as the program's do."""
    fn = functools.partial(_quantize_one, bits=bits, k=k)
    if stacked:
        return jax.vmap(fn)(jax.random.split(key, w.shape[0]), w)
    return fn(key, w)


def _select(vals, cid):
    """vals[cid] per element, as a masked sum over the clusters: an
    elementwise pass, where a gather of one value per element is slow on
    a TPU."""
    return sum(jnp.where(cid == c, vals[..., c], 0.0)
               for c in range(vals.shape[-1]))


def dequant(leaf, dtype):
    """The float32 matrix a quantized leaf stands for, rounded through
    the served parameter dtype."""
    q, cid, scale, zero = leaf
    w = (q.astype(jnp.float32) - _select(zero, cid)) / _select(scale, cid)
    return w.astype(dtype).astype(jnp.float32)


def _fp8(x):
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, a, b, control):
    """The reference's einsum: float32 at ``precision=HIGHEST``; with
    `control` both operands first rounded to float8 (e4m3, one scale per
    tensor), the nearest precision below the bfloat16 the configurations
    state."""
    if control:
        return jnp.einsum(eq, _fp8(a).astype(jnp.bfloat16),
                          _fp8(b).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
