"""Shared model building blocks: norms, RoPE (incl. GLM half/2-D variant),
initializers, and the quantization-transparent dense layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ------------------------------------------------- activation sharding ----
#: axis aliases resolved against the active mesh: "dp" = the data-parallel
#: axes (("pod","data") or ("data",)), "tp" = "model".
import os as _os

_HINTS_ON = _os.environ.get("REPRO_SHARD_HINTS", "1") != "0"


def shard_hint(x, *spec):
    """Best-effort `with_sharding_constraint`: resolves "dp"/"tp" aliases
    against the active mesh, drops non-divisible axes, and is a no-op when
    no mesh is active (tests / single device) or REPRO_SHARD_HINTS=0.

    GSPMD's sharding propagation gives up inside scanned layers (it
    replicates q/k/v and re-gathers activations every layer — see
    EXPERIMENTS.md §Perf baseline); pinning the activation layout at block
    boundaries removes that redundancy.
    """
    if not _HINTS_ON:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    axis_size = dict(mesh.shape)

    def resolve(ax):
        if ax is None:
            return None
        if ax == "dp":
            ax = tuple(a for a in ("pod", "data") if a in axis_size) or None
            if ax is None:
                return None
        elif ax == "tp":
            ax = "model" if "model" in axis_size else None
            if ax is None:
                return None
        return ax

    out = []
    for dim, ax in zip(x.shape, spec):
        ax = resolve(ax)
        if ax is None:
            out.append(None)
            continue
        n = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= axis_size[a]
        out.append(ax if dim % n == 0 and dim >= n else None)
    from jax.sharding import PartitionSpec as _P
    try:
        return jax.lax.with_sharding_constraint(x, _P(*out))
    except Exception:
        return x


def tp_dense(x, w, b=None):
    """Row-parallel (Megatron-style) linear with an EXPLICIT shard_map
    reduction: local partial matmul over the TP shard of the contraction
    dim, then psum over "model" in the activation dtype.

    Why not let GSPMD insert it (EXPERIMENTS.md §Perf cell A iter 3):
      * GSPMD reduces the partials in the dot's accumulation dtype (f32 on
        the CPU-lowered dry-run) — 2× the wire bytes of a bf16 reduce;
      * GSPMD also emits dx all-reduces in backward, which row-parallel
        linear does not need (dy is replicated over "model"; dx_local =
        dy @ w_localᵀ is exact). shard_map's transpose gets this right.

    Falls back to `dense` when no mesh is active, dims don't divide, the
    weight is quantized/stacked oddly, or a bias is present.
    """
    if (not _HINTS_ON or b is not None or
            isinstance(w, ops.QUANTIZED) or w.ndim != 2 or x.ndim < 2):
        return dense(x, w, b)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return dense(x, w, b)
    import math
    from jax.sharding import PartitionSpec as _P
    sizes = dict(mesh.shape)
    tp = sizes["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    fsdp = "data" if "data" in sizes else None
    dpn = math.prod(sizes[a] for a in dp_axes) if dp_axes else 1
    K, N = w.shape
    B0 = x.shape[0]
    if K % tp or (fsdp and N % sizes[fsdp]) or B0 % dpn or B0 < dpn or tp == 1:
        return dense(x, w, b)

    def body(xb, wb):
        if fsdp:
            wb = jax.lax.all_gather(wb, fsdp, axis=1, tiled=True)
        part = jnp.dot(xb, wb.astype(xb.dtype),
                       preferred_element_type=jnp.float32)
        return jax.lax.psum(part.astype(xb.dtype), "model")

    xspec = _P(*((dp_axes if dp_axes else None,) +
                 (None,) * (x.ndim - 2) + ("model",)))
    wspec = _P("model", fsdp)
    ospec = _P(*((dp_axes if dp_axes else None,) + (None,) * (x.ndim - 1)))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(xspec, wspec),
                       out_specs=ospec, check_vma=False)
    return fn(x, w)


def dense(x, w, b=None):
    """Linear layer; dispatches to the quantized path for SplitQuantTensor
    and PackedSplitQuantTensor leaves (kernels/ops.py). Computation dtype
    follows x."""
    if isinstance(w, ops.QUANTIZED):
        return ops.linear(x, w, b)
    y = jnp.dot(x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def materialize(w, dtype=None):
    """Dense view of a (possibly quantized) parameter, for ops that need the
    raw array (einsum over experts, depthwise conv taps, …)."""
    if isinstance(w, ops.QUANTIZED):
        w = w.dequantize()
    return w.astype(dtype) if dtype is not None else w


def embed_lookup(table, ids):
    if isinstance(table, ops.QUANTIZED):
        table = table.dequantize()
    return jnp.take(table, ids, axis=0)


# ---------------------------------------------------------------- norms ----
def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def apply_norm(x, p, norm_type: str):
    if norm_type == "rms":
        return rms_norm(x, p["norm_scale"])
    return layer_norm(x, p["norm_scale"], p["norm_bias"])


def init_norm(d, norm_type: str, dtype):
    if norm_type == "rms":
        return {"norm_scale": jnp.zeros((d,), dtype)}
    return {"norm_scale": jnp.ones((d,), dtype),
            "norm_bias": jnp.zeros((d,), dtype)}


# ----------------------------------------------------------------- rope ----
def rope_freqs(head_dim: int, theta: float, rotary_dim: int | None = None):
    rd = rotary_dim or head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    return inv  # (rd/2,)


def apply_rope(x, positions, theta: float, variant: str = "full"):
    """x: (..., S, H, D). variant 'half' rotates only the first D/2 dims
    (GLM's 2-D RoPE uses half the channels for position)."""
    if variant == "none":
        return x
    D = x.shape[-1]
    rd = D // 2 if variant == "half" else D
    inv = rope_freqs(D, theta, rd)                       # (rd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, rd/2)
    cos = jnp.cos(ang)[..., None, :]                      # (..., S, 1, rd/2)
    sin = jnp.sin(ang)[..., None, :]
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rot, x[..., rd:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ init ---
def he_init(key, shape, dtype, fan_in=None):
    fan = fan_in if fan_in is not None else shape[0]
    std = (2.0 / fan) ** 0.5
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


def stack_layer_init(init_fn, key, n_layers: int):
    """vmap an init over layer index → stacked (L, ...) params for scan."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_fn)(keys)
