"""Model-level SplitQuant application: walk a parameter pytree, replace
quantizable weight leaves with :class:`SplitQuantTensor`s.

Paper §4.1 rules honored:
  * normalization γ/β are "semantically not weights" → never quantized;
  * gate/decay parameters of recurrent layers (RWKV decay, RG-LRU gates)
    are treated the same way;
  * biases are clustered+quantized like weights (1-D);
  * batch-norm folding is a no-op for the archs here (none use BN), but the
    hook exists for conv frontends.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .quantize import QuantConfig
from .splitquant import SplitQuantTensor, baseline_quant_tensor, splitquant_tensor

#: parameter-path fragments that are never quantized (semantically not weights)
DEFAULT_EXCLUDE = (
    "norm", "ln_", "layernorm", "rmsnorm", "scale_param",
    "decay", "gate_a", "rg_lru", "time_", "alibi", "rope",
    # MoE routers stay fp32: top-k selection flips discretely under
    # quantization noise, destroying accuracy for ~0 memory savings
    "router",
)


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """What to quantize and how."""

    cfg: QuantConfig = QuantConfig(bits=8)
    method: str = "splitquant"          # "splitquant" | "baseline" | "percentile"
    k: int = 3                          # number of split layers (paper: 3)
    quantize_biases: bool = True        # paper quantizes biases too
    quantize_embeddings: bool = False
    min_size: int = 64                  # leave tiny params alone
    exclude: tuple = DEFAULT_EXCLUDE
    act_chunks: int = 3                 # §4.2 activation split (0/1 disables)
    sample_size: int = 1 << 18

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts).lower()


#: path fragments marking stacked (scan-over-layers) parameter groups
STACK_FRAGMENTS = ("layers", "moe_layers", "groups", "tail",
                   "enc_layers", "dec_layers")


def infer_stack_dims(path_s: str, leaf) -> int:
    """Leading axes quantized independently: 1 under a layer stack, 2 for
    per-expert MoE weights (L, E, d, f) — DESIGN.md §5 (per-expert
    clustering)."""
    in_stack = any(f"/{f}/" in f"/{path_s}/" or path_s.startswith(f + "/")
                   for f in STACK_FRAGMENTS)
    if not in_stack:
        return 0
    if leaf.ndim >= 4:
        return 2
    return 1


def _quantizable(path_s: str, leaf, policy: QuantPolicy) -> bool:
    if not isinstance(leaf, jnp.ndarray) or not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    if leaf.size < policy.min_size:
        return False
    if any(frag in path_s for frag in policy.exclude):
        return False
    is_table = any(f in path_s for f in ("embed", "pos_table", "enc_pos",
                                         "dec_pos"))
    if is_table and not policy.quantize_embeddings:
        return False
    if leaf.ndim == 0:
        return False
    sd = infer_stack_dims(path_s, leaf)
    if leaf.ndim - sd < 1:
        return False
    if leaf.ndim - sd == 1 and not policy.quantize_biases:
        return False
    return True


#: fallback clip when method="percentile" is asked for without an explicit
#: percentile (paper §1: "often 99% is used in practice")
DEFAULT_PERCENTILE = 0.99

#: per-path override keys a recipe may carry (see repro.calib.recipe)
OVERRIDE_KEYS = ("bits", "k", "method", "percentile")

# Each leaf is quantized as one compiled program. Run op by op, a stacked
# tensor's k-means distances and per-cluster masks are all live at once:
# a full-width 1.6B model's quantization then peaks near 16 GB.
_splitquant_jit = jax.jit(splitquant_tensor, static_argnames=(
    "cfg", "k", "sample_size", "kmeans_iters", "stack_dims"))
_baseline_jit = jax.jit(baseline_quant_tensor,
                        static_argnames=("cfg", "stack_dims"))


def resolve_policy(policy: QuantPolicy, override: Optional[dict] = None
                   ) -> QuantPolicy:
    """Effective policy for one leaf: apply a per-path override (bits / k /
    method / percentile) and normalize method-dependent percentile handling
    in ONE place:

    * ``baseline``    — never clips (percentile forced to None);
    * ``percentile``  — always clips (an unset/None percentile falls back
                        to :data:`DEFAULT_PERCENTILE`);
    * ``splitquant``  — uses cfg.percentile as given (normally None).
    """
    if override:
        unknown = set(override) - set(OVERRIDE_KEYS)
        if unknown:
            raise ValueError(f"unknown override keys {sorted(unknown)}")
        cfg_kw = {kk: override[kk] for kk in ("bits", "percentile")
                  if kk in override}
        pol_kw = {kk: override[kk] for kk in ("method", "k")
                  if kk in override}
        policy = policy.replace(
            cfg=dataclasses.replace(policy.cfg, **cfg_kw), **pol_kw)
    if policy.method == "baseline":
        policy = policy.replace(
            cfg=dataclasses.replace(policy.cfg, percentile=None))
    elif policy.method == "percentile":
        pct = (policy.cfg.percentile if policy.cfg.percentile is not None
               else DEFAULT_PERCENTILE)
        policy = policy.replace(
            cfg=dataclasses.replace(policy.cfg, percentile=pct))
    return policy


def quantize_tree(key: jax.Array, params, policy: QuantPolicy,
                  is_quantizable: Optional[Callable] = None,
                  overrides: Optional[dict] = None):
    """Return a copy of ``params`` with quantizable leaves replaced by
    SplitQuantTensors (method-dependent), plus a report dict.

    * ``splitquant``  — k-means split, per-cluster scales (the paper).
    * ``baseline``    — one scale set from full min/max range.
    * ``percentile``  — one scale set from the clipped range (de-facto
                        outlier treatment the paper argues against).
    * ``none``        — leave the leaf in floating point (only meaningful
                        as a per-path override).

    ``overrides``: optional ``{path: {bits|k|method|percentile: ...}}`` map
    (exact lowercase "a/b/c" paths as reported in ``report["quantized"]``)
    applied on top of ``policy`` — the mechanism a calibration
    :class:`~repro.calib.recipe.QuantRecipe` uses for mixed-precision
    deployment. Unmatched override paths raise (a silently ignored
    override would serve the wrong bit-widths).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    report = {"quantized": [], "skipped": [], "deployed_bytes": 0,
              "orig_bytes": 0, "per_path": {}}
    overrides = dict(overrides or {})
    unused = set(overrides)
    out_leaves = []
    keys = jax.random.split(key, max(len(flat), 1))
    for (path, leaf), k_i in zip(flat, keys):
        path_s = _path_str(path)
        ok = (is_quantizable or _quantizable)(path_s, leaf, policy)
        if not ok:
            out_leaves.append(leaf)
            report["skipped"].append(path_s)
            continue
        eff = resolve_policy(policy, overrides.get(path_s))
        unused.discard(path_s)
        if eff.method == "none":
            out_leaves.append(leaf)
            report["skipped"].append(path_s)
            continue
        sd = infer_stack_dims(path_s, leaf)
        if eff.method == "splitquant":
            sq = _splitquant_jit(k_i, leaf, eff.cfg, k=eff.k,
                                 sample_size=eff.sample_size, stack_dims=sd)
        elif eff.method in ("baseline", "percentile"):
            sq = _baseline_jit(leaf, eff.cfg, stack_dims=sd)
        else:
            raise ValueError(f"unknown method {eff.method!r}")
        out_leaves.append(sq)
        report["quantized"].append(path_s)
        report["per_path"][path_s] = {"bits": eff.cfg.bits, "k": sq.k,
                                      "method": eff.method,
                                      "bytes": sq.nbytes_deployed()}
        report["deployed_bytes"] += sq.nbytes_deployed()
        report["orig_bytes"] += leaf.size * 4
    if unused:
        raise ValueError(f"overrides matched no quantizable leaf: "
                         f"{sorted(unused)}")
    return jax.tree_util.tree_unflatten(treedef, out_leaves), report


def dequantize_tree(params):
    """Replace every SplitQuantTensor leaf with its dequantized dense array
    (simulated-quantization evaluation path)."""
    return jax.tree_util.tree_map(
        lambda l: l.dequantize() if isinstance(l, SplitQuantTensor) else l,
        params, is_leaf=lambda l: isinstance(l, SplitQuantTensor))
