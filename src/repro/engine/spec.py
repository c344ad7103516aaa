"""Self-speculative decoding: a low-bit SplitQuant DRAFT of the served
weights proposes tokens, the full-precision TARGET verifies whole windows
in one fused pass (DESIGN.md §9).

SplitQuant's headline property — aggressively quantized models stay
*faithful* to their fp parent — is exactly what a speculative draft
needs: cheap to hold, rarely wrong. The subsystem reuses the two serving
pieces already in-tree rather than growing new ones:

  * the DRAFT is the same architecture loaded from a calibration
    :class:`~repro.calib.recipe.QuantRecipe` (mixed low-bit weights, no
    k-means at startup when the recipe ships a pre-quantized ckpt). It
    shares the target's slot-cache GEOMETRY — same (L, N, T, Hkv, D),
    same kv_mode/qchunks — but owns its own slot arrays, and decodes
    through the exact same jitted fused decode entry point as the
    target (`engine._jitted_entry_points`, greedy variant), so drafting
    is k batched decode steps over all slots at once;

  * the VERIFY pass is `kernels/prefill_attention.py` — a draft window
    *is* a prefill chunk: the window's queries attend the slot's
    committed INT8 prefix plus the window's own K/V (round-tripped
    through cache storage so every row scores exactly like a plain
    decode step, see the kernel's verify mode), the epilogue quantizes
    the window K/V, and accepted rows therefore land in the slot as
    FINAL bytes — no re-write after acceptance.

Accept rule (greedy, lossless): window = [last committed token,
d_1 .. d_{w-1}] fed at positions [pos, pos+w); verify row j's argmax
g_{j+1} is the target's greedy token after window token j. With
a = the longest prefix where d_i == g_i, the engine commits
g_1 .. g_{a+1} — a accepted drafts plus the target's own correction —
so every committed token is the target's argmax given the committed
prefix and speculative output is token-identical to plain greedy
decoding (asserted across fp / int8-dynamic / int8-static KV in
tests/test_spec.py). Rejected rows are undone by
`kvcache.rollback_slot`: kv_pos → -1 beyond the accepted point is the
whole rollback (validity-by-position), and the next write overwrites
the stale codes, so a rolled-back slot is bit-identical to one that
never speculated.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import Span

from . import engine as _engine
from .kvcache import init_slot_cache


def load_draft_params(recipe_dir: str, params, cfg):
    """Mint the draft weight tree from a saved QuantRecipe: restore the
    pre-quantized checkpoint if the recipe ships one (no k-means at
    engine start), else apply the recipe's per-path mixed-precision
    policies to the target's own ``params`` — the draft is the SAME
    model, just low-bit (self-speculation)."""
    from repro.calib import QuantRecipe

    rec = QuantRecipe.load(recipe_dir)
    if rec.arch and rec.arch != cfg.name:
        raise ValueError(
            f"draft recipe {recipe_dir!r} was calibrated for arch "
            f"{rec.arch!r}, serving {cfg.name!r} — a mismatched draft "
            f"would propose garbage and pay full verify cost for it")
    ck = rec.resolve_ckpt_dir(recipe_dir)
    if ck is not None:
        from repro.checkpoint import ckpt
        draft, _ = ckpt.restore(ck, params)
        return draft
    if rec.policies:
        from repro.core import QuantPolicy, quantize_tree
        draft, _ = quantize_tree(jax.random.PRNGKey(0), params,
                                 QuantPolicy(), overrides=rec.policies)
        return draft
    raise ValueError(
        f"draft recipe {recipe_dir!r} carries neither a pre-quantized "
        f"checkpoint nor quantization policies — nothing to draft with")


def accept_length(drafts, target_toks, window: int) -> int:
    """Longest accepted draft prefix: a = max n such that
    drafts[i] == target_toks[i] for all i < n. ``drafts`` are
    d_1..d_{window-1}; ``target_toks`` are the verify rows' argmax
    g_1..g_window. Returns a in [0, window-1]; the engine then commits
    target_toks[:a+1] (accepted drafts + the correction token)."""
    a = 0
    while a < window - 1 and int(drafts[a]) == int(target_toks[a]):
        a += 1
    return a


@functools.lru_cache(maxsize=None)
def jitted_verify(cfg):
    """Process-wide jitted verify entry point, one compile per (arch,
    window-bucket) — slot / pos_start / length stay traced scalars. The
    greedy argmax over every window row is folded into the executable
    (the accept rule only consumes argmax tokens), so a verify is one
    dispatch plus a (Sq,)-int host transfer. The cache is donated: the
    window's K/V codes are scattered in place."""
    from repro.models import transformer

    def vstep(p, c, toks, slot, pos_start, length):
        logits, cache = transformer.verify_step_slots(
            p, cfg, c, toks, slot, pos_start, length)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache

    return jax.jit(vstep, donate_argnums=(1,))


class SpecDecoder:
    """Draft side of the speculative engine: owns the draft weights and
    the draft slot cache (target geometry, own arrays), and mirrors every
    cache-lifecycle event — prefill, retire, rollback — so the draft's
    view of each slot tracks the committed sequence.

    The draft cache always uses DYNAMIC scales even when the target
    serves static recipe constants: the recipe was calibrated on the
    target's activations, and a mis-scaled draft cache only costs
    acceptance (never correctness — the accept rule guards that), so the
    draft keeps the scale mode that needs no extra calibration artifact.
    """

    def __init__(self, cfg, ecfg, draft_params, tracer=None,
                 registry=None):
        from repro.models.common import dtype_of
        self.cfg = cfg
        self.ecfg = ecfg
        self.k = ecfg.spec_k
        # obs.Tracer (falsy → None): the draft pass emits one aggregated
        # "draft" span per engine step with dispatch/wait attribution
        self.tracer = tracer if tracer else None
        # always-on draft-side instruments (obs.metrics): the engine
        # shares its registry so the draft's dispatch volume and wall
        # share live alongside the queueing gauges
        self._mx = None
        if registry is not None:
            self._mx = {
                "steps": registry.counter(
                    "spec_draft_steps", "batched draft decode dispatches"),
                "draft_s": registry.histogram(
                    "spec_draft_pass_seconds",
                    "whole per-engine-step draft pass (all iterations)"),
            }
        if ecfg.draft_dequantize:
            # one-time expansion of packed SplitQuantTensors into the
            # compute dtype: every draft decode step would otherwise
            # re-dequantize the whole weight tree (the low-bit recipe's
            # job here is faithfulness + storage, not per-step compute)
            from repro.core import dequantize_tree
            draft_params = dequantize_tree(draft_params)
        self.params = draft_params
        # the draft-twin cache is serving STATE, not a derived quantity:
        # its rows must stay token-aligned with the target cache or the
        # next verify window rolls back everything, so engine
        # snapshot/restore (engine/recovery.py, DESIGN.md §13) persists
        # and restores it alongside the target's under the "draft/"
        # prefix — a spec engine restored without its twin would pay a
        # silent full re-draft-prefill of every live slot
        self.cache = init_slot_cache(
            cfg, ecfg.n_slots, ecfg.max_len, mode=ecfg.kv_mode,
            dtype=dtype_of(ecfg.kv_dtype), qchunks=ecfg.kv_qchunks)
        # the draft shares the target's jitted entry points (same arch ⇒
        # same executables; only the param/cache leaves differ), so a
        # spec engine costs zero extra compiles for drafting
        self._decode, self._prefill = _engine._jitted_entry_points(
            cfg, ecfg.fused_attn, True)                    # always greedy
        self._chunk_prefill = (_engine._jitted_chunk_prefill(cfg)
                               if ecfg.prefill_chunk else None)
        self.n_draft_steps = 0
        self.n_suspended_steps = 0
        # wall of the most recent draft pass — the flight recorder's
        # per-step draft_s field (always tracked: two clock reads per
        # pass, unlike the tracer/registry views this has no off switch)
        self.last_draft_s = 0.0
        if self._mx is not None:
            self._mx["suspended"] = registry.counter(
                "spec_suspended_steps",
                "decode steps where the degradation ladder routed a "
                "spec-enabled engine through plain decode")

    def note_suspended(self) -> None:
        """Record one plain-decode step taken while speculation is
        suspended (degradation-ladder rung >= 1). Tokens committed by
        those steps are never written to the draft cache, so the slot's
        draft rows grow position HOLES; holes are masked out of draft
        attention (validity-by-position), which can only cost acceptance
        — the verify pass stays authoritative, so resuming speculation
        after a suspension remains token-identical (the `spec_k→0 is
        free` property the ladder's first rung relies on)."""
        self.n_suspended_steps += 1
        if self._mx is not None:
            self._mx["suspended"].inc()

    # ------------------------------------------------- slot lifecycle ----
    def prefill_oneshot(self, toks, slot: int, length: int) -> None:
        """Mirror a one-shot admission into the draft cache (same dense
        fp materialization + write_prefill path as the target's)."""
        _, pcache = self._prefill(self.params, toks)
        self.cache = _engine._WRITE(self.cache, jnp.int32(slot), pcache,
                                    jnp.int32(length))

    def prefill_chunk(self, toks, slot: int, pos_start: int,
                      length: int) -> None:
        """Mirror one fused prefill chunk into the draft cache."""
        _, self.cache = self._chunk_prefill(
            self.params, self.cache, toks, jnp.int32(slot),
            jnp.int32(pos_start), jnp.int32(length))

    def clear(self, slot: int) -> None:
        self.cache = _engine._CLEAR(self.cache, jnp.int32(slot))

    def rollback(self, slot: int, accept_len: int) -> None:
        """Drop draft rows for rejected tokens — identical contract to
        the target-side rollback (kv_pos → -1 beyond the accepted
        point); the next draft pass overwrites the stale codes."""
        self.cache = _engine._ROLLBACK(self.cache, jnp.int32(slot),
                                       jnp.int32(accept_len))

    # ------------------------------------------------------- drafting ----
    def draft(self, last_tok, pos, steps):
        """Propose up to k greedy tokens per slot in batched decode steps
        over the draft cache.

        last_tok / pos: (N,) host arrays of the engine's committed state;
        steps: (N,) per-slot window lengths w (0 for slots that are idle
        or mid-prefill). Iteration j feeds window token w_j at pos+j for
        every slot still inside its window, writing its draft-cache row;
        a slot past its window (and every inactive slot) PARKS — it
        re-feeds its current (token, position), so the only row it
        touches is one the next chunk / admission / draft pass overwrites
        anyway (the same ride-along invariant as the engine's decode
        batch). Running max(steps) iterations (window w needs w feeds:
        w-1 drafts plus the row-write for the window's last token) keeps
        the draft cache hole-free even on full acceptance, so acceptance
        doesn't decay over long generations.

        Returns drafts (k, N) int32 — drafts[j] is d_{j+1} per slot; rows
        at >= steps-1 are garbage the caller never reads.
        """
        N = self.ecfg.n_slots
        cur_tok = np.asarray(last_tok, np.int32).copy()
        cur_pos = np.asarray(pos, np.int32).copy()
        steps = np.asarray(steps)
        drafts = np.zeros((self.k, N), np.int32)
        mx = self._mx
        t_pass = time.perf_counter()
        dispatch_s = wait_s = 0.0
        n_iter = int(steps.max())
        with Span(self.tracer, "draft", iters=n_iter) as sp:
            clock = sp.clock
            for j in range(n_iter):
                t_d = clock()
                toks, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(cur_tok[:, None]),
                    jnp.asarray(cur_pos))
                dispatch_s += (t_w := clock()) - t_d
                toks = np.asarray(toks)            # device wait per iter
                wait_s += clock() - t_w
                self.n_draft_steps += 1
                if j < self.k:
                    drafts[j] = toks
                adv = (j + 1) < steps
                cur_tok = np.where(adv, toks, cur_tok).astype(np.int32)
                cur_pos = np.where(adv, cur_pos + 1,
                                   cur_pos).astype(np.int32)
            sp.note(dispatch_s=dispatch_s, wait_s=wait_s)
        self.last_draft_s = time.perf_counter() - t_pass
        if mx:
            mx["steps"].inc(n_iter)
            mx["draft_s"].observe(self.last_draft_s)
        return drafts
