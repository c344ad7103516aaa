"""Continuous-batching inference engine.

`Engine` owns the three serving pieces: a `Scheduler` (FCFS queue + slot
pool), a `SlotKVCache` (preallocated, optionally INT8), and the jitted
model entry points. The serving loop is token-level:

    eng = Engine(cfg, params, EngineConfig(n_slots=4))
    eng.submit(prompt_a); eng.submit(prompt_b)
    finished = eng.drain()

Each `step()` (1) admits queued requests into free slots; (2) prefills —
either ONE-SHOT (`prefill_chunk=0`: a per-request dense prefill whose fp
cache `write_prefill` re-quantizes into the slot, batch 1, right-padded
to a length bucket so jit recompiles are bounded) or CHUNKED
(`prefill_chunk>0`: at most that many prompt tokens per step stream
through `transformer.prefill_chunk_slots`, whose fused kernel quantizes
K/V in-kernel and writes codes straight into the slot cache — no fp
prefill cache exists and a long prompt no longer stalls decoding, see
DESIGN.md §6); (3) runs ONE batched decode step over all decoding slots
at their own positions; (4) retires finished slots so the next step can
refill them. A long generation therefore occupies exactly one slot
instead of stalling a whole wave, and with chunked prefill a long PROMPT
occupies at most `prefill_chunk` tokens of any step.

Mid-prefill slots are invisible to decode (`Scheduler.active_slots`
excludes them) but still ride along in the fixed-shape decode batch,
parked at their next-unwritten position: the parked step writes garbage
K/V at exactly the row the slot's NEXT prefill chunk overwrites (and the
chunk kernel masks cache rows at >= pos_start), so the parked write can
never leak into any attention result.

With ``spec_k > 0`` the decode step is SPECULATIVE (`engine/spec.py`,
DESIGN.md §9): a low-bit draft model proposes up to k greedy tokens per
slot over its own slot cache, the target verifies each slot's window in
one fused prefill-kernel pass, and 1..k+1 tokens commit per slot per
step — token-identical to plain greedy decoding by the lossless accept
rule.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import pack_weights
from repro.models import get_model
from repro.obs.tracer import Span

from .faults import DegradationLadder, FaultInjector, StepFailure
from .kvcache import clear_slot, init_slot_cache, rollback_slot, \
    write_prefill
from .scheduler import EngineRequest, Scheduler, SubmitError

ENGINE_FAMILIES = ("dense", "moe", "vlm")

#: Materialization-counter hook: incremented once per LEGACY one-shot
#: prefill dispatch — each one materializes a dense full-precision
#: (L, S, Hkv, D) cache that `write_prefill` then pads, re-quantizes and
#: copies into the slot cache. The fused chunked-prefill path must never
#: bump it (asserted in tests/test_prefill_attention.py).
FP_PREFILL_MATERIALIZATIONS = 0


def bucket_len(n: int, bucket: int, max_len: int) -> int:
    """Round a prompt length up to its prefill bucket (bounded jit
    recompiles). Single definition — the serve benchmark warms exactly
    these shapes, so it must agree with the engine byte-for-byte."""
    return min(max_len, -(-n // bucket) * bucket)


@functools.lru_cache(maxsize=None)
def _jitted_prefill(cfg):
    """Prefill depends only on the arch — shared across fused/sampling
    variants so an engine flag flip never recompiles prefill buckets."""
    model = get_model(cfg)
    return jax.jit(lambda p, toks: model.prefill(p, cfg, {"tokens": toks}))


@functools.lru_cache(maxsize=None)
def _jitted_entry_points(cfg, fused: bool, greedy: bool):
    """Process-wide jitted (decode, prefill) per (arch config, fused flag,
    sampling mode).

    Jitting per Engine INSTANCE (the old scheme) meant every restart — and
    every benchmark repetition — recompiled the decode step and each
    prefill bucket from scratch; sharing the wrappers here makes engine
    spin-up O(cache lookup) after the first instance and lets benchmarks
    measure steady state instead of XLA compile time.

    The cache argument is DONATED: the serving loop always replaces its
    cache with the returned one, and donation lets XLA update the slot
    arrays in place instead of copying every (L, N, T, ...) leaf each
    decode step — an O(cache-size) saving per token for both the fused
    and the materializing read path.

    ``greedy`` folds argmax sampling into the decode executable: one
    dispatch and a (N,)-int host transfer per step instead of a separate
    argmax jit call plus the full logits pull."""
    from repro.models import transformer

    def step(p, c, t, pos):
        logits, cache = transformer.decode_step_slots(p, cfg, c, t, pos,
                                                      fused=fused)
        if greedy:
            with jax.named_scope("lm_head"):
                toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return toks, cache
        return logits, cache

    decode = jax.jit(step, donate_argnums=(1,))
    return decode, _jitted_prefill(cfg)


@functools.lru_cache(maxsize=None)
def _jitted_chunk_prefill(cfg):
    """Process-wide jitted chunked-prefill entry point. One compile per
    CHUNK BUCKET shape (the (1, Sc) tokens arg); slot / pos_start / length
    are traced scalars, so slots and chunk offsets never recompile. The
    cache is donated — chunk writes update the slot arrays in place."""
    from repro.models import transformer

    def chunk(p, c, toks, slot, pos_start, length):
        return transformer.prefill_chunk_slots(p, cfg, c, toks, slot,
                                               pos_start, length)

    return jax.jit(chunk, donate_argnums=(1,))


# slot/length stay traced: one compile per prefill bucket shape, shared by
# every engine in the process; the old cache is dead after each call, so
# its buffers are donated (in-place row writes)
_WRITE = jax.jit(write_prefill, donate_argnums=(0,))
_CLEAR = jax.jit(clear_slot, donate_argnums=(0,))
_ROLLBACK = jax.jit(rollback_slot, donate_argnums=(0,))


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 8
    max_len: int = 256
    max_new_tokens: int = 32            # default per-request token budget
    temperature: float = 0.0            # 0 ⇒ greedy
    eos_id: int = -1                    # -1 ⇒ never stop early
    kv_mode: str = "fp"                 # "fp" | "int8" (SplitQuant §4.2)
    kv_qchunks: int = 4                 # ranges per head-vector in int8 mode
    kv_dtype: str = "float32"           # fp-mode storage; "bfloat16" on TPU
    prefill_bucket: int = 16            # prompt lengths round up to a multiple
    fused_attn: bool = True             # decode reads via the fused dequant-
                                        # in-kernel attention (no full-
                                        # precision cache copy). False =
                                        # legacy materialize-then-attend,
                                        # kept as the cross-checked oracle
    prefill_chunk: int = 96             # chunked fused prefill — admit at
                                        # most this many prompt tokens per
                                        # step, quantize-in-kernel slot
                                        # writes, decode keeps running while
                                        # long prompts stream in. Default ON
                                        # (~4x prefill_bucket, the serve-
                                        # bench soak sweet spot) now that
                                        # soak + verify coverage has
                                        # accumulated; prefill_chunk=0 is
                                        # the legacy one-shot opt-out
                                        # (serve_bench pins it for its
                                        # stall baseline)
    spec_k: int = 0                     # >0: self-speculative decoding — a
                                        # low-bit draft proposes up to k
                                        # greedy tokens per slot per step,
                                        # the target verifies the window in
                                        # ONE fused pass (engine/spec.py,
                                        # DESIGN.md §9). Output is token-
                                        # identical to spec_k=0 greedy.
                                        # Requires temperature <= 0
    draft_recipe: Optional[str] = None  # QuantRecipe dir the draft weights
                                        # are minted from (spec_k > 0);
                                        # None = draft with the target's
                                        # own weights (acceptance ~1, no
                                        # draft cost win — mostly a test
                                        # and bring-up configuration)
    draft_dequantize: bool = True       # expand the draft's packed low-
                                        # bit weights to the compute dtype
                                        # ONCE at engine start: the low-
                                        # bit recipe buys draft
                                        # faithfulness + storage, and a
                                        # packed draft would otherwise pay
                                        # a full dequant per draft step on
                                        # backends without the fused
                                        # dequant-matmul. False keeps the
                                        # draft packed (memory-bound
                                        # deployments with the kernel)
    metrics: bool = True                # always-ON metrics registry
                                        # (repro.obs.metrics, DESIGN.md
                                        # §11): monotonic counters /
                                        # gauges / fixed-bucket
                                        # histograms over the queueing
                                        # signals (queue depth, admit
                                        # latency, slot occupancy,
                                        # prefill backlog, tokens in
                                        # flight, spec-acceptance EWMA).
                                        # Unlike trace, this is bounded-
                                        # memory and cheap enough to
                                        # never turn off — overhead is
                                        # asserted within the serve-
                                        # bench noise floor (≤1%).
                                        # False exists for that
                                        # overhead measurement
    metrics_kv_every: int = 0           # >0: sample KV clip-fraction /
                                        # occupancy gauges from live
                                        # int8 cache rows every N steps
                                        # (kvcache.kv_quality_counters —
                                        # a bounded host transfer, so
                                        # NOT free; keep the period
                                        # coarse in production)
    trace: bool = False                 # default-OFF observability
                                        # (repro.obs, DESIGN.md §10):
                                        # lifecycle events + a ring-
                                        # buffer record of every phase
                                        # span with dispatch-vs-device-
                                        # wait attribution. The spans'
                                        # profiler annotations are
                                        # written either way; no sync
                                        # point is added
    trace_capacity: int = 1 << 16       # tracer ring-buffer records;
                                        # oldest drop first on overflow
    trace_kv_every: int = 0             # >0: sample KV quantization-
                                        # quality counters (clip fraction,
                                        # code occupancy, outlier-chunk
                                        # histogram) every N steps — a
                                        # host transfer of live cache
                                        # rows, traced-mode cost only
    # --- fault tolerance (DESIGN.md §12) -------------------------------
    max_queue: int = 0                  # >0: bounded submit queue; an
                                        # arrival into a full queue
                                        # triggers overload_policy. 0 =
                                        # unbounded (historical behavior).
                                        # The production set point comes
                                        # from the measured saturation
                                        # knee (scheduler.
                                        # admission_set_point)
    overload_policy: str = "reject-new" # full-queue victim choice:
                                        # "reject-new" | "shed-oldest" |
                                        # "shed-by-class" (oldest queued
                                        # batch-class request first)
    degrade: bool = False               # graceful-degradation ladder:
                                        # under sustained backlog disable
                                        # speculation (rung 1, output-
                                        # identical), defer batch-class
                                        # admissions (rung 2), shed
                                        # queued load (rung 3); each rung
                                        # change is a metrics event
    degrade_thresholds: tuple = ()      # 3 ascending pressure bounds
                                        # (queue depth + prefill backlog
                                        # chunks) for rungs 1..3; () →
                                        # (N, 2N, 4N) slots-scaled default
    degrade_patience: int = 2           # consecutive steps a threshold
                                        # crossing must persist before
                                        # the rung moves (hysteresis;
                                        # descent takes 2x)
    max_retries: int = 2                # per-slot consecutive-failure
                                        # budget for step retry; one more
                                        # failure quarantines the slot's
                                        # request as "failed"
    retry_backoff_s: float = 0.0005     # base for the bounded exponential
                                        # backoff between retry attempts
                                        # (doubles per attempt, capped)
    fault_spec: Optional[object] = None # faults.FaultSpec: seeded
                                        # synthetic fault injection (chaos
                                        # testing). None = no injection;
                                        # the retry/quarantine machinery
                                        # is always on regardless
    # --- crash safety (engine/recovery.py, DESIGN.md §13) --------------
    journal_path: Optional[str] = None  # append-only JSONL WAL of request
                                        # lifecycle transitions, fsync'd
                                        # once per step — the replay
                                        # source for crash recovery.
                                        # None = no journal
    journal_resume: bool = False        # append to an existing journal
                                        # (recovery/supervisor restart)
                                        # instead of starting a fresh one
    snapshot_path: Optional[str] = None # directory Engine.snapshot()
                                        # writes (atomic tmp + rename);
                                        # with snapshot_every, the engine
                                        # auto-snapshots here
    snapshot_every: int = 0             # >0: snapshot every N steps at
                                        # the end-of-step boundary (after
                                        # the journal fsync, so snapshot
                                        # state ⊆ journal horizon)
    # --- flight recorder + incident capture (obs/flight.py, §14) --------
    flight: bool = True                 # always-on bounded ring of coarse
                                        # per-step records (the black
                                        # box); overhead gated <= max(1%,
                                        # noise) by serve_bench like the
                                        # metrics registry
    flight_capacity: int = 512          # ring size in steps
    incident_dir: Optional[str] = None  # arm the anomaly-detector sweep
                                        # and write incident bundles
                                        # under this directory (atomic
                                        # tmp+fsync+rename). None = sweep
                                        # off, recorder still on
    incident_cooldown: int = 50         # steps: per-detector refire
                                        # cooldown AND global min gap
                                        # between bundles — a fault storm
                                        # produces one bundle, not one
                                        # per step


class Engine:
    """submit()/step()/drain() continuous-batching server.

    ``kv_scales``: optional static KV quantization constants from an
    offline calibration recipe (``repro.calib``) — dict of
    ``k_scale/k_zero/v_scale/v_zero`` (L, Hkv, C) arrays. Requires
    ``kv_mode="int8"``; decode writes then skip the per-step min/max
    reduce and scale storage amortizes to ~0 bytes/token (DESIGN.md §7).

    ``draft_params``: optional pre-built draft weight tree for
    ``spec_k > 0`` (same architecture as ``params`` — typically the
    low-bit quantized copy). Overrides ``ecfg.draft_recipe``; when both
    are absent the target drafts for itself (acceptance ~1, no draft
    cost win — a bring-up configuration).
    """

    def __init__(self, cfg, params, ecfg: EngineConfig,
                 rng: Optional[jax.Array] = None,
                 clock=time.perf_counter,
                 kv_scales: Optional[dict] = None,
                 draft_params=None, tracer=None, registry=None):
        if cfg.family not in ENGINE_FAMILIES:
            raise NotImplementedError(
                f"engine serves transformer families {ENGINE_FAMILIES}, "
                f"got {cfg.family!r} (recurrent-state continuous batching "
                f"is a separate cache layout"
                + (" — and spec_k > 0 additionally needs positional KV "
                   "rollback, which recurrent state cannot provide)"
                   if ecfg.spec_k else ")"))
        if cfg.window is not None and cfg.window < ecfg.max_len:
            raise NotImplementedError(
                "windowed (ring) slot caches not wired up yet; "
                f"window={cfg.window} < max_len={ecfg.max_len}")
        self.cfg = cfg
        self.ecfg = ecfg
        self.model = get_model(cfg)
        self.clock = clock
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        from repro.models.common import dtype_of
        # --- observability (repro.obs, DESIGN.md §10) -------------------
        # an explicit tracer wins; else ecfg.trace mints one on the
        # engine's own clock (trace time and metrics share one axis).
        # Falsy tracers normalize to None so every record site guards
        # with a single `if tr:` branch; phase spans (self._span) write
        # their profiler annotation either way.
        if tracer is None and ecfg.trace:
            from repro.obs import Tracer
            tracer = Tracer(capacity=ecfg.trace_capacity, clock=clock,
                            meta={"arch": cfg.name, "n_slots": ecfg.n_slots,
                                  "spec_k": ecfg.spec_k,
                                  "kv_mode": ecfg.kv_mode,
                                  "prefill_chunk": ecfg.prefill_chunk})
        self.tracer = tracer if tracer else None
        # --- always-on metrics registry (obs.metrics, DESIGN.md §11) ----
        # an explicit registry wins (shared across engines / exported by
        # a server); else ecfg.metrics mints a private one. Instruments
        # resolve ONCE here so the hot path is attribute ops behind a
        # single `if mx:` branch; ecfg.metrics=False leaves mx None —
        # the configuration the overhead assertion measures against.
        self.registry = None
        self._mx = None
        if registry is not None or ecfg.metrics:
            from repro.obs.metrics import MetricsRegistry, RESTORE_BUCKETS_S
            self.registry = registry if registry is not None \
                else MetricsRegistry()
            r = self.registry
            self._mx = {
                "steps": r.counter("engine_steps", "Engine.step() calls"),
                "decode_steps": r.counter(
                    "engine_decode_steps", "batched plain-decode steps"),
                "spec_steps": r.counter(
                    "engine_spec_steps", "speculative decode steps"),
                "tokens": r.counter(
                    "engine_tokens_generated", "committed output tokens"),
                "prefill_tokens": r.counter(
                    "engine_prefill_tokens", "prompt tokens prefilled"),
                "prefill_chunks": r.counter(
                    "engine_prefill_chunks", "fused prefill chunks run"),
                "step_s": r.histogram(
                    "engine_step_seconds", "full Engine.step() wall"),
                "decode_s": r.histogram(
                    "engine_decode_step_seconds",
                    "batched decode dispatch + device + sample"),
                "occupancy": r.gauge(
                    "engine_slot_occupancy",
                    "occupied slots (decoding + mid-prefill) / n_slots"),
                "decoding": r.gauge(
                    "engine_slots_decoding", "slots in the decode batch"),
                "backlog": r.gauge(
                    "engine_prefill_backlog_chunks",
                    "prompt chunks still to stream for mid-prefill slots"),
                "in_flight": r.gauge(
                    "engine_tokens_in_flight",
                    "unexhausted generation budget across occupied slots"),
                "deadline": r.counter(
                    "engine_deadline_exceeded",
                    "requests retired by the step-boundary deadline "
                    "sweep (TTFT or total-wall)"),
                "retries": r.counter(
                    "engine_step_retries",
                    "decode step re-executions after rollback (injected "
                    "or detected failures)"),
                "rung": r.gauge(
                    "engine_degradation_rung",
                    "current degradation-ladder rung (0 normal, 1 spec "
                    "off, 2 defer batch, 3 shed)"),
                "degr_transitions": r.counter(
                    "engine_degradation_transitions",
                    "degradation-ladder rung changes"),
                # crash safety (engine/recovery.py, DESIGN.md §13) —
                # registered unconditionally so a box that never crashes
                # still exports the zeros an alert can sit on
                "snapshots": r.counter(
                    "engine_snapshots",
                    "engine state snapshots written (atomic tmp+rename)"),
                "restores": r.counter(
                    "engine_restore",
                    "engine state restores from a snapshot"),
                "replayed": r.counter(
                    "engine_journal_replayed_requests",
                    "un-retired requests resumed or re-enqueued by "
                    "journal replay after a restore"),
                "restore_s": r.histogram(
                    "engine_restore_duration_s",
                    "snapshot restore + journal replay wall time",
                    buckets=RESTORE_BUCKETS_S),
            }
            # rung 0 is a real state, not "unset" — render it from the
            # start (to_prometheus omits unset gauges)
            self._mx["rung"].set(0)
            if ecfg.spec_k:
                self._mx["accept_ewma"] = r.gauge(
                    "spec_accept_ewma",
                    "EWMA of per-verify draft-token acceptance fraction")
            if ecfg.metrics_kv_every:
                for side in ("k", "v"):
                    self._mx[f"kv_{side}_clip"] = r.gauge(
                        f"kv_{side}_clip_frac",
                        f"sampled {side.upper()}-cache code saturation "
                        f"(static scale drifted narrow when trending up)")
                    self._mx[f"kv_{side}_occ"] = r.gauge(
                        f"kv_{side}_occupancy",
                        f"sampled {side.upper()}-cache code-range use "
                        f"(scale drifted wide when trending down)")
        # --- crash safety (engine/recovery.py, DESIGN.md §13) -----------
        # the journal is a WAL, not a trace: always written when
        # configured, fsync'd once per step boundary in step()
        self.journal = None
        if ecfg.journal_path:
            from .recovery import RequestJournal
            self.journal = RequestJournal(
                ecfg.journal_path, clock=clock,
                meta={"arch": cfg.name, "n_slots": ecfg.n_slots,
                      "kv_mode": ecfg.kv_mode, "spec_k": ecfg.spec_k},
                resume=ecfg.journal_resume)
        self.sched = Scheduler(ecfg.n_slots, clock=clock,
                               tracer=self.tracer, registry=self.registry,
                               max_queue=ecfg.max_queue,
                               overload_policy=ecfg.overload_policy,
                               journal=self.journal)
        # --- fault tolerance (engine/faults.py, DESIGN.md §12) ----------
        self._faults = (FaultInjector(ecfg.fault_spec)
                        if ecfg.fault_spec else None)
        if self._faults is not None and ecfg.spec_k:
            raise NotImplementedError(
                "fault injection targets the plain decode path; the "
                "speculative path's verify/rollback already exercises "
                "mid-step recovery and injecting there would need "
                "draft-cache-aware retry bookkeeping that is not wired "
                "up — run chaos with spec_k=0 (the ladder's rung-1 "
                "configuration)")
        self._ladder = None
        self._rung = 0
        if ecfg.degrade:
            N_ = ecfg.n_slots
            self._ladder = DegradationLadder(
                ecfg.degrade_thresholds or (N_, 2 * N_, 4 * N_),
                patience=ecfg.degrade_patience)
        # --- flight recorder + incident capture (obs/flight.py, §14) ----
        # the recorder is the black box: always on (like the registry)
        # unless explicitly disabled; the detector sweep only runs when
        # an incident_dir is armed, so a plain run pays one ring append
        self._flight = None
        if ecfg.flight:
            from ..obs.flight import FlightRecorder
            self._flight = FlightRecorder(
                capacity=ecfg.flight_capacity, clock=clock,
                meta={"arch": cfg.name, "n_slots": ecfg.n_slots,
                      "kv_mode": ecfg.kv_mode, "spec_k": ecfg.spec_k})
        self._detect = None
        if ecfg.incident_dir:
            from ..obs.detect import AnomalyDetector
            self._detect = AnomalyDetector(
                cooldown_steps=ecfg.incident_cooldown,
                queue_set_point=(ecfg.max_queue or None))
        self.incidents: list = []        # bundle paths written this run
        self._last_bundle_step = None
        # latest sampled KV quality signals (fed by the periodic
        # kv_quality_counters pull; None until the first sample)
        self._last_clip_frac = None
        self._last_span_frac = None
        self.cache = init_slot_cache(
            cfg, ecfg.n_slots, ecfg.max_len, mode=ecfg.kv_mode,
            dtype=dtype_of(ecfg.kv_dtype), qchunks=ecfg.kv_qchunks,
            kv_scales=kv_scales)
        self._greedy = ecfg.temperature <= 0
        self._decode, self._prefill = _jitted_entry_points(
            cfg, ecfg.fused_attn, self._greedy)
        self._chunk_prefill = (_jitted_chunk_prefill(cfg)
                               if ecfg.prefill_chunk else None)
        self._write = _WRITE
        self._clear = _CLEAR
        # --- self-speculative decoding (engine/spec.py, DESIGN.md §9) ---
        self._spec = None
        if ecfg.spec_k:
            if not self._greedy:
                raise NotImplementedError(
                    "spec_k > 0 requires greedy decoding (temperature <= "
                    "0): the lossless accept rule compares argmax tokens; "
                    "temperature sampling needs speculative rejection "
                    "sampling, which is not wired up")
            from . import spec as spec_mod
            if draft_params is None:
                draft_params = (
                    spec_mod.load_draft_params(ecfg.draft_recipe, params,
                                               cfg)
                    if ecfg.draft_recipe else params)
            self._spec = spec_mod.SpecDecoder(cfg, ecfg, draft_params,
                                              tracer=self.tracer,
                                              registry=self.registry)
            self._verify = spec_mod.jitted_verify(cfg)
        # the served weights, packed once into the dequant-matmul's layout
        # (kernels/ops.pack_weights) after any draft tree was minted from
        # the unpacked ones: the decode and chunk steps repack nothing
        self.params, (n_packed, n_bytes, n_left) = pack_weights(params)
        if self._mx is not None:
            r = self.registry
            r.gauge("engine_packed_weight_leaves",
                    "quantized weights packed once at engine start").set(
                        n_packed)
            r.gauge("engine_packed_weight_bytes",
                    "device bytes of the packed weights").set(n_bytes)
            r.gauge("engine_unpacked_quant_leaves",
                    "quantized leaves left unpacked (dequantized on every "
                    "call)").set(n_left)
        if n_packed or n_left:
            print(f"[engine] packed weights: {n_packed} leaves, {n_bytes} "
                  f"bytes; {n_left} quantized leaves left unpacked",
                  file=sys.stderr)
        # host-side slot state
        N = ecfg.n_slots
        self._last_tok = np.zeros(N, np.int32)
        self._pos = np.zeros(N, np.int32)
        self._prefill_prog = np.zeros(N, np.int64)   # prompt tokens written
        # consecutive corrupt-output attempts per slot (step retry);
        # crossing max_retries quarantines the slot's request as "failed"
        self._fail_streak = np.zeros(N, np.int64)
        self._uid = 0
        self._any_deadlines = False      # skip the per-step sweep until
                                         # a submit carries a deadline
        self.n_step_retries = 0
        self.n_quarantined = 0
        self.n_decode_steps = 0
        self.n_prefills = 0
        self.n_prefill_chunks = 0
        self.n_spec_steps = 0
        self.n_verify_calls = 0
        self.n_verify_tokens = 0
        self.n_spec_commit_tokens = 0   # tokens actually appended by spec
                                        # steps (eos/budget truncation can
                                        # commit fewer than accepted+1)
        self.decode_step_s: list[float] = []
        self.spec_step_s: list[float] = []
        # full step() wall + prompt tokens prefilled + decoders already
        # mid-generation at step start: the admission-stall telemetry
        # (serve_bench's soak reports the p95 of step latency among steps
        # whose prefill work ran while OTHER requests were decoding —
        # prefill with an idle decode batch stalls nobody)
        self.step_s: list[float] = []
        # seconds this step's readbacks (decode tokens, first tokens)
        # waited on the device (the flight record's wait_s: a slow step
        # waited on the device or on the host)
        self._step_wait_s = 0.0
        self.step_prefill_tokens: list[int] = []
        self.step_decode_slots: list[int] = []
        self._t_start: Optional[float] = None

    def load_kv_scales(self, kv_scales: dict) -> None:
        """Hot-swap a freshly loaded calibration recipe's static KV scales
        into a DYNAMIC int8 cache without draining slots (ROADMAP item):
        in-flight codes are requantized under the new constants once, and
        every subsequent write skips both the min/max reduce and the
        per-entry scale scatter. No-op for requests already finished; new
        admissions quantize with the recipe constants from the start."""
        from .kvcache import hotswap_static_scales
        self.cache = jax.jit(hotswap_static_scales)(self.cache, {
            k: jnp.asarray(v, jnp.float32) for k, v in kv_scales.items()})
        # self._decode retraces automatically: the cache's static flag is
        # pytree metadata, so the jit cache keys on it

    # ------------------------------------------------------------ intake --
    def submit(self, prompt, max_new_tokens: Optional[int] = None, *,
               cls: Optional[str] = None,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request; returns its uid. Non-blocking — work happens
        in step()/drain(). An explicit max_new_tokens=0 means "no tokens"
        (the request completes at admission with empty output).

        Validation happens HERE, not deep inside admission: a malformed
        request raises a structured `SubmitError` (a ValueError) before
        it consumes queue space — empty prompts, negative budgets, and
        prompt+budget combinations that cannot fit ``max_len`` (the old
        behavior silently truncated the budget, which made a request's
        output length depend on a config it never saw). ``cls`` is the
        loadgen request class (admission-policy key); the deadlines are
        wall-clock seconds from submit, enforced at step boundaries.

        Note the bounded queue (ecfg.max_queue) can shed on submit: the
        uid is still returned and the request lands in ``finished`` with
        reason "shed" — same lifecycle, it just never held a slot."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise SubmitError("empty_prompt",
                              "empty prompt (no tokens to prefill)")
        budget = (self.ecfg.max_new_tokens if max_new_tokens is None
                  else max_new_tokens)
        if budget < 0:
            raise SubmitError("bad_budget",
                              f"max_new_tokens must be >= 0, got {budget}")
        if len(prompt) + budget > self.ecfg.max_len:
            raise SubmitError(
                "too_long",
                f"prompt ({len(prompt)}) + max_new_tokens ({budget}) "
                f"exceeds max_len {self.ecfg.max_len}")
        req = EngineRequest(uid=self._uid, prompt=prompt,
                            max_new_tokens=budget, cls=cls,
                            ttft_deadline_s=ttft_deadline_s,
                            deadline_s=deadline_s)
        self._uid += 1
        if ttft_deadline_s is not None or deadline_s is not None:
            self._any_deadlines = True
        if self._faults is not None:
            self._faults.note_submit(req.uid)
        self.sched.submit(req)
        return req.uid

    def cancel(self, uid: int) -> bool:
        """Cancel a request mid-flight: queued requests finish
        immediately ("cancelled", never held a slot); slotted requests —
        including MID-CHUNKED-PREFILL ones — retire through the full
        slot-release path, so the cache row, draft-cache twin, and
        prefill bookkeeping all free together. Returns False when the
        uid is unknown or already finished (cancel is idempotent and
        racing a natural finish is not an error)."""
        for req in self.sched.queue:
            if req.uid == uid:
                if self.tracer:
                    self.tracer.event("cancel", uid=uid, slot=-1)
                self.sched.drop_queued(req, "cancelled")
                return True
        for slot, req in enumerate(self.sched.slots):
            if req is not None and req.uid == uid:
                if self.tracer:
                    self.tracer.event("cancel", uid=uid, slot=slot)
                self._retire(slot, "cancelled")
                return True
        return False

    def _deadline_expired(self, req: EngineRequest, now: float) -> bool:
        if req.t_submit is None:
            return False
        waited = now - req.t_submit
        if req.deadline_s is not None and waited > req.deadline_s:
            return True
        return (req.ttft_deadline_s is not None
                and req.t_first_token is None
                and waited > req.ttft_deadline_s)

    def _enforce_deadlines(self) -> None:
        """Step-boundary deadline sweep (DESIGN.md §12): queued requests
        whose TTFT/total-wall deadline already passed retire as
        "deadline_exceeded" without ever consuming a slot, and slotted
        ones (including mid-prefill) free their slot for work that can
        still make its SLO. Step-boundary granularity is deliberate —
        mid-step preemption would tear the batched decode dispatch."""
        now = self.clock()
        for req in [r for r in self.sched.queue
                    if self._deadline_expired(r, now)]:
            self.sched.drop_queued(req, "deadline_exceeded")
            if self._mx:
                self._mx["deadline"].inc()
        for slot, req in enumerate(self.sched.slots):
            if req is not None and self._deadline_expired(req, now):
                self._retire(slot, "deadline_exceeded")
                if self._mx:
                    self._mx["deadline"].inc()

    # ---------------------------------------------------------- sampling --
    def _sample(self, logits):
        """logits (..., V) → token ids."""
        if self.ecfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        self.rng, k = jax.random.split(self.rng)
        return jax.random.categorical(k, logits / self.ecfg.temperature)

    # ----------------------------------------------------------- serving --
    def _bucket(self, n: int) -> int:
        return bucket_len(n, self.ecfg.prefill_bucket, self.ecfg.max_len)

    def _retire(self, slot: int, reason: str = "eos"):
        """Free the slot everywhere: scheduler, cache row (kv_pos → -1),
        and host-side position/token state, so idle slots genuinely ride
        along at pos 0. A speculative engine clears the draft's mirror
        row too. ``reason`` ∈ obs.schema.RETIRE_REASONS."""
        self.sched.retire(slot, reason=reason)
        self.cache = self._clear(self.cache, jnp.int32(slot))
        if self._spec is not None:
            self._spec.clear(slot)
        self._pos[slot] = 0
        self._last_tok[slot] = 0

    def _evict_slot(self, slot: int):
        """Recovery-only (engine/recovery.py): drop a restored slot whose
        request the journal proves already retired after the snapshot was
        taken — clear the cache row and host state WITHOUT a second
        retire, so exactly-once holds across the crash."""
        if slot in self.sched._prefilling:
            self.sched._prefilling.remove(slot)
        self.sched.slots[slot] = None
        self.cache = self._clear(self.cache, jnp.int32(slot))
        if self._spec is not None:
            self._spec.clear(slot)
        self._pos[slot] = 0
        self._last_tok[slot] = 0
        self._prefill_prog[slot] = 0
        self._fail_streak[slot] = 0

    def _start_decoding(self, slot: int, req: EngineRequest, logits_row,
                        S: int, phase: str):
        """Shared admission tail: sample the FIRST generated token from the
        prompt's final logits row and move the slot into decode (or retire
        it on eos / exhausted budget). Reading the token waits on the
        device for the prefill: that is the child span
        ``<phase>.readback``, counted in the step's wait like a decode
        readback."""
        with self._span(phase + ".readback") as readback:
            first = int(self._sample(logits_row))
        self._step_wait_s += readback.dur
        req.t_first_token = self.clock()
        if self.tracer:
            self.tracer.event("first_token", uid=req.uid, slot=slot)
        if self.journal:
            self.journal.event("first_token", uid=req.uid, slot=slot)
        if first == self.ecfg.eos_id:                 # eos is never emitted
            self._retire(slot, "eos")
            return
        req.out.append(first)
        if self._mx:
            self._mx["tokens"].inc()
        self._last_tok[slot] = first
        self._pos[slot] = S
        if len(req.out) >= req.max_new_tokens:
            self._retire(slot, "budget")
        elif S >= self.ecfg.max_len:
            self._retire(slot, "max_len")

    def _admit_one(self, slot: int, req: EngineRequest) -> int:
        """Legacy ONE-SHOT admission: dense per-request prefill (this is
        the fp (L, S, Hkv, D) materialization) + write_prefill's
        pad/requantize/copy. Returns prompt tokens prefilled."""
        global FP_PREFILL_MATERIALIZATIONS
        if req.max_new_tokens <= 0:                   # explicit 0-token ask
            req.t_first_token = req.t_submit
            self.sched.retire(slot, reason="zero_budget")
            return 0
        S = len(req.prompt)
        with self._span("prefill_oneshot", slot=slot) as sp:
            Sp = self._bucket(S)
            toks = np.zeros((1, Sp), np.int32)
            toks[0, :S] = req.prompt                  # right-pad
            t_d = self.clock()
            logits, pcache = self._prefill(self.params, jnp.asarray(toks))
            dispatch_s = self.clock() - t_d
            self.n_prefills += 1
            FP_PREFILL_MATERIALIZATIONS += 1
            # only [0, S) becomes visible; bucket padding stays masked
            self.cache = self._write(self.cache, jnp.int32(slot), pcache,
                                     jnp.int32(S))
            if self._spec is not None:
                # mirror the prompt into the draft cache (its own one-shot
                # dense materialization — count it honestly)
                self._spec.prefill_oneshot(jnp.asarray(toks), slot, S)
                FP_PREFILL_MATERIALIZATIONS += 1
            # _start_decoding's sample blocks on the prefill logits, so
            # the span's tail (dur - dispatch_s) is device wait +
            # first-token work
            self._start_decoding(slot, req, logits[0, S - 1], S,
                                 "prefill_oneshot")
            sp.note(uid=req.uid, tokens=S, dispatch_s=dispatch_s)
        return S

    # --------------------------------------------------- chunked prefill --
    def _admit_chunked(self, slot: int, req: EngineRequest):
        """Chunked admission: mark the slot mid-prefill; `_prefill_work`
        streams its prompt in over the next step(s)."""
        if req.max_new_tokens <= 0:
            req.t_first_token = req.t_submit
            self.sched.retire(slot, reason="zero_budget")
            return
        self.sched.begin_prefill(slot)
        self._prefill_prog[slot] = 0
        self._pos[slot] = 0                           # parked (see below)
        self._last_tok[slot] = 0

    def _prefill_work(self) -> int:
        """Spend this step's `prefill_chunk`-token budget on mid-prefill
        slots (FCFS). Each dispatched chunk streams through the fused
        kernel: K/V quantized in-kernel, codes written straight into the
        slot rows. A slot whose prompt completes samples its first token
        from the chunk's last logits row and joins the decode batch; a
        slot still mid-prefill stays parked at its next-unwritten position
        (`_pos` = progress), so the decode batch's fixed-shape ride-along
        write lands exactly where the NEXT chunk will overwrite it.

        Chunks are NEVER split to fit leftover budget: a slot's next chunk
        is always min(prefill_chunk, remaining prompt), and if the step's
        remaining budget cannot cover it the work waits for the next step.
        Chunk boundaries are therefore a pure function of (prompt length,
        prefill_chunk) — independent of concurrent load — so a request
        generates the exact same tokens whether it prefilled alone or
        under contention (an int8 cache makes boundary placement visible:
        tokens after a boundary attend the QUANTIZED prefix, so
        load-dependent boundaries would make generations irreproducible).
        Dispatch is asynchronous and nothing here waits on the chunk
        (until a completed prompt samples its first token): a chunk's
        device time is read from a profiler trace, where its
        ``repro.prefill_chunk`` span and its device ops share one clock.
        Returns prompt tokens processed."""
        budget = self.ecfg.prefill_chunk
        spent = 0
        for slot in self.sched.prefill_slots():
            req = self.sched.slots[slot]
            S = len(req.prompt)
            done = int(self._prefill_prog[slot])
            n = min(self.ecfg.prefill_chunk, S - done)
            if n > budget:          # whole chunk or nothing (FCFS head
                break               # waits; boundaries stay load-free)
            with self._span("prefill_chunk", slot=slot, pos_start=done,
                            n=n) as sp:
                Sc = bucket_len(n, self.ecfg.prefill_bucket,
                                self.ecfg.prefill_chunk)
                toks = np.zeros((1, Sc), np.int32)
                toks[0, :n] = req.prompt[done:done + n]   # right-pad
                t_d = self.clock()
                logits, self.cache = self._chunk_prefill(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.int32(slot), jnp.int32(done), jnp.int32(n))
                sp.note(uid=req.uid, dispatch_s=self.clock() - t_d)
                if self._spec is not None:  # mirror the chunk to the draft
                    self._spec.prefill_chunk(jnp.asarray(toks), slot, done,
                                             n)
                self.n_prefill_chunks += 1
                if self._mx:
                    self._mx["prefill_chunks"].inc()
                budget -= n
                spent += n
                done += n
                self._prefill_prog[slot] = done
                self._pos[slot] = done                # parked position
                if done >= S:                         # prompt complete
                    self.sched.finish_prefill(slot)
                    self._start_decoding(slot, req, logits[0], S,
                                         "prefill_chunk")
        return spent

    # ------------------------------------------- speculative decoding --
    def _spec_step(self, active: list[int]) -> None:
        """One SPECULATIVE decode step (DESIGN.md §9): the low-bit draft
        proposes up to `spec_k` greedy tokens per active slot in batched
        decode steps over its own cache, then the target scores each
        slot's whole window in ONE fused verify pass and commits the
        longest matching draft prefix plus its own correction token —
        between 1 and spec_k+1 tokens per slot per step, always exactly
        the tokens plain greedy decoding would have produced.

        Windows are per-slot (`w = min(spec_k+1, cache headroom,
        remaining budget)`), so budget-capped slots degrade to w=1 —
        an ordinary decode step expressed through the verify path — and
        spec/non-spec slots mix freely in one step. Verify writes the
        window's K/V codes in-kernel; rejected rows are undone by
        `rollback_slot` on both caches (kv_pos → -1 is the whole
        rollback), leaving slot bytes bit-identical to a never-speculated
        engine once overwritten."""
        k = self.ecfg.spec_k
        Sq = k + 1
        N = self.ecfg.n_slots
        pos0 = self._pos.copy()
        commit0 = self.n_spec_commit_tokens
        t0 = self.clock()
        # per-slot window lengths: 0 parks the slot through the draft
        # pass (idle / mid-prefill), w >= 1 for decoding slots
        w = np.zeros(N, np.int64)
        for s in active:
            req = self.sched.slots[s]
            rem = req.max_new_tokens - len(req.out)
            w[s] = max(1, min(Sq, self.ecfg.max_len - int(pos0[s]), rem))
        drafts = self._spec.draft(self._last_tok, pos0, w)     # (k, N)
        from .spec import accept_length
        tr = self.tracer
        for s in active:
            req = self.sched.slots[s]
            ws = int(w[s])
            with self._span("verify", slot=s) as sp:
                toks = np.zeros((1, Sq), np.int32)
                toks[0, 0] = self._last_tok[s]
                toks[0, 1:ws] = drafts[:ws - 1, s]
                t_d = self.clock()
                garg, self.cache = self._verify(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.int32(s), jnp.int32(pos0[s]), jnp.int32(ws))
                t_w = self.clock()
                garg = np.asarray(garg)        # (Sq,) target argmax rows
                                               # — the device wait
                wait_s = self.clock() - t_w
                self.n_verify_calls += 1
                self.n_verify_tokens += ws
                a = accept_length(drafts[:, s], garg, ws)
                self.sched.note_spec(s, proposed=ws - 1, accepted=a)
                sp.note(uid=req.uid, tokens=ws, accepted=a,
                        dispatch_s=t_w - t_d, wait_s=wait_s)
            new_pos = int(pos0[s]) + a + 1
            if a + 1 < ws:                     # rejected rows to undo
                with self._span("rollback", slot=s) as sp:
                    self.cache = _ROLLBACK(self.cache, jnp.int32(s),
                                           jnp.int32(new_pos))
                    self._spec.rollback(s, new_pos)
                    sp.note(uid=req.uid, accept_len=new_pos)
                if tr:
                    tr.event("rollback", uid=req.uid, slot=s,
                             accept_len=new_pos,
                             rejected=ws - (a + 1))
            # commit g_1..g_{a+1} with the same eos/budget/max_len
            # semantics as sequential decode steps
            with self._span("accept_commit", slot=s) as sp:
                sp.note(uid=req.uid, committed=a + 1)
                for t in (int(x) for x in garg[:a + 1]):
                    if t == self.ecfg.eos_id:  # eos is never emitted
                        self._retire(s, "eos")
                        break
                    req.out.append(t)
                    self.n_spec_commit_tokens += 1
                    self._last_tok[s] = t
                    self._pos[s] += 1
                    if len(req.out) >= req.max_new_tokens:
                        self._retire(s, "budget")
                        break
                    if self._pos[s] >= self.ecfg.max_len:
                        self._retire(s, "max_len")
                        break
        self.n_spec_steps += 1
        self.spec_step_s.append(self.clock() - t0)
        self.sched.note_step(len(active))
        if self._mx:
            self._mx["spec_steps"].inc()
            self._mx["tokens"].inc(self.n_spec_commit_tokens - commit0)
            if self.sched.accept_ewma is not None:
                self._mx["accept_ewma"].set(self.sched.accept_ewma)

    # --------------------------------------- plain decode with retry --
    def _dispatch_decode(self, n_active: int) -> np.ndarray:
        """One batched plain-decode dispatch over all N slots; returns
        the per-slot sampled tokens on host. The ``decode`` span opens
        before staging, and its children split it: ``decode.stage`` (the
        two host->device puts, on small models as costly as the
        matmuls), ``decode.dispatch`` (the jitted call until it returns)
        and ``decode.readback`` (the host transfer, which waits on the
        device). Their durations are the span's ``dispatch_s`` and
        ``wait_s``, and the readback's counts in the flight record's
        ``wait_s``. The tracked decode_step_s metric keeps its
        historical bracket (post-staging t0) so its trend stays
        comparable across PRs."""
        with self._span("decode", slots=n_active) as sp:
            with self._span("decode.stage"):
                tokens = jnp.asarray(self._last_tok[:, None])
                pos = jnp.asarray(self._pos)
            t0 = self.clock()
            with self._span("decode.dispatch") as dispatch:
                out, self.cache = self._decode(self.params, self.cache,
                                               tokens, pos)
                if not self._greedy:
                    out = self._sample(out[:, -1])
            with self._span("decode.readback") as readback:
                toks = np.asarray(out)
            self.n_decode_steps += 1
            # toks is on host here, so this brackets the real per-step
            # decode latency (dispatch + device compute + sample)
            dt = self.clock() - t0
            self.decode_step_s.append(dt)
            if self._mx:
                self._mx["decode_steps"].inc()
                self._mx["decode_s"].observe(dt)
            sp.note(dispatch_s=dispatch.dur, wait_s=readback.dur)
        self._step_wait_s += readback.dur
        return toks

    def _decode_with_retry(self, active: list) \
            -> tuple[Optional[np.ndarray], list]:
        """Plain decode step with bounded retry-on-failure (§12).

        Failure sources: injected faults (ecfg.fault_spec) and the
        always-on sanity check that every sampled token id is in-vocab —
        the host-side detector for corrupted logits (greedy sampling is
        folded into the jitted executable, so NaN logits are observable
        only as a garbage argmax; an out-of-range id is the symptom, and
        unlike a raised exception it is per-SLOT attributable).

        Recovery contract: a failed attempt may already have written this
        step's K/V row for every decoding slot, so ALL active slots roll
        back to their pre-step positions — `rollback_slot`'s kv_pos→-1
        positional invalidation, the same primitive speculative decoding
        rolls rejected windows back with — and the step re-executes.
        Greedy decode re-derives bit-identical tokens from the unchanged
        committed prefix (the spec-path hypothesis property of
        tests/test_spec.py, re-asserted end-to-end under fault storms in
        tests/test_faults.py). A slot whose token stays corrupt for
        ``max_retries + 1`` consecutive attempts is quarantined — retired
        as "failed" and dropped from the batch — so one poison request
        can never wedge everyone else. Unattributable failures (raised
        exceptions) share the attempt budget and fail the WHOLE batch
        when it exhausts: the loud backstop for a deterministically
        crashing step, loud because silently spinning would be worse.

        Returns (tokens, surviving_active); tokens is None when every
        slot was quarantined."""
        pos0 = self._pos.copy()
        attempt = 0
        while active:
            inj = self._faults
            kind = inj.draw_step() if inj else None
            try:
                if kind == "exception":
                    raise StepFailure("injected transient step exception")
                if kind == "slow":
                    inj.sleep()
                toks = self._dispatch_decode(len(active))
                if inj is not None:
                    toks = inj.corrupt_tokens(
                        toks, active,
                        {s: self.sched.slots[s].uid for s in active})
                bad = [s for s in active
                       if not 0 <= int(toks[s]) < self.cfg.vocab]
                if bad:
                    raise StepFailure(
                        f"out-of-vocab decode token(s): "
                        f"{[(s, int(toks[s])) for s in bad]}", slots=bad)
                self._fail_streak[active] = 0
                return toks, active
            except StepFailure as e:
                attempt += 1
                self.n_step_retries += 1
                if self._mx:
                    self._mx["retries"].inc()
                if self._detect is not None:
                    # attributable failures carry the victim slots — name
                    # the first victim's uid in the incident trigger
                    uid = (self.sched.slots[e.slots[0]].uid
                           if e.slots and self.sched.slots[e.slots[0]]
                           is not None else None)
                    self._detect.note("step_retry", reason=str(e), uid=uid)
                # undo any K/V the failed dispatch wrote: every active
                # slot back to its pre-step position (host _pos has not
                # advanced, so re-execution is bit-identical)
                for s in active:
                    self.cache = _ROLLBACK(self.cache, jnp.int32(s),
                                           jnp.int32(pos0[s]))
                if e.slots:
                    for s in e.slots:
                        self._fail_streak[s] += 1
                        if self._fail_streak[s] > self.ecfg.max_retries:
                            print(f"[engine] quarantining slot {s} (uid "
                                  f"{self.sched.slots[s].uid}): corrupt "
                                  f"decode output {self._fail_streak[s]} "
                                  f"attempts running", file=sys.stderr)
                            self.n_quarantined += 1
                            if self._detect is not None:
                                self._detect.note(
                                    "quarantine",
                                    uid=self.sched.slots[s].uid,
                                    reason=f"slot {s}: corrupt output "
                                           f"{int(self._fail_streak[s])} "
                                           f"attempts running")
                            self._retire(s, "failed")
                            self._fail_streak[s] = 0
                            active = [a for a in active if a != s]
                elif attempt > self.ecfg.max_retries:
                    print(f"[engine] decode failed {attempt} attempts "
                          f"with no attributable slot — failing the "
                          f"whole batch: {e}", file=sys.stderr)
                    for s in list(active):
                        self._fail_streak[s] = 0
                        self.n_quarantined += 1
                        if self._detect is not None:
                            self._detect.note(
                                "quarantine",
                                uid=self.sched.slots[s].uid,
                                reason=f"slot {s}: whole-batch failure "
                                       f"after {attempt} attempts")
                        self._retire(s, "failed")
                    active = []
                if active and self.ecfg.retry_backoff_s > 0:
                    time.sleep(min(0.05, self.ecfg.retry_backoff_s
                                   * (2.0 ** (attempt - 1))))
        return None, []

    def _prefill_backlog(self) -> int:
        """Prompt chunks still to stream for mid-prefill slots — the
        prefill half of the ladder's pressure signal and the end-of-step
        backlog gauge."""
        if not self.ecfg.prefill_chunk:
            return 0
        backlog = 0
        for s in self.sched.prefill_slots():
            rem = len(self.sched.slots[s].prompt) \
                - int(self._prefill_prog[s])
            backlog += -(-rem // self.ecfg.prefill_chunk)
        return backlog

    def _span(self, name: str, **args) -> Span:
        """A phase span on the engine's clock (``repro.<name>`` in a
        profiler trace; a ring-buffer record too when tracing)."""
        return Span(self.tracer, name, self.clock, **args)

    def step(self) -> list[EngineRequest]:
        """Admit + (chunk-budgeted) prefill + one batched decode step.
        Returns requests finishing now."""
        with self._span("step") as sp:
            return self._step(sp)

    def _step(self, sp: Span) -> list[EngineRequest]:
        if self._t_start is None:
            self._t_start = self.clock()
        t_step0 = self.clock()
        self._step_wait_s = 0.0
        # --- injected process death (faults.crash_rate, §13) -----------
        # drawn before ANY step work: the journal's durability horizon is
        # the step boundary, so flush whatever arrived since the last
        # step's fsync (client submits land between steps) and die —
        # recovery then sees exactly the pre-step state
        if self._faults is not None and self._faults.draw_crash():
            if self.journal:
                self.journal.sync()
            self._faults.crash()
        n_done_before = len(self.sched.finished)
        # decoders that were ALREADY mid-generation when this step's
        # prefill work ran — the requests a prefill stall actually delays
        # (a slot admitted and first-decoded in the same step was not
        # waiting on anything; counting it would inflate the one-shot
        # stall baseline with the idle-engine admission burst)
        n_decoding_before = len(self.sched.active_slots())
        # dispatch-wall ring lengths at step start: whichever ring grew
        # this step holds the step's decode/verify dispatch wall (the
        # coarse dispatch split in the flight record)
        n_dec0, n_spec0 = len(self.decode_step_s), len(self.spec_step_s)
        with self._span("admit"):
            placed = self._admit()
        prefill_tokens = 0
        if not self.ecfg.prefill_chunk:
            for slot, req in placed:
                prefill_tokens += self._admit_one(slot, req)
        else:
            prefill_tokens = self._prefill_work()
            # nobody is decoding ⇒ nobody can be stalled: keep spending
            # whole-chunk budgets until a slot finishes its prompt and
            # joins the decode batch (the chunk budget only throttles
            # prefill that would delay CONCURRENT decode steps; a
            # decode-idle engine prefills at one-shot speed)
            while not self.sched.active_slots() and \
                    self.sched.prefill_slots():
                prefill_tokens += self._prefill_work()
        active = self.sched.active_slots()
        if active and self._spec is not None and self._rung < 1:
            # speculative step: draft k tokens batched over the draft
            # cache, verify each slot's window in one fused pass, commit
            # 1..spec_k+1 tokens per slot (token-identical to the plain
            # decode branch below)
            self._spec_step(active)
        elif active:
            # idle slots ride along at pos 0 with token 0 (fixed decode
            # shape == jit cache of exactly one entry); _retire cleared
            # their kv_pos rows, so each idle step re-marks only its own
            # t=0 entry, and the next admit rewrites the row wholesale.
            # Mid-prefill slots ride along the same way, parked at their
            # next-unwritten position: the garbage row the ride-along
            # write marks valid is overwritten by the slot's next chunk,
            # and the chunk kernel masks cache rows at >= pos_start, so
            # it can never be attended (per-slot attention shields every
            # other request)
            if self._spec is not None:
                # ladder rung >= 1: spec engine routed through plain
                # decode — output-identical by the lossless accept rule,
                # so suspension is the free first degradation
                self._spec.note_suspended()
            toks, active = self._decode_with_retry(active)
            with self._span("accept_commit") as sc:
                sc.note(slots=len(active))
                emitted = 0
                for slot in active:
                    req = self.sched.slots[slot]
                    t = int(toks[slot])
                    self._pos[slot] += 1
                    if t == self.ecfg.eos_id:
                        self._retire(slot, "eos")
                        continue
                    req.out.append(t)
                    emitted += 1
                    self._last_tok[slot] = t
                    if len(req.out) >= req.max_new_tokens:
                        self._retire(slot, "budget")
                    elif self._pos[slot] >= self.ecfg.max_len:
                        self._retire(slot, "max_len")
                self.sched.note_step(len(active))
                if self._mx:
                    self._mx["tokens"].inc(emitted)
        tr = self.tracer
        if tr and self.ecfg.trace_kv_every and self.cache.mode == "int8" \
                and len(self.step_s) % self.ecfg.trace_kv_every == 0:
            # periodic KV quantization-quality sample: a host transfer of
            # live cache rows — traced-mode-only cost, span-attributed
            from .kvcache import kv_quality_counters
            with self._span("kv_sample"):
                tr.counter("kv_quality", kv_quality_counters(self.cache))
        self.step_s.append(self.clock() - t_step0)
        self.step_prefill_tokens.append(prefill_tokens)
        self.step_decode_slots.append(n_decoding_before)
        sp.note(prefill_tokens=prefill_tokens,
                decode_slots=n_decoding_before)
        with self._span("record"):
            self._record_step(prefill_tokens, n_dec0, n_spec0,
                              n_decoding_before)
        return self.sched.finished[n_done_before:]

    def _admit(self) -> list:
        """Deadline sweep, degradation ladder and admission; returns the
        (slot, request) pairs admitted. Chunked admission only marks a
        slot mid-prefill, so it happens here; a one-shot prefill is the
        caller's."""
        if self._any_deadlines:
            self._enforce_deadlines()
        # --- degradation ladder (faults.DegradationLadder, §12) --------
        # pressure = queue depth + prefill backlog chunks, fed BEFORE
        # admission so this step's policy reflects the load it is about
        # to admit under
        defer = ()
        if self._ladder is not None:
            pressure = len(self.sched.queue) + self._prefill_backlog()
            rung = self._ladder.update(pressure)
            if rung != self._rung:
                if self._mx:
                    self._mx["degr_transitions"].inc()
                if self.tracer:
                    self.tracer.event("degrade", rung=rung,
                                      prev=self._rung, pressure=pressure)
                self._rung = rung
            if self._mx:
                self._mx["rung"].set(rung)
            if rung >= 3:
                # shed queued load (batch class first) back down to the
                # rung-2 threshold — enough relief to stop climbing
                self.sched.shed_queued_to(int(self._ladder.thresholds[1]))
            if rung >= 2:
                defer = ("batch",)
        placed = self.sched.admit(defer=defer)
        if self.ecfg.prefill_chunk:
            for slot, req in placed:
                self._admit_chunked(slot, req)
        return placed

    def _record_step(self, prefill_tokens: int, n_dec0: int, n_spec0: int,
                     n_decoding_before: int) -> None:
        """End-of-step bookkeeping: registry gauges, journal sync,
        periodic snapshot, flight record and detector sweep."""
        mx = self._mx
        if mx:
            # end-of-step queueing gauges: O(n_slots) host bookkeeping,
            # no device traffic — the always-on cost the ≤1% overhead
            # bound covers
            mx["steps"].inc()
            mx["step_s"].observe(self.step_s[-1])
            if prefill_tokens:
                mx["prefill_tokens"].inc(prefill_tokens)
            occupied = in_flight = 0
            for r in self.sched.slots:
                if r is not None:
                    occupied += 1
                    in_flight += max(0, r.max_new_tokens - len(r.out))
            backlog = self._prefill_backlog()
            mx["occupancy"].set(occupied / self.ecfg.n_slots)
            mx["decoding"].set(len(self.sched.active_slots()))
            mx["backlog"].set(backlog)
            mx["in_flight"].set(in_flight)
            if self.ecfg.metrics_kv_every and self.cache.mode == "int8" \
                    and len(self.step_s) % self.ecfg.metrics_kv_every == 0:
                # periodic KV quality gauges: bounded host transfer of
                # live cache rows (kvcache.kv_quality_counters) — the
                # one metrics signal that is NOT free, which is why it
                # has its own period and defaults off
                from .kvcache import kv_quality_counters
                kc = kv_quality_counters(self.cache)
                clips = []
                for side in ("k", "v"):
                    if kc.get(f"{side}_clip_frac") is not None:
                        mx[f"kv_{side}_clip"].set(kc[f"{side}_clip_frac"])
                        mx[f"kv_{side}_occ"].set(kc[f"{side}_occupancy"])
                        clips.append(kc[f"{side}_clip_frac"])
                # stash the worse-side samples for the flight record /
                # kv_clip_spike detector (same pull, no extra transfer)
                if clips:
                    self._last_clip_frac = max(clips)
                spans = []
                for side in ("k", "v"):
                    hist = kc.get(f"{side}_span_outlier_hist")
                    if hist and sum(hist) > 0:
                        # buckets at > 4x the median chunk span — the
                        # OCS outlier tail (quality.OUTLIER_LOG2_EDGES)
                        spans.append(sum(hist[5:]) / sum(hist))
                if spans:
                    self._last_span_frac = max(spans)
        # --- crash safety (§13): make the boundary durable --------------
        # journal fsync FIRST, then the periodic snapshot — so a snapshot
        # never holds state the journal hasn't seen (snapshot ⊆ WAL)
        if self.journal is not None:
            self.journal.sync()
        if self.ecfg.snapshot_every and self.ecfg.snapshot_path \
                and len(self.step_s) % self.ecfg.snapshot_every == 0:
            self.snapshot()
        # --- flight record + anomaly sweep (obs/flight.py, §14) ---------
        # after the journal fsync so a bundle's journal tail includes
        # this step; the record is one small dict + ring append — the
        # always-on cost the flight_recorder overhead bound covers
        fr, det = self._flight, self._detect
        if fr is not None or det is not None:
            uids = self.sched.occupied_uids()
            rec = {
                "step": len(self.step_s) - 1,
                "step_s": round(self.step_s[-1], 6),
                "decode_s": round(
                    self.decode_step_s[-1]
                    if len(self.decode_step_s) > n_dec0 else
                    (self.spec_step_s[-1]
                     if len(self.spec_step_s) > n_spec0 else 0.0), 6),
                "draft_s": round(self._spec.last_draft_s, 6)
                if self._spec is not None and self._rung < 1 else 0.0,
                "wait_s": round(self._step_wait_s, 6),
                "queue": len(self.sched.queue),
                "backlog": self._prefill_backlog(),
                "occupied": len(uids),
                "decoding": n_decoding_before,
                "rung": self._rung,
                "retries": self.n_step_retries,
                "quarantined": self.n_quarantined,
                "accept": (round(self.sched.accept_ewma, 4)
                           if self._spec is not None
                           and self.sched.accept_ewma is not None
                           else None),
                "spec_off": bool(self._spec is not None
                                 and self._rung >= 1),
                "clip_frac": self._last_clip_frac,
                "span_frac": self._last_span_frac,
                "uids": uids,
            }
            if fr is not None:
                rec = fr.record(**rec)
            if det is not None:
                firings = det.sweep(rec)
                if firings:
                    self._capture_incident(firings)

    # -------------------------------------------- incident capture (§14) --
    def _capture_incident(self, firings, force: bool = False):
        """Write one incident bundle for a batch of detector firings —
        the first firing is the named trigger. A global cooldown
        (ecfg.incident_cooldown steps) gates bundles so a fault storm
        yields one incident, not one per step; ``force`` bypasses it
        (explicit dumps: supervisor restart, IntegrityError)."""
        if not self.ecfg.incident_dir or not firings:
            return None
        step = len(self.step_s)
        if not force and self._last_bundle_step is not None \
                and step - self._last_bundle_step \
                < self.ecfg.incident_cooldown:
            return None
        from ..obs.flight import tail_lines, write_incident_bundle
        from ..obs.provenance import provenance
        from .recovery import _engine_fingerprint, _req_doc
        trigger = firings[0]
        docs: dict = {
            "trigger.json": {
                "schema": 1, "step": step,
                "trigger": trigger.to_dict(),
                "firings": [f.to_dict() for f in firings],
                "faults_injected": (self._faults.counts()
                                    if self._faults is not None else None),
            },
            "flight.json": {
                "header": (self._flight.header()
                           if self._flight is not None else None),
                "records": (self._flight.window()
                            if self._flight is not None else []),
            },
            "metrics.json": (self.registry.snapshot()
                             if self.registry is not None else None),
            "fingerprint.json": _engine_fingerprint(self),
            "provenance.json": provenance(),
            "requests.json": {
                "active": [dict(_req_doc(r), slot=s)
                           for s, r in enumerate(self.sched.slots)
                           if r is not None],
                "queued": [_req_doc(r) for r in self.sched.queue],
                "poison_uids": (sorted(self._faults.poison_uids)
                                if self._faults is not None else []),
            },
        }
        if self.ecfg.journal_path:
            if self.journal is not None:
                self.journal.sync()
            docs["journal_tail.jsonl"] = tail_lines(
                self.ecfg.journal_path, 200)
        # sequence from what's on disk, not this object's counter: a
        # supervised restart replaces the engine but bundles persist,
        # and an overwritten bundle would silently eat an incident
        try:
            seq = len([d for d in os.listdir(self.ecfg.incident_dir)
                       if d.startswith("incident-")
                       and not d.endswith(".tmp")])
        except OSError:
            seq = 0
        name = f"incident-{seq:03d}-{trigger.detector}"
        path = write_incident_bundle(self.ecfg.incident_dir, name, docs)
        self.incidents.append(path)
        self._last_bundle_step = step
        print(f"[engine] incident bundle: {path} "
              f"(trigger {trigger.detector}: {trigger.reason})",
              file=sys.stderr)
        return path

    def dump_incident(self, detector: str, reason: str = "",
                      uid: Optional[int] = None):
        """Explicitly capture an incident bundle (bypasses the cooldown).
        Used by the serve supervisor after an ``InjectedCrash`` restart
        and by the restore path on ``IntegrityError`` — anomalies that
        happen outside the step loop, where no sweep will run."""
        from ..obs.detect import Firing
        return self._capture_incident(
            [Firing(detector, len(self.step_s), reason, uid=uid)],
            force=True)

    # ------------------------------------------------- crash safety ------
    def snapshot(self, path: Optional[str] = None) -> str:
        """Write the full serving state (quantized slot cache, draft
        twin, scheduler queue + slot table, host decode state, PRNG key)
        to ``path`` atomically (engine/recovery.py, DESIGN.md §13)."""
        from .recovery import snapshot_engine
        path = path if path is not None else self.ecfg.snapshot_path
        if not path:
            raise ValueError("snapshot needs a path (argument or "
                             "EngineConfig.snapshot_path)")
        out = snapshot_engine(self, path)
        if self._mx:
            self._mx["snapshots"].inc()
        if self.journal:
            self.journal.event("snapshot", step=len(self.step_s))
        return out

    def restore(self, path: str) -> dict:
        """Restore serving state from a snapshot into this (freshly
        constructed, idle) engine. Integrity-validated: checksums, code
        ranges, kv_pos invariants — raises ``IntegrityError`` rather
        than serve a corrupt artifact. Returns the snapshot manifest."""
        from .recovery import IntegrityError, restore_engine
        t0 = self.clock()
        try:
            manifest = restore_engine(self, path)
        except IntegrityError as e:
            # capture the rejected artifact's context before failing loud
            self.dump_incident("integrity_error", reason=str(e))
            raise
        if self._mx:
            self._mx["restores"].inc()
            self._mx["restore_s"].observe(self.clock() - t0)
        return manifest

    def recover(self, snapshot_path: Optional[str] = None,
                journal_path: Optional[str] = None) -> dict:
        """Snapshot restore + journal replay: resume what the snapshot
        holds, re-enqueue journal submissions past the snapshot horizon,
        evict anything the journal proves already retired. Either source
        may be absent (journal-only recovery re-prefills everything).
        Returns recovery.recover_engine's summary dict."""
        from .recovery import IntegrityError, recover_engine
        t0 = self.clock()
        try:
            info = recover_engine(
                self,
                snapshot_path if snapshot_path is not None
                else self.ecfg.snapshot_path,
                journal_path if journal_path is not None
                else self.ecfg.journal_path)
        except IntegrityError as e:
            self.dump_incident("integrity_error", reason=str(e))
            raise
        if self._mx:
            if info["manifest"] is not None:
                self._mx["restores"].inc()
            self._mx["replayed"].inc(info["n_restored"]
                                     + info["n_requeued"])
            self._mx["restore_s"].observe(self.clock() - t0)
        return info

    def drain(self, timeout_s: Optional[float] = None,
              stall_steps: int = 10_000) -> list[EngineRequest]:
        """Run until queue and slots are empty; returns all finished
        requests in uid order.

        Watchdog (§12): the loop is bounded by wall clock (``timeout_s``,
        None = unbounded) AND by a no-progress counter — ``stall_steps``
        consecutive steps during which nothing observable moved (no
        finish, no admission, no token committed, no prefill progress).
        A healthy engine always moves one of those per step, so tripping
        either bound means a wedge; the watchdog force-fails every
        outstanding request (reason "failed") with a loud log instead of
        hanging the caller forever. The historical drain() — plain
        ``while not idle: step()`` — is the defaults' behavior on any
        non-wedged engine."""
        t0 = self.clock()
        stalled = 0
        sig = None
        while not self.sched.idle:
            self.step()
            cur = (len(self.sched.finished), self.sched.n_admitted,
                   sum(len(r.out) for r in self.sched.slots
                       if r is not None),
                   int(self._prefill_prog.sum()))
            if cur == sig:
                stalled += 1
            else:
                stalled = 0
                sig = cur
            if stalled >= stall_steps:
                self._force_fail_outstanding(
                    f"no progress across {stalled} consecutive steps")
                break
            if timeout_s is not None and self.clock() - t0 > timeout_s:
                self._force_fail_outstanding(
                    f"drain exceeded timeout_s={timeout_s}")
                break
        self.sweep_idle_rows()
        return sorted(self.sched.finished, key=lambda r: r.uid)

    def sweep_idle_rows(self) -> None:
        """Clear the ride-along position marks idle slots accumulate.

        An idle slot in the fixed-shape decode batch re-marks its own
        t=0 row each step (by design — the next admission rewrites the
        row wholesale), so after the LAST decode step of a drain, slots
        that retired before it still carry one stray mark. Clearing
        empty slots here (target and draft caches) restores the
        "drained engine ⇒ empty slot pool" invariant the chaos harness
        leak-checks with `kvcache.occupied_slots`. O(n_slots) tiny
        dispatches, once per drain — not hot-path cost."""
        for s, r in enumerate(self.sched.slots):
            if r is None:
                self.cache = self._clear(self.cache, jnp.int32(s))
                if self._spec is not None:
                    self._spec.clear(s)

    def _force_fail_outstanding(self, why: str) -> None:
        """Watchdog action: fail every queued + slotted request so the
        drain terminates with the full exactly-once retire accounting
        intact (a wedged engine must still leave no request in limbo)."""
        n_q = len(self.sched.queue)
        n_s = sum(r is not None for r in self.sched.slots)
        print(f"[engine] drain watchdog tripped ({why}): force-failing "
              f"{n_q} queued + {n_s} slotted request(s)", file=sys.stderr)
        for slot, req in enumerate(self.sched.slots):
            if req is not None:
                self._retire(slot, "failed")
        while self.sched.queue:
            self.sched.drop_queued(self.sched.queue[0], "failed")

    # ----------------------------------------------------------- metrics --
    def metrics(self) -> dict:
        from repro.obs import mean, pct as p, phase_breakdown
        fin = self.sched.finished
        reasons: dict = {}
        for r in fin:
            reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
        ttfts = [r.ttft for r in fin if r.ttft is not None]
        tps = [r.tokens_per_s for r in fin if r.tokens_per_s is not None]
        total_tokens = sum(len(r.out) for r in fin)
        wall = (self.clock() - self._t_start) if self._t_start else 0.0
        steps = np.asarray(self.decode_step_s, np.float64)
        full = np.asarray(self.step_s, np.float64)
        pmask = (np.asarray(self.step_prefill_tokens, np.int64) > 0) \
            & (np.asarray(self.step_decode_slots, np.int64) > 0)
        withp = full[pmask[:full.size]] if full.size else full
        spec = {}
        if self.ecfg.spec_k:
            hist = np.bincount(np.asarray(self.sched.accept_hist,
                                          np.int64),
                               minlength=self.ecfg.spec_k + 1) \
                if self.sched.accept_hist else np.zeros(0, np.int64)
            sstep = np.asarray(self.spec_step_s, np.float64)
            spec = {
                "spec_k": self.ecfg.spec_k,
                "spec_steps": self.n_spec_steps,
                "verify_calls": self.n_verify_calls,
                "verify_tokens": self.n_verify_tokens,
                "draft_steps": (self._spec.n_draft_steps
                                if self._spec else 0),
                "draft_proposed": self.sched.spec_proposed,
                "draft_accepted": self.sched.spec_accepted,
                "acceptance_rate": self.sched.acceptance_rate(),
                # accept_hist[a] = verify calls that accepted exactly a
                # draft tokens (a in [0, spec_k])
                "accept_hist": hist.tolist(),
                # tokens actually COMMITTED per verify (eos/budget can
                # truncate below accepted+1, so this is computed from
                # appended tokens, not from the accept histogram)
                "tokens_per_verify_mean": (
                    self.n_spec_commit_tokens / self.n_verify_calls
                    if self.n_verify_calls else None),
                "spec_step_p50_s": p(sstep, 50),
                "spec_step_p95_s": p(sstep, 95),
                "spec_by_slot": [list(x) for x in self.sched.spec_by_slot],
                # live acceptance gauge: EWMA over per-verify fractions —
                # tracks recent drift the cumulative rate smooths away
                "acceptance_ewma": self.sched.accept_ewma,
                # plain-decode steps taken while the ladder suspended
                # speculation (rung >= 1) — output-identical by the
                # accept rule, costs only acceptance on resume
                "spec_suspended_steps": (self._spec.n_suspended_steps
                                         if self._spec else 0),
            }
        out = {
            "n_finished": len(fin),
            "total_tokens": total_tokens,
            "wall_s": wall,
            "tokens_per_s": total_tokens / wall if wall > 0 else None,
            "decode_steps": self.n_decode_steps,
            "prefills": self.n_prefills,
            "prefill_chunks": self.n_prefill_chunks,
            "prefill_chunk": self.ecfg.prefill_chunk,
            "slot_utilization": self.sched.utilization(),
            "queue_depth_max": max(self.sched.queue_depth_hist, default=0),
            # always-on queueing signals (scheduler records these at
            # submit/admit time with or without a tracer — obs.summary
            # keeps the None-on-empty convention)
            "queue_depth_at_submit_p50": p(self.sched.queue_depth_submit,
                                           50),
            "queue_depth_at_submit_p95": p(self.sched.queue_depth_submit,
                                           95),
            "admit_latency_mean_s": mean(self.sched.admit_latency_s),
            "admit_latency_p50_s": p(self.sched.admit_latency_s, 50),
            "admit_latency_p95_s": p(self.sched.admit_latency_s, 95),
            "ttft_mean_s": mean(ttfts),
            "ttft_p50_s": p(ttfts, 50),
            "ttft_p95_s": p(ttfts, 95),
            "request_tokens_per_s_mean": mean(tps),
            "decode_step_p50_s": p(steps, 50),
            "decode_step_p95_s": p(steps, 95),
            "decode_step_mean_s": mean(steps),
            # full-step latency: the admission-stall telemetry — a step
            # that prefilled a whole prompt one-shot blocks every decoding
            # slot for that long; chunked prefill bounds it by the budget
            "step_p50_s": p(full, 50),
            "step_p95_s": p(full, 95),
            "step_with_prefill_p95_s": p(withp, 95),
            "steps_with_prefill": int(pmask.sum()),
            "fused_attn": self.ecfg.fused_attn,
            "kv_mode": self.cache.mode,
            "kv_static_scales": self.cache.static,
            "kv_bytes_per_token": self.cache.bytes_per_token(),
            # fault-tolerance accounting (§12): the retire-reason
            # partition (every finished request counted exactly once)
            # plus the policy counters the chaos harness asserts over
            "retire_reasons": reasons,
            "requests_shed": self.sched.n_shed,
            "requests_cancelled": self.sched.n_cancelled,
            "step_retries": self.n_step_retries,
            "quarantined": self.n_quarantined,
            "degradation_rung": self._rung,
            "degradation_transitions": (self._ladder.n_transitions
                                        if self._ladder else 0),
            # flight recorder + incident capture (§14)
            "flight_recorded": (self._flight.n_recorded
                                if self._flight is not None else 0),
            "incidents": list(self.incidents),
            "anomalies_fired": (self._detect.n_fired
                                if self._detect is not None else 0),
            **spec,
        }
        if self._faults is not None:
            out["faults_injected"] = self._faults.counts()
        if self.registry is not None:
            # the always-on registry snapshot rides along so one
            # metrics() call is the full observability surface (the
            # same dict SnapshotWriter streams and to_prometheus
            # renders)
            out["registry"] = self.registry.snapshot()
        if self.tracer:
            # traced engines embed the phase-attribution summary so every
            # metrics consumer (serve.py --metrics-json, the benchmarks)
            # gets the step-time breakdown without reparsing the trace
            out["phase_attribution"] = phase_breakdown(self.tracer.events)
            out["trace_records"] = len(self.tracer.events)
            out["trace_dropped"] = self.tracer.dropped
        return out
