"""Quantized serving driver: SplitQuant-preprocess a model's weights, low-
bit quantize, and serve requests (the paper's deployment story).

Default path is the continuous-batching engine (`repro.engine`) with an
optionally INT8-quantized KV cache; `--wave` selects the legacy wave-
synchronous loop for comparison.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --bits 2 --method splitquant --requests 4 --kv-mode int8

Calibrated serving (repro.calib): `--save-recipe DIR` runs the offline
step once — quantize the weights (honoring any recipe policies), collect
KV range statistics on calibration prompts, and write a QuantRecipe +
quantized checkpoint. `--recipe DIR` then serves from that directory:
weights restore pre-quantized (no k-means at startup) and the INT8 KV
cache uses the recipe's static scales (no per-step min/max reduce).

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --bits 2 --save-recipe /tmp/rec
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --recipe /tmp/rec --kv-mode int8
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.configs import get_arch
from repro.core import QuantConfig, QuantPolicy, quantize_tree
from repro.engine import (Engine, EngineConfig, FaultSpec, InjectedCrash,
                          admission_set_point, occupied_slots)
from repro.models import get_model
from repro.runtime.serve_loop import Request, ServeConfig, Server
from .compile_cache import use_compile_cache


def load_recipe_params(recipe_dir, params, arch=None, reduced=None):
    """(params, recipe, kv_scales) from a saved QuantRecipe: restore the
    pre-quantized checkpoint if the recipe points at one (no k-means),
    else apply the recipe's per-path policies to `params`.

    ``arch``/``reduced``: when given, validated against the recipe's
    provenance — a mismatched recipe otherwise dies deep inside a
    checkpoint lookup or a shape error with no hint of the real cause.
    """
    from repro.calib import QuantRecipe
    from repro.checkpoint import ckpt

    rec = QuantRecipe.load(recipe_dir)
    if arch is not None and rec.arch and rec.arch != arch:
        raise ValueError(f"recipe {recipe_dir!r} was calibrated for arch "
                         f"{rec.arch!r}, serving {arch!r}")
    if reduced is not None and "reduced" in rec.meta \
            and bool(rec.meta["reduced"]) != bool(reduced):
        raise ValueError(f"recipe {recipe_dir!r} was calibrated with "
                         f"reduced={rec.meta['reduced']}, serving "
                         f"reduced={reduced}")
    ck = rec.resolve_ckpt_dir(recipe_dir)
    if ck is not None:
        params, step = ckpt.restore(ck, params)
        print(f"recipe: restored pre-quantized weights (step {step}) — "
              f"no k-means at startup")
    elif rec.policies:
        params, report = quantize_tree(
            jax.random.PRNGKey(0), params,
            QuantPolicy(), overrides=rec.policies)
        print(f"recipe: quantized {len(report['quantized'])} tensors from "
              f"recipe policies ({report['deployed_bytes']/2**20:.1f} MiB)")
    return params, rec, rec.kv_scales


def save_recipe(recipe_dir, cfg, model, params, args) -> None:
    """Offline calibration: quantize weights, measure KV ranges, persist
    QuantRecipe + quantized checkpoint under `recipe_dir`."""
    from repro.calib import QuantRecipe, collect_kv_stats, kv_static_scales
    from repro.checkpoint import ckpt

    policy = QuantPolicy(cfg=QuantConfig(bits=args.bits), method=args.method)
    qparams_tree, report = quantize_tree(jax.random.PRNGKey(0), params,
                                         policy)
    kv_scales = None
    if cfg.family in ("dense", "moe", "vlm"):
        rng = np.random.default_rng(0)
        # long calibration prompts: RoPE'd K ranges are position-dependent,
        # so coverage must extend past the serving prompt lengths
        calib = [rng.integers(0, cfg.vocab, size=(4, 48)) for _ in range(4)]
        kv_scales = kv_static_scales(
            collect_kv_stats(cfg, qparams_tree, calib, qchunks=4))
    os.makedirs(recipe_dir, exist_ok=True)
    ckpt.save(os.path.join(recipe_dir, "ckpt"), 0, qparams_tree)
    rec = QuantRecipe(
        name=f"{cfg.name}-int{args.bits}-{args.method}",
        arch=args.arch,
        policies={p: {"bits": d["bits"], "k": d["k"], "method": d["method"]}
                  for p, d in report["per_path"].items()},
        kv_scales=kv_scales, kv_qchunks=4, ckpt_dir="ckpt",
        meta={"deployed_bytes": report["deployed_bytes"],
              "orig_bytes": report["orig_bytes"], "reduced": args.reduced})
    rec.save(recipe_dir)
    print(f"saved recipe + quantized ckpt to {recipe_dir} "
          f"({report['deployed_bytes']/2**20:.1f} MiB deployed)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--method", default="splitquant",
                    choices=["splitquant", "baseline", "percentile", "none"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--wave", action="store_true",
                    help="use the legacy wave-batching loop")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine slot count / wave max_batch")
    ap.add_argument("--kv-mode", default="fp", choices=["fp", "int8"],
                    help="engine KV cache storage (int8 = SplitQuant §4.2 "
                         "chunked-range quantization of K/V at rest)")
    ap.add_argument("--fused-attn", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="decode attention reads the slot cache through "
                         "the fused dequant-in-kernel path (Pallas on "
                         "TPU, chunked jnp elsewhere) — no full-precision "
                         "cache copy per step. Default ON; "
                         "--no-fused-attn selects the legacy materialize-"
                         "then-attend oracle")
    ap.add_argument("--prefill-chunk", type=int,
                    default=EngineConfig.prefill_chunk,
                    help="chunked fused prefill: admit at most this many "
                         "prompt tokens per engine step, quantizing K/V "
                         "in-kernel straight into the slot cache (no "
                         "dense fp prefill cache, decode keeps running "
                         "under long prompts). Default ON (engine "
                         "default); 0 = legacy one-shot prefill opt-out")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: a low-bit draft "
                         "proposes up to k greedy tokens per slot per "
                         "step and the target verifies the window in one "
                         "fused pass (output token-identical to "
                         "spec_k=0 greedy). 0 = off")
    ap.add_argument("--draft-recipe", default=None,
                    help="calibration recipe dir the speculative DRAFT "
                         "weights are minted from (with --spec-k; "
                         "without it the target drafts for itself — "
                         "acceptance ~1 but no draft-cost win)")
    ap.add_argument("--max-queue", default="0", metavar="N|auto",
                    help="admission control: bound the submit queue at N "
                         "requests; a submit past the bound triggers "
                         "--overload-policy. 0 = unbounded (legacy). "
                         "'auto' sizes the bound from the measured "
                         "open-loop saturation knee in the repo's "
                         "BENCH_serve.json (2x the p95 queue depth at "
                         "the last SLO-attaining sweep point)")
    ap.add_argument("--overload-policy", default="reject-new",
                    choices=["reject-new", "shed-oldest", "shed-by-class"],
                    help="who loses when the bounded queue is full: the "
                         "incoming request, the oldest queued one, or "
                         "the oldest queued batch-class request "
                         "(falling back to the incoming one)")
    ap.add_argument("--degrade", action="store_true",
                    help="enable the graceful-degradation ladder: under "
                         "sustained backlog the engine first suspends "
                         "speculative decoding (output-identical), then "
                         "defers batch-class admissions, then sheds "
                         "queued work — each rung transition is a "
                         "metrics event (engine_degradation_rung)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="seeded chaos injection, e.g. "
                         "'exception=0.05,nan=0.02,seed=3' (keys: "
                         "exception, nan, slow, slow_s, poison, crash, "
                         "crash_kill, seed, max). Failed steps retry "
                         "after KV rollback; slots that keep failing "
                         "retire as 'failed'; crash=p dies at a step "
                         "boundary (recover with --supervise or "
                         "--recover-from). Post-drain invariants (clean "
                         "retire reasons, no slot-pool leak) are "
                         "asserted. Engine only; incompatible with "
                         "--spec-k")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="durable request journal (DESIGN.md §13): "
                         "append-only JSONL WAL of submit/admit/"
                         "first_token/retire transitions, fsync'd once "
                         "per engine step — the replay source for crash "
                         "recovery. Validates under trace_report "
                         "--validate. Engine only (not --wave)")
    ap.add_argument("--snapshot", default=None, metavar="DIR",
                    help="engine state snapshot directory (atomic "
                         "tmp+rename): quantized slot cache, draft twin, "
                         "scheduler queue + slot table, host decode "
                         "state, with per-array checksums in the "
                         "manifest. Written every --snapshot-every steps")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="with --snapshot: snapshot every N engine steps "
                         "at the end-of-step boundary (after the journal "
                         "fsync). 0 = never automatically")
    ap.add_argument("--recover-from", default=None, metavar="DIR",
                    help="start by restoring this snapshot dir and "
                         "replaying --journal against it (fresh-process "
                         "recovery after a crash): snapshot-live "
                         "requests resume from their quantized KV, "
                         "journal submissions past the snapshot horizon "
                         "re-prefill, already-retired uids are reported "
                         "from the journal and never re-run. The dir "
                         "may be absent (crash before the first "
                         "snapshot) if --journal is given")
    ap.add_argument("--supervise", type=int, default=0, metavar="N",
                    help="in-process supervisor: on an injected crash "
                         "(--faults crash=p), rebuild the engine, "
                         "recover from --snapshot/--journal and keep "
                         "serving, up to N restarts. Restarted engines "
                         "run with the crash injector disarmed (the "
                         "same seed would deterministically re-crash at "
                         "the same boundary)")
    ap.add_argument("--verify-recovery", action="store_true",
                    help="after serving, re-run the same workload on an "
                         "uncrashed reference engine and assert every "
                         "normally-finished request's tokens are "
                         "identical — the zero-divergence recovery "
                         "proof (exits nonzero on mismatch)")
    ap.add_argument("--drain-timeout", type=float, default=None,
                    metavar="S",
                    help="drain watchdog: force-fail all outstanding "
                         "requests after this many wall seconds (None = "
                         "no wall limit; the no-progress watchdog still "
                         "applies)")
    ap.add_argument("--drain-stall-steps", type=int, default=10_000,
                    metavar="N",
                    help="drain watchdog: force-fail outstanding "
                         "requests after N consecutive engine steps "
                         "with no progress (tokens, admissions, prefill "
                         "chunks, or retires)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable engine tracing (repro.obs) and write the "
                         "JSONL event log here: per-request lifecycle "
                         "events + per-step phase spans with dispatch-vs-"
                         "device-wait attribution. Inspect with "
                         "launch/trace_report.py. Engine only (not "
                         "--wave); a profiling mode — adds sync points")
    ap.add_argument("--trace-chrome", default=None, metavar="PATH",
                    help="with --trace: also export a Chrome/Perfetto "
                         "trace.json (one track per slot, one per engine "
                         "phase) to this path")
    ap.add_argument("--trace-kv-every", type=int, default=0,
                    metavar="N",
                    help="with --trace and --kv-mode int8: sample KV "
                         "quantization-quality counters (clip fraction, "
                         "occupancy, outlier-chunk histogram) every N "
                         "engine steps into the trace. 0 = off")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the full Engine.metrics() dict as JSON "
                         "with the shared provenance header "
                         "(machine-checkable soak runs; includes the "
                         "always-on registry snapshot, and the "
                         "phase_attribution section when --trace is on). "
                         "Engine only (not --wave)")
    ap.add_argument("--metrics-snapshot", default=None, metavar="PATH",
                    help="stream periodic JSONL snapshots of the "
                         "always-on metrics registry (queue depth, admit "
                         "latency, slot occupancy, prefill backlog, "
                         "tokens in flight, spec acceptance EWMA) to "
                         "this path while serving; line 1 is the shared "
                         "provenance header. Engine only (not --wave)")
    ap.add_argument("--metrics-interval", type=float, default=1.0,
                    metavar="S",
                    help="with --metrics-snapshot: minimum seconds "
                         "between snapshots (a final flush always "
                         "happens at drain)")
    ap.add_argument("--metrics-prom", default=None, metavar="PATH",
                    help="write the final registry state in Prometheus "
                         "text exposition format at exit (the scrape "
                         "surface, minus the HTTP listener). Engine "
                         "only (not --wave)")
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable the always-on metrics registry (the "
                         "overhead-measurement configuration; metrics "
                         "are otherwise cheap enough to never turn off)")
    ap.add_argument("--incident-dir", default=None, metavar="DIR",
                    help="arm the anomaly-detector sweep and write "
                         "incident bundles (flight window + metrics + "
                         "journal tail + fingerprint + request docs) "
                         "under DIR on trigger; inspect with "
                         "repro.launch.incident_report")
    ap.add_argument("--incident-cooldown", type=int, default=50,
                    metavar="N",
                    help="steps between detector refires / bundles "
                         "(default 50) — a fault storm yields one "
                         "incident, not one per step")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained weights before quantizing")
    ap.add_argument("--recipe", default=None,
                    help="serve from a saved calibration recipe dir: "
                         "pre-quantized weights + static KV scales")
    ap.add_argument("--save-recipe", default=None,
                    help="run offline calibration, write recipe + "
                         "quantized ckpt to this dir, and exit")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)
    if args.ckpt_dir:
        from repro.checkpoint import ckpt
        (params, _), step = ckpt.restore(args.ckpt_dir, (params, None))
        print(f"restored step {step}")

    if args.save_recipe:
        save_recipe(args.save_recipe, cfg, model, params, args)
        return

    kv_scales = None
    kv_qchunks = 4
    if args.recipe:
        params, rec, kv_scales = load_recipe_params(
            args.recipe, params, arch=args.arch, reduced=args.reduced)
        kv_qchunks = rec.kv_qchunks        # scales are (L, Hkv, kv_qchunks)
        if kv_scales is not None and args.kv_mode != "int8":
            kv_scales = None               # static scales only apply to int8
    elif args.method != "none":
        policy = QuantPolicy(cfg=QuantConfig(bits=args.bits),
                             method=args.method)
        params, report = quantize_tree(key, params, policy)
        print(f"quantized {len(report['quantized'])} tensors to "
              f"INT{args.bits} ({args.method}); deployed "
              f"{report['deployed_bytes']/2**20:.1f} MiB vs fp32 "
              f"{report['orig_bytes']/2**20:.1f} MiB")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(4, 12))
               for _ in range(args.requests)]

    from repro.engine.engine import ENGINE_FAMILIES
    if args.draft_recipe and not args.spec_k:
        raise ValueError(
            "--draft-recipe only takes effect with --spec-k > 0 — the "
            "recipe would be silently ignored and serving would proceed "
            "plain-greedy")
    if args.spec_k and args.wave:
        # loud, mirroring the family check below: the wave loop has no
        # speculative path, and silently dropping spec_k would let an
        # operator believe they measured speculative serving
        raise NotImplementedError(
            "--wave has no speculative path (spec_k > 0 is an engine "
            "feature) — drop --wave or --spec-k")
    if args.spec_k and cfg.family not in ENGINE_FAMILIES:
        # loud, not a silent wave fallback: the caller asked for
        # speculative decoding and these families cannot provide the
        # positional rollback it needs — surface the family's own reason
        from repro.models import get_model as _gm
        vf = getattr(_gm(cfg), "verify_step_slots", None)
        if vf is None:
            raise NotImplementedError(
                f"--spec-k: the {cfg.family!r} family has no speculative "
                f"verify path")
        vf()
    if (args.trace_chrome or args.trace_kv_every) and not args.trace:
        raise ValueError(
            "--trace-chrome / --trace-kv-every require --trace — without "
            "it no trace is recorded and the flags would be silently "
            "ignored")
    if not args.wave and cfg.family not in ENGINE_FAMILIES:
        print(f"note: {cfg.family!r} family has no slot-cache layout yet; "
              f"serving with the wave loop")
        args.wave = True
    if args.no_metrics and (args.metrics_snapshot or args.metrics_prom):
        raise ValueError(
            "--no-metrics disables the registry the "
            "--metrics-snapshot/--metrics-prom exporters read — drop "
            "one side")
    if args.wave and (args.trace or args.metrics_json
                      or args.metrics_snapshot or args.metrics_prom):
        # loud, mirroring the spec_k check above: the wave loop has no
        # tracer, registry, or metrics dict, and silently dropping the
        # flags would let an operator believe they captured a trace
        raise NotImplementedError(
            "--trace/--metrics-json/--metrics-snapshot/--metrics-prom "
            "are engine features — the wave loop has no tracer, "
            "registry, or metrics() snapshot; drop --wave")
    if args.wave and (args.faults or args.degrade
                      or args.max_queue not in ("0", 0)):
        raise NotImplementedError(
            "--faults/--degrade/--max-queue are engine features — the "
            "wave loop has no retry, ladder, or admission control; "
            "drop --wave")
    if args.wave and (args.journal or args.snapshot or args.recover_from
                      or args.supervise or args.verify_recovery):
        raise NotImplementedError(
            "--journal/--snapshot/--recover-from/--supervise/"
            "--verify-recovery are engine features — the wave loop has "
            "no journal, snapshot, or recovery path; drop --wave")
    if args.wave and args.incident_dir:
        raise NotImplementedError(
            "--incident-dir is an engine feature — the wave loop has no "
            "flight recorder or anomaly detectors; drop --wave")
    if args.snapshot_every and not args.snapshot:
        raise ValueError(
            "--snapshot-every without --snapshot DIR has nowhere to "
            "write — give a snapshot directory or drop the interval")
    if args.supervise and not (args.journal or args.snapshot):
        raise ValueError(
            "--supervise has nothing to recover from — give --journal "
            "and/or --snapshot (journal-only recovery re-prefills "
            "everything; snapshots make restarts cheap)")
    if args.recover_from and not os.path.isdir(args.recover_from) \
            and not args.journal:
        raise ValueError(
            f"--recover-from: {args.recover_from!r} does not exist and "
            f"no --journal was given — there is no state to recover")
    if args.max_queue == "auto":
        # size the bound from the committed open-loop knee: the p95
        # queue depth at the last sweep point that still attained its
        # SLO is the deepest backlog this box has been MEASURED to
        # absorb — 2x that is the admission set point (DESIGN.md §12).
        # Every failure here is loud: 'auto' with no measurement would
        # silently serve unbounded, which is the opposite of what the
        # operator asked for
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "..", "..")
        bench = os.path.abspath(os.path.join(root, "BENCH_serve.json"))
        regen = ("PYTHONPATH=src python benchmarks/serve_bench.py "
                 "--requests 12")
        import json as _json
        try:
            with open(bench) as f:
                data = _json.load(f)
        except FileNotFoundError:
            raise SystemExit(
                f"--max-queue auto: {bench} not found — the admission "
                f"bound is sized from the measured open-loop saturation "
                f"knee; run the serving benchmark once to produce it:\n"
                f"  {regen}")
        except ValueError as e:
            raise SystemExit(
                f"--max-queue auto: {bench} is not valid JSON ({e}) — "
                f"regenerate it:\n  {regen}")
        if "open_loop" not in data:
            raise SystemExit(
                f"--max-queue auto: {bench} has no 'open_loop' section "
                f"(it predates the open-loop SLO sweep) — regenerate "
                f"it:\n  {regen}")
        max_queue = admission_set_point(data["open_loop"]) or 0
        print(f"admission: --max-queue auto -> "
              f"{max_queue or 'unbounded (no measured knee)'} "
              f"(from {bench})")
    else:
        max_queue = int(args.max_queue)
    if args.wave:
        srv = Server(cfg, params, ServeConfig(
            max_batch=args.slots, max_new_tokens=args.max_new_tokens,
            max_len=256))
        out = srv.serve([Request(i, p) for i, p in enumerate(prompts)])
        for r in out:
            print(f"req {r.uid}: {len(r.out)} tokens -> {r.out[:12]}")
        return

    base_faults = FaultSpec.parse(args.faults) if args.faults else None

    def mk_engine(registry=None, resume=False, faults=base_faults):
        # rebuildable so the supervisor can replace a crashed engine
        # in-process; `registry` carries metric counters across restarts
        # (restore/replay counts must survive into --metrics-prom)
        return Engine(cfg, params, EngineConfig(
            n_slots=args.slots, max_len=256,
            max_new_tokens=args.max_new_tokens, kv_mode=args.kv_mode,
            kv_qchunks=kv_qchunks, fused_attn=args.fused_attn,
            prefill_chunk=args.prefill_chunk, spec_k=args.spec_k,
            draft_recipe=args.draft_recipe, metrics=not args.no_metrics,
            trace=bool(args.trace), trace_kv_every=args.trace_kv_every,
            max_queue=max_queue, overload_policy=args.overload_policy,
            degrade=args.degrade, fault_spec=faults,
            journal_path=args.journal, journal_resume=resume,
            snapshot_path=args.snapshot,
            snapshot_every=args.snapshot_every,
            incident_dir=args.incident_dir,
            incident_cooldown=args.incident_cooldown),
            kv_scales=kv_scales, registry=registry)

    # --recover-from is a fresh-process restart: the journal already
    # holds this workload's submit records, so the WAL is appended to
    # (resume) rather than truncated
    eng = mk_engine(resume=args.recover_from is not None)
    writer = None
    if args.metrics_snapshot:
        from repro.kernels import act_quant
        from repro.obs import RegistryQuantProbe, SnapshotWriter
        writer = SnapshotWriter(args.metrics_snapshot, eng.registry,
                                interval_s=args.metrics_interval)
        # live act-quant clip-fraction gauges: the observed kernel
        # wrappers feed the registry through the existing probe hook
        act_quant.set_quality_probe(RegistryQuantProbe(eng.registry))
    recovered = {}              # uid -> journal retire record (pre-crash)
    if args.recover_from is not None:
        info = eng.recover(args.recover_from, args.journal)
        recovered.update(info["retired"])
        print(f"recover: {info['n_restored']} live requests restored"
              f"{' from snapshot' if info['manifest'] else ' (no snapshot)'}"
              f", {info['n_requeued']} re-enqueued from the journal, "
              f"{len(info['retired'])} already retired pre-crash")
    else:
        for p in prompts:
            eng.submit(p)

    def run_to_drain(eng):
        if writer is None:
            return eng.drain(timeout_s=args.drain_timeout,
                             stall_steps=args.drain_stall_steps)
        # step manually so snapshots land DURING the run (the point of
        # an open-ended soak), not just at drain
        while not eng.sched.idle:
            eng.step()
            writer.maybe_write()
        writer.write()                            # final flush
        return sorted(eng.sched.finished, key=lambda r: r.uid)

    restarts = 0
    while True:
        try:
            fin = run_to_drain(eng)
            break
        except InjectedCrash as exc:
            if restarts >= args.supervise:
                raise
            restarts += 1
            print(f"supervisor: engine crashed ({exc}) — restart "
                  f"{restarts}/{args.supervise}, recovering from "
                  f"{'snapshot+journal' if args.snapshot else 'journal'}",
                  flush=True)
            if args.incident_dir:
                # capture from the CRASHED engine, whose flight window
                # and scheduler state describe the death — the rebuilt
                # engine starts with an empty ring
                eng.dump_incident("injected_crash", reason=str(exc))
            # crash injector disarmed on restart: a fresh injector with
            # the same seed would re-crash at the same step boundary,
            # turning every supervised run into a restart-budget exhaust
            import dataclasses as _dc
            calm = _dc.replace(base_faults, crash_rate=0.0) \
                if base_faults else None
            eng = mk_engine(registry=eng.registry, resume=True,
                            faults=calm)
            info = eng.recover(args.snapshot, args.journal)
            recovered.update(info["retired"])
    for uid in sorted(recovered):
        rec = recovered[uid]
        print(f"req {uid}: {rec['n_out']} tokens ({rec['reason']}) "
              f"-> {rec['out'][:12]}  (retired pre-crash, from journal)")
    for r in fin:
        # shed/failed/expired requests never produced a first token, so
        # ttft/tokens_per_s are None — a chaos run must not crash the
        # report loop that summarizes it
        ttft = "n/a" if r.ttft is None else f"{r.ttft*1e3:.0f} ms"
        tps = "n/a" if r.tokens_per_s is None \
            else f"{r.tokens_per_s:.1f} tok/s"
        print(f"req {r.uid}: {len(r.out)} tokens ({r.finish_reason}) "
              f"-> {r.out[:12]}  (ttft {ttft}, {tps})")
    m = eng.metrics()
    if args.faults:
        # chaos invariants (DESIGN.md §12): every submitted request
        # retired exactly once with a schema reason, and the drained
        # engine holds no residual state — a fault injector that leaks
        # slots or finish states would silently poison later admissions
        from repro.obs.schema import RETIRE_REASONS
        reasons = sorted([r.finish_reason for r in eng.sched.finished]
                         + [rec["reason"] for rec in recovered.values()])
        bad = [x for x in reasons if x not in RETIRE_REASONS]
        eng.sweep_idle_rows()       # idempotent; the manual-step path
        leak = occupied_slots(eng.cache)  # (snapshot writer) skips drain
        problems = []
        # exactly-once across incarnations: live finishes and journal-
        # replayed retires must partition the workload, never overlap
        live_uids = {r.uid for r in eng.sched.finished}
        twice = sorted(live_uids & set(recovered))
        if twice:
            problems.append(f"uids retired twice (live + journal): "
                            f"{twice}")
        if len(live_uids | set(recovered)) != len(prompts):
            problems.append(f"{len(live_uids | set(recovered))} retired "
                            f"!= {len(prompts)} submitted")
        if bad:
            problems.append(f"non-schema retire reasons {bad}")
        if any(eng.sched.slots) or eng.sched.queue:
            problems.append("scheduler not empty after drain")
        if leak:
            problems.append(f"slot-pool leak: cache rows {leak} still "
                            f"occupied")
        print(f"chaos  : injected {m.get('faults_injected')}, "
              f"{m['step_retries']} step retries, retire reasons "
              f"{m['retire_reasons']}")
        if problems:
            raise SystemExit("chaos invariants VIOLATED: "
                             + "; ".join(problems))
    if args.verify_recovery:
        # zero-divergence proof (DESIGN.md §13): greedy decode is a
        # pure function of (weights, prompt), so every request that
        # finished normally — pre-crash from the journal, resumed from
        # a snapshot, or re-prefilled after replay — must be token-
        # identical to a run that never crashed
        normal = ("eos", "budget", "max_len", "zero_budget")
        ref = Engine(cfg, params, EngineConfig(
            n_slots=args.slots, max_len=256,
            max_new_tokens=args.max_new_tokens, kv_mode=args.kv_mode,
            kv_qchunks=kv_qchunks, fused_attn=args.fused_attn,
            prefill_chunk=args.prefill_chunk, spec_k=args.spec_k,
            draft_recipe=args.draft_recipe, metrics=False),
            kv_scales=kv_scales)
        for p in prompts:
            ref.submit(p)
        ref_out = {r.uid: list(r.out) for r in ref.drain()}
        got = {uid: (list(rec["out"]), rec["reason"])
               for uid, rec in recovered.items()}
        got.update({r.uid: (list(r.out), r.finish_reason) for r in fin})
        survivors = sorted(u for u, (_, why) in got.items()
                           if why in normal)
        diverged = [u for u in survivors if got[u][0] != ref_out.get(u)]
        if diverged:
            raise SystemExit(
                f"recovery verification FAILED: requests {diverged} "
                f"diverged from the uncrashed reference run")
        excl = len(got) - len(survivors)
        print(f"recover: {len(survivors)} surviving requests verified "
              f"token-identical to an uncrashed reference run"
              + (f" ({excl} shed/failed/expired excluded)" if excl
                 else ""))
    print(f"engine: {m['tokens_per_s']:.1f} tok/s, "
          f"util {m['slot_utilization']:.0%}, kv={m['kv_mode']}"
          f"{'/static' if m['kv_static_scales'] else ''} "
          f"({m['kv_bytes_per_token']:.0f} B/token/layer)")
    if args.spec_k:
        rate = m["acceptance_rate"]
        print(f"spec   : k={m['spec_k']}, acceptance "
              f"{'n/a' if rate is None else f'{rate:.1%}'}, "
              f"{m['draft_accepted']}/{m['draft_proposed']} drafts "
              f"accepted over {m['verify_calls']} verifies "
              f"({m['tokens_per_verify_mean'] or 0:.2f} tokens/verify)")
    if args.trace:
        n = eng.tracer.to_jsonl(args.trace)
        print(f"trace  : {n} records -> {args.trace} "
              f"({eng.tracer.dropped} dropped); inspect with "
              f"python -m repro.launch.trace_report {args.trace}")
        if args.trace_chrome:
            eng.tracer.to_chrome(args.trace_chrome)
            print(f"trace  : chrome/perfetto -> {args.trace_chrome}")
        pa = m["phase_attribution"]
        if pa["coverage"] is not None:
            print(f"trace  : phase coverage {pa['coverage']:.0%} of "
                  f"step wall; dispatch {pa['dispatch_frac']:.0%} / "
                  f"device wait {pa['device_wait_frac']:.0%} of "
                  f"attributed time")
    if args.incident_dir:
        # count on disk, not eng.incidents: supervised restarts replace
        # the engine object but the bundles persist
        bundles = sorted(
            d for d in (os.listdir(args.incident_dir)
                        if os.path.isdir(args.incident_dir) else [])
            if d.startswith("incident-"))
        print(f"incidents: {len(bundles)} bundle(s) -> "
              f"{args.incident_dir}"
              + (f"; inspect with python -m repro.launch.incident_report "
                 f"{os.path.join(args.incident_dir, bundles[0])}"
                 if bundles else " (no anomalies)"))
    if args.metrics_snapshot:
        print(f"metrics: {writer.seq} snapshots -> "
              f"{args.metrics_snapshot}")
    if args.metrics_prom:
        with open(args.metrics_prom, "w") as f:
            f.write(eng.registry.to_prometheus())
        print(f"metrics: prometheus text -> {args.metrics_prom}")
    if args.metrics_json:
        import json

        from repro.obs import provenance

        # the same provenance header every BENCH_*.json carries — a
        # metrics dump without it is uninterpretable once copied off-box
        with open(args.metrics_json, "w") as f:
            json.dump({"provenance": provenance(), **m}, f, indent=2,
                      default=float)
        print(f"metrics: -> {args.metrics_json}")


if __name__ == "__main__":
    main()
