"""Serving throughput: continuous-batching engine vs wave-synchronous
server on a mixed-length request workload.

The workload is adversarial for wave batching: most requests want a few
tokens, a minority want many. In a wave, every batch slot is held until
the wave's longest member finishes; the engine retires and refills slots
per step, so the long tail no longer stalls short requests.

The INT8 cache is additionally served two ways: the legacy
materialize-then-attend read (dequantize the whole slot cache per decode
step; now behind `fused_attn=False` — the engine default flipped to
fused) and the fused dequant-in-kernel read
(`repro.kernels.decode_attention`) — the fused-vs-materialized delta and
per-decode-step latency percentiles are tracked per PR. `--max-len`
defaults to 512 so the cache is deep enough for the read path to
dominate the step.

A mixed prefill+decode SOAK config additionally serves a long-prompt
workload two ways: legacy ONE-SHOT prefill (every admission blocks the
step for a whole prompt's prefill — the stall baseline) vs CHUNKED fused
prefill (`prefill_chunk` tokens per step, quantize-in-kernel slot
writes, `kernels/prefill_attention.py`). It reports TTFT p50/p95, the
p95 of full-step latency among steps that did prefill work (decode-step
latency under concurrent prefill — the admission-stall metric), and
chunked-vs-one-shot tokens/s + greedy agreement.

    PYTHONPATH=src python benchmarks/serve_bench.py --requests 24

Emits BENCH_serve.json next to this file (tokens/s, per-step p50/p95,
TTFT, speedups, soak percentiles, and greedy token agreement across
every pair of paths) so the perf trajectory accumulates.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from loadgen import (CLASSES, find_knee, make_open_loop_workload,  # noqa: E402
                     request_slo, slo_summary)
from run import provenance  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.engine import (Engine, EngineConfig,  # noqa: E402
                          admission_set_point)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.obs import token_agreement  # noqa: E402
from repro.runtime.serve_loop import Request, ServeConfig, Server  # noqa: E402


def make_workload(rng, n_requests, vocab, long_every=6,
                  short_tokens=16, long_tokens=96):
    """Mixed lengths: mostly short prompts/generations, every `long_every`-th
    request is a long one (the wave-stalling tail). Generation lengths are
    sized so decode dominates the wall at max_len 512 — the serving regime
    the fused cache read targets (admissions amortize over ~30 steps)."""
    reqs = []
    for i in range(n_requests):
        is_long = (i % long_every) == long_every - 1
        plen = int(rng.integers(24, 48)) if is_long else int(rng.integers(4, 12))
        budget = long_tokens if is_long else short_tokens
        reqs.append((rng.integers(0, vocab, size=plen), budget))
    return reqs


def make_soak_workload(rng, n_requests, vocab, long_prompt=(144, 208),
                       short_prompt=(4, 10), long_tokens=16,
                       short_tokens=48):
    """Concurrent prefill+decode stress: every other request carries a
    LONG prompt (one-shot prefill of it stalls the whole step for the
    prompt length) while the short requests in neighboring slots are
    mid-generation — the regime chunked prefill targets. Queue depth is
    kept above the slot count so admissions keep happening while slots
    decode."""
    reqs = []
    for i in range(n_requests):
        if i % 2:
            plen = int(rng.integers(*long_prompt))
            budget = long_tokens
        else:
            plen = int(rng.integers(*short_prompt))
            budget = short_tokens
        reqs.append((rng.integers(0, vocab, size=plen), budget))
    return reqs


def run_soak(cfg, params, workload, max_len, slots, prefill_chunk,
             repeats=1):
    """One soak config: INT8 cache, fused decode (the engine defaults),
    one-shot (prefill_chunk=0) or chunked prefill."""
    ecfg = EngineConfig(n_slots=slots, max_len=max_len, kv_mode="int8",
                        prefill_bucket=16, prefill_chunk=prefill_chunk)
    return run_engine(cfg, params, workload, ecfg, repeats)


def run_wave(srv, workload, repeats=1):
    """Best-of-`repeats`, same treatment as `run_engine` — comparing a
    best-of-N engine against a single wave sample would bias the tracked
    speedup upward on a noisy box. `srv` is constructed ONCE by the
    caller: `Server.__init__` jits its decode per instance, so a fresh
    Server per repeat would put XLA compile time inside every wave wall
    while the engine repeats hit the process-wide jit cache."""
    best = None
    for _ in range(repeats):
        reqs = [Request(i, p.copy(), max_new_tokens=b)
                for i, (p, b) in enumerate(workload)]
        t0 = time.perf_counter()
        out = srv.serve(reqs)
        wall = time.perf_counter() - t0
        total = sum(len(r.out) for r in out)
        m = {"wall_s": wall, "total_tokens": total,
             "tokens_per_s": total / wall}
        if best is None or m["tokens_per_s"] > best[1]["tokens_per_s"]:
            best = (out, m)
    return best


def run_engine(cfg, params, workload, ecfg, repeats=1):
    """Best-of-`repeats` run (greedy decoding: outputs are identical
    across repeats, so the fastest run is the steady-state measurement —
    sub-second walls on a shared box otherwise measure scheduler noise)."""
    best = None
    for _ in range(repeats):
        eng = Engine(cfg, params, ecfg)
        for p, b in workload:
            eng.submit(p, max_new_tokens=b)
        t0 = time.perf_counter()
        fin = eng.drain()
        wall = time.perf_counter() - t0
        m = eng.metrics()
        m["wall_s"] = wall
        m["tokens_per_s"] = m["total_tokens"] / wall
        if best is None or m["tokens_per_s"] > best[1]["tokens_per_s"]:
            best = (fin, m)
    return best


def run_open_loop(cfg, params, arrivals, ecfg):
    """One open-loop point: submit each request at its SCHEDULED wall
    time while the engine steps regardless — the submission rate is an
    independent variable, unlike the closed-loop runs above where it
    implicitly tracks the service rate. Arrivals may carry robustness
    fields (loadgen §12 options): per-request deadlines pass through to
    ``Engine.submit`` and scheduled client cancellations fire at their
    wall times via ``Engine.cancel``. Returns (slo_summary, metrics);
    SLO judging covers every request that the engine FINISHED for any
    reason — shed / cancelled / expired requests simply never attain
    (they produced no timely tokens), which is exactly how an external
    client would score them.
    """
    eng = Engine(cfg, params, ecfg)
    by_uid = {}
    cancels = []                               # (cancel_t, uid), sorted
    i, n = 0, len(arrivals)
    t0 = time.perf_counter()
    while i < n or not eng.sched.idle:
        now = time.perf_counter() - t0
        while i < n and arrivals[i].t <= now:
            a = arrivals[i]
            uid = eng.submit(a.prompt, max_new_tokens=a.max_new_tokens,
                             cls=a.cls, ttft_deadline_s=a.ttft_deadline_s,
                             deadline_s=a.deadline_s)
            by_uid[uid] = a
            if a.cancel_t is not None:
                cancels.append((a.cancel_t, uid))
            # backdate to the SCHEDULED arrival: when the engine was busy
            # stepping past this arrival's time, the request has already
            # been "waiting" since then — charging the queue from the
            # submit call instead would hide exactly the queueing delay
            # the open-loop method exists to measure. Look the request up
            # by uid: under a bounded queue an overload victim is
            # finished (shed) during submit, so it may live in
            # `finished`, or — shed-oldest/by-class — not be queue[-1].
            req = next((r for r in reversed(eng.sched.queue)
                        if r.uid == uid), None) \
                or next(r for r in reversed(eng.sched.finished)
                        if r.uid == uid)
            req.t_submit = t0 + a.t
            i += 1
        while cancels and cancels[0][0] <= now:
            eng.cancel(cancels.pop(0)[1])
        if eng.sched.idle:
            # nothing in flight: sleep toward the next event — arrival
            # or scheduled cancel — (capped so late-running generations
            # never oversleep a burst). i < n is guaranteed here (else
            # the loop condition would have exited).
            nxt = arrivals[i].t
            if cancels:
                nxt = min(nxt, cancels[0][0])
            time.sleep(min(max(nxt - now, 0.0), 0.02))
            continue
        eng.step()
    wall = time.perf_counter() - t0
    fin = sorted(eng.sched.finished, key=lambda r: r.uid)
    judged = [request_slo(by_uid[r.uid], r) for r in fin]
    m = eng.metrics()
    return slo_summary(judged, wall), m


def run_recovery_bench(cfg, params, vocab, n, seed, slots, max_len):
    """Seeded crash/recovery measurement (DESIGN.md §13): replay a
    loadgen schedule whose appended ``crash_t`` draws pick the crash
    moment, serve it with the journal + periodic snapshots armed, "die"
    at the first step boundary past the scheduled crash time (stop
    stepping — the durable state is exactly what a SIGKILL would leave),
    then recover in a fresh engine and drain. Reports restore latency,
    how much work survived in the snapshot vs re-prefilled from the
    journal, and token identity of the combined outputs against an
    uncrashed reference run of the same schedule."""
    import shutil
    import tempfile
    sched = make_open_loop_workload(seed, n, vocab, float("inf"),
                                    crash_rate=1.0)
    crash_t = min(a.crash_t for a in sched)
    ecfg = EngineConfig(n_slots=slots, max_len=max_len, kv_mode="int8",
                        prefill_bucket=16)

    def submit_all(eng):
        for a in sched:
            eng.submit(a.prompt, max_new_tokens=a.max_new_tokens,
                       cls=a.cls)

    ref = Engine(cfg, params, ecfg)
    submit_all(ref)
    ref_out = {r.uid: list(r.out) for r in ref.drain()}

    tmp = tempfile.mkdtemp(prefix="recovery_bench_")
    jpath = os.path.join(tmp, "journal.jsonl")
    spath = os.path.join(tmp, "snap")
    try:
        wcfg = EngineConfig(**{**ecfg.__dict__, "journal_path": jpath,
                               "snapshot_path": spath,
                               "snapshot_every": 2})
        eng = Engine(cfg, params, wcfg)
        submit_all(eng)
        t0 = time.perf_counter()
        crashed_at_step = None
        while not eng.sched.idle:
            eng.step()
            if time.perf_counter() - t0 >= crash_t:
                crashed_at_step = len(eng.step_s)
                break
        eng2 = Engine(cfg, params, EngineConfig(
            **{**wcfg.__dict__, "journal_resume": True}))
        t1 = time.perf_counter()
        info = eng2.recover(spath, jpath)
        restore_s = time.perf_counter() - t1
        fin = {r.uid: list(r.out) for r in eng2.drain()}
        combined = {uid: list(rec["out"])
                    for uid, rec in info["retired"].items()}
        combined.update(fin)
        return {
            "requests": n,
            "seed": seed,
            "crash_t_s": crash_t,
            "crashed_at_step": crashed_at_step,
            "snapshot_every": 2,
            "restore_duration_s": restore_s,
            "n_restored_from_snapshot": info["n_restored"],
            "n_requeued_from_journal": info["n_requeued"],
            "n_retired_pre_crash": len(info["retired"]),
            "token_identical_vs_uncrashed":
                sorted(combined) == sorted(ref_out)
                and all(combined[u] == ref_out[u] for u in ref_out),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of-N runs per engine config")
    ap.add_argument("--soak-requests", type=int, default=10,
                    help="requests in the mixed prefill+decode soak "
                         "(0 disables the soak)")
    ap.add_argument("--soak-prefill-chunk", type=int, default=96,
                    help="prompt-token budget per step for the chunked "
                         "soak config (too-small budgets pay a dispatch "
                         "per bucket-rounded chunk and under-fill the "
                         "whole-chunk-or-nothing budget; ~4x the "
                         "prefill_bucket is the sweet spot on the CI box)")
    ap.add_argument("--open-loop-requests", type=int, default=24,
                    help="requests per open-loop sweep point (0 disables "
                         "the open-loop SLO section)")
    ap.add_argument("--open-loop-rates", default="1,2,4,8,inf",
                    help="comma-separated base Poisson rates (req/s) to "
                         "sweep; 'inf' is the all-at-once closed-loop "
                         "limit that guarantees a measured saturation "
                         "knee even when the finite rates all keep up")
    ap.add_argument("--open-loop-seed", type=int, default=7,
                    help="loadgen seed — same seed reproduces the exact "
                         "arrival schedule, class draws, and prompts")
    ap.add_argument("--slo-threshold", type=float, default=0.9,
                    help="attainment level defining the saturation knee")
    ap.add_argument("--recovery-requests", type=int, default=8,
                    help="requests in the seeded crash/recovery "
                         "measurement (restore latency, survivor "
                         "counts, token identity vs an uncrashed "
                         "reference; 0 disables the section)")
    ap.add_argument("--recovery-seed", type=int, default=13,
                    help="loadgen seed for the crash schedule — same "
                         "seed reproduces the arrivals AND the "
                         "appended crash_t draws")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: caps requests/repeats/soak so the "
                         "bench (including the tracing-overhead section) "
                         "finishes in minutes — for the trace smoke job, "
                         "not for tracked numbers")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 8)
        args.repeats = 1
        args.soak_requests = min(args.soak_requests, 4)
        args.max_len = min(args.max_len, 256)
        args.open_loop_requests = min(args.open_loop_requests, 8)
        args.open_loop_rates = "2,inf"
        args.recovery_requests = min(args.recovery_requests, 6)

    cfg = get_arch(args.arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    workload = make_workload(rng, args.requests, cfg.vocab)
    n_long = sum(1 for _, b in workload if b > 16)
    print(f"workload: {len(workload)} requests ({n_long} long-tail), "
          f"{args.slots} slots")

    scfg = ServeConfig(max_batch=args.slots, max_new_tokens=64,
                       max_len=args.max_len)
    # prefill_chunk pinned to 0 (one-shot): the engine default flipped to
    # chunked, but this bench's tracked engine-vs-wave and fused-vs-
    # materialized numbers are decode-path comparisons whose prefill
    # treatment must stay fixed across PRs — the soak below measures the
    # chunked-vs-oneshot delta explicitly
    ecfg = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                        prefill_bucket=16, prefill_chunk=0)

    # fused_attn defaults ON now — the materialized read is the explicit
    # oracle config, the fused one is the engine default
    ecfg8 = EngineConfig(**{**ecfg.__dict__, "kv_mode": "int8",
                            "fused_attn": False})
    ecfg8f = EngineConfig(**{**ecfg8.__dict__, "fused_attn": True})

    # warm the (process-shared) jit caches on a throwaway pass so wall
    # times compare steady state, not compilation. One representative per
    # PREFILL BUCKET shape (the engine's own bucketing): a warmup that
    # misses a bucket leaves an XLA compile inside somebody's measured
    # wall time.
    from repro.engine.engine import bucket_len
    reps = {}
    for p, b in workload:
        reps.setdefault(bucket_len(len(p), ecfg.prefill_bucket,
                                   args.max_len), (p, 8))
    warm = list(reps.values())
    srv = Server(cfg, params, scfg)
    run_wave(srv, warm)
    for w in (ecfg, ecfg8, ecfg8f):
        run_engine(cfg, params, warm, w)

    wave_out, wave = run_wave(srv, workload, args.repeats)
    eng_out, eng = run_engine(cfg, params, workload, ecfg, args.repeats)
    eng8_out, eng8 = run_engine(cfg, params, workload, ecfg8, args.repeats)
    eng8f_out, eng8f = run_engine(cfg, params, workload, ecfg8f,
                                  args.repeats)

    # greedy-token agreement checks (shared helper: repro.obs.summary)
    agreement = token_agreement
    agree_engine_wave = agreement(eng_out, wave_out)
    agree_int8_fp = agreement(eng8_out, eng_out)
    agree_fused = agreement(eng8f_out, eng8_out)

    # ---- tracing: disabled-mode overhead vs run-to-run noise, and the
    # traced phase attribution. Tracing defaults OFF and must cost only a
    # branch — quantified by re-running the default-config (untraced)
    # measurement and comparing the delta (= the box's noise floor)
    # against the already-measured eng8f run of the same config.
    _, eng8f_rerun = run_engine(cfg, params, workload, ecfg8f,
                                args.repeats)
    a, b = eng8f["tokens_per_s"], eng8f_rerun["tokens_per_s"]
    noise_frac = abs(a - b) / max(a, b)
    traced_cfg = EngineConfig(**{**ecfg8f.__dict__, "trace": True})
    run_engine(cfg, params, warm, traced_cfg)           # warm traced path
    _, traced = run_engine(cfg, params, workload, traced_cfg,
                           args.repeats)
    pa = traced["phase_attribution"]
    trace = {
        "untraced_tokens_per_s": a,
        "untraced_rerun_tokens_per_s": b,
        "noise_frac": noise_frac,
        "traced_tokens_per_s": traced["tokens_per_s"],
        # enabled-mode cost (sync points + record pushes) — a PROFILING
        # mode number, reported, not asserted
        "traced_overhead_frac": 1.0 - traced["tokens_per_s"] / max(a, b),
        "coverage": pa["coverage"],
        "dispatch_frac": pa["dispatch_frac"],
        "device_wait_frac": pa["device_wait_frac"],
        "phase_attribution": pa,
    }
    # disabled-mode overhead must be noise: the two untraced runs are
    # the same binary + config, so any systematic gap IS measurement
    # noise — a generous 1.5x bound catches only real regressions
    # (e.g. instrumentation accidentally on the hot path) without
    # flaking on a busy CI box
    assert max(a, b) / min(a, b) < 1.5, \
        f"untraced serve throughput unstable: {a:.1f} vs {b:.1f} tok/s"
    assert pa["coverage"] is None or pa["coverage"] >= 0.9, \
        f"phase coverage {pa['coverage']} < 0.9 of step wall"

    # ---- mixed prefill+decode soak: one-shot stall baseline vs chunked
    soak = None
    if args.soak_requests:
        soak_wl = make_soak_workload(rng, args.soak_requests, cfg.vocab)
        for pc in (0, args.soak_prefill_chunk):     # warm all jit buckets
            run_soak(cfg, params, soak_wl, args.max_len, args.slots, pc)
        # INTERLEAVED best-of-N: the tracked chunked-vs-oneshot ratios
        # compare the two configs, so back-to-back repeat pairs keep a
        # noisy box from loading one side's repeats into a bad regime
        stall_out = stall = chunk_out = chunk = None
        for _ in range(args.repeats):
            so, sm = run_soak(cfg, params, soak_wl, args.max_len,
                              args.slots, 0)
            co, cm = run_soak(cfg, params, soak_wl, args.max_len,
                              args.slots, args.soak_prefill_chunk)
            if stall is None or sm["tokens_per_s"] > stall["tokens_per_s"]:
                stall_out, stall = so, sm
            if chunk is None or cm["tokens_per_s"] > chunk["tokens_per_s"]:
                chunk_out, chunk = co, cm
        pick = ("tokens_per_s", "ttft_p50_s", "ttft_p95_s",
                "decode_step_p95_s", "step_p95_s",
                "step_with_prefill_p95_s", "steps_with_prefill",
                "prefill_chunks", "wall_s")
        soak = {
            "requests": len(soak_wl),
            "prefill_chunk": args.soak_prefill_chunk,
            "oneshot": {k: stall[k] for k in pick},
            "chunked": {k: chunk[k] for k in pick},
            "speedup_chunked_vs_oneshot_tokens_per_s":
                chunk["tokens_per_s"] / stall["tokens_per_s"],
            # THE stall metric: p95 full-step latency among steps that did
            # prefill work — one-shot pays a whole prompt there, chunked
            # pays at most the chunk budget. None when a (smoke-sized)
            # run never overlapped prefill with live decoders.
            "step_with_prefill_p95_improvement":
                stall["step_with_prefill_p95_s"]
                / chunk["step_with_prefill_p95_s"]
                if stall["step_with_prefill_p95_s"] is not None
                and chunk["step_with_prefill_p95_s"] is not None else None,
            "greedy_agreement_chunked_vs_oneshot":
                agreement(chunk_out, stall_out),
        }

    # ---- metrics registry overhead: the registry is ALWAYS ON (unlike
    # the tracer, which is a profiling mode), so its hot-path cost must
    # be indistinguishable from run-to-run noise. Same config twice —
    # metrics on (the eng8f run above, registry default-enabled) vs
    # EngineConfig(metrics=False) — and the gap is asserted under
    # max(1%, the noise floor measured between the two untraced runs).
    ecfg8f_off = EngineConfig(**{**ecfg8f.__dict__, "metrics": False})
    run_engine(cfg, params, warm, ecfg8f_off)        # same jit cache, but
    # INTERLEAVED best-of-N pairs (min 3): the on/off delta is ~0.1% by
    # microbenchmark (tests/test_metrics.py), far under the box's noise,
    # so the two sides must sample the same machine regime — reusing the
    # earlier eng8f wall from a different moment of the run measures the
    # box, not the registry
    m_on = m_off = None
    for _ in range(max(args.repeats, 3)):
        _, mo = run_engine(cfg, params, workload, ecfg8f)
        _, mf = run_engine(cfg, params, workload, ecfg8f_off)
        if m_on is None or mo["tokens_per_s"] > m_on["tokens_per_s"]:
            m_on = mo
        if m_off is None or mf["tokens_per_s"] > m_off["tokens_per_s"]:
            m_off = mf
    on_tps, off_tps = m_on["tokens_per_s"], m_off["tokens_per_s"]
    mx_overhead_frac = 1.0 - on_tps / off_tps
    # bound = max(1%, 3 × measured noise): noise_frac comes from a SINGLE
    # pair of identical runs, which understates tail noise — the same
    # 3σ-style widening check_regression.py applies to its relative
    # gates (a 1.6% "overhead" reading on a 1.5%-noisy box is the box,
    # not the registry; the ~0.1% true registry cost is microbenchmarked
    # in tests/test_metrics.py)
    mx_bound = max(0.01, 3.0 * noise_frac)
    metrics_overhead = {
        "metrics_on_tokens_per_s": on_tps,
        "metrics_off_tokens_per_s": off_tps,
        "overhead_frac": mx_overhead_frac,
        "bound_frac": mx_bound,
    }
    assert mx_overhead_frac <= mx_bound, (
        f"always-on metrics registry costs {mx_overhead_frac:.2%} of "
        f"decode throughput ({on_tps:.1f} vs {off_tps:.1f} tok/s) — above "
        f"both the 1% budget and 3x the {noise_frac:.2%} noise floor; "
        f"something landed on the hot path outside the `if mx:` guards")

    # ---- flight recorder overhead: like the registry, the flight ring
    # (obs/flight.py) is ALWAYS ON — one per-step record dict + deque
    # append — so it gets the identical interleaved on/off treatment and
    # the identical <= max(1%, 3x noise) bound. The on side is the
    # default config (flight enabled); off is EngineConfig(flight=False).
    ecfg8f_fr_off = EngineConfig(**{**ecfg8f.__dict__, "flight": False})
    run_engine(cfg, params, warm, ecfg8f_fr_off)
    f_on = f_off = None
    for _ in range(max(args.repeats, 3)):
        _, fo = run_engine(cfg, params, workload, ecfg8f)
        _, ff = run_engine(cfg, params, workload, ecfg8f_fr_off)
        if f_on is None or fo["tokens_per_s"] > f_on["tokens_per_s"]:
            f_on = fo
        if f_off is None or ff["tokens_per_s"] > f_off["tokens_per_s"]:
            f_off = ff
    fr_on_tps, fr_off_tps = f_on["tokens_per_s"], f_off["tokens_per_s"]
    fr_overhead_frac = 1.0 - fr_on_tps / fr_off_tps
    fr_bound = max(0.01, 3.0 * noise_frac)
    flight_recorder = {
        "flight_on_tokens_per_s": fr_on_tps,
        "flight_off_tokens_per_s": fr_off_tps,
        "overhead_frac": fr_overhead_frac,
        "bound_frac": fr_bound,
    }
    assert fr_overhead_frac <= fr_bound, (
        f"always-on flight recorder costs {fr_overhead_frac:.2%} of "
        f"decode throughput ({fr_on_tps:.1f} vs {fr_off_tps:.1f} tok/s) "
        f"— above both the 1% budget and 3x the {noise_frac:.2%} noise "
        f"floor; the per-step record grew beyond one dict + ring append")

    # ---- open-loop SLO sweep: offered load is the independent variable;
    # each point replays a seeded Poisson+burst schedule against the
    # default serving config and judges every request against its class
    # SLO (loadgen.CLASSES). The sweep must contain a measured saturation
    # knee — the 'inf' endpoint (everything at t=0) guarantees one.
    open_loop = None
    if args.open_loop_requests:
        rates = [float(r) for r in args.open_loop_rates.split(",")]
        ol_ecfg = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                               kv_mode="int8", prefill_bucket=16)
        # the 'inf' endpoint gets a 2x-deep queue: it exists to measure
        # saturation, and a fast box can drain n requests before the
        # FCFS tail blows its TTFT SLO — doubling the backlog keeps the
        # closed-loop limit saturating on any box, so the sweep always
        # contains its knee
        schedules = {r: make_open_loop_workload(
            args.open_loop_seed,
            args.open_loop_requests * (1 if np.isfinite(r) else 2),
            cfg.vocab, r)
            for r in rates}
        # warm every prefill bucket the sweep's prompts will hit (class
        # draws differ per rate — the arrival process consumes a
        # rate-dependent number of rng draws — so take the union)
        ol_reps = {}
        for sched in schedules.values():
            for arr in sched:
                ol_reps.setdefault(
                    bucket_len(len(arr.prompt), ol_ecfg.prefill_bucket,
                               args.max_len), (arr.prompt, 8))
        run_engine(cfg, params, list(ol_reps.values()), ol_ecfg)
        points = []
        olms = {}
        for r in rates:
            slo, olm = run_open_loop(cfg, params, schedules[r], ol_ecfg)
            olms[r] = (slo, olm)
            pt = {
                "rate_rps": r,
                # mean effective arrival rate of the MMPP-2 (bursts at
                # 4x the base rate for 25% of wall time)
                "offered_rps": r * (1 + (4.0 - 1) * 0.25),
                "queue_depth_at_submit_p95":
                    olm["queue_depth_at_submit_p95"],
                "admit_latency_p95_s": olm["admit_latency_p95_s"],
                **slo,
            }
            points.append(pt)
            att = pt["slo_attainment"]
            print(f"open-loop rate {r:>5g} rps: attainment "
                  f"{'n/a' if att is None else f'{att:.0%}'}, goodput "
                  f"{pt['goodput_tokens_per_s']:.1f} tok/s, admit p95 "
                  f"{(pt['admit_latency_p95_s'] or 0) * 1e3:.1f} ms")
        knee = find_knee(points, args.slo_threshold)
        inter = [{"offered_rps": p["offered_rps"], "slo_attainment":
                  p["per_class"]["interactive"]["slo_attainment"]}
                 for p in points]
        open_loop = {
            "seed": args.open_loop_seed,
            "requests_per_point": args.open_loop_requests,
            "burst_factor": 4.0,
            "burst_fraction": 0.25,
            "slo_threshold": args.slo_threshold,
            "classes": CLASSES,
            "points": points,
            "knee": knee,
            "knee_interactive": find_knee(inter, args.slo_threshold),
        }

        # ---- overload comparison (DESIGN.md §12): one seeded schedule
        # at a SUSTAINED finite rate well past the knee (4x the last
        # SLO-attaining base rate), run twice — shed OFF (unbounded FCFS
        # queue) vs the full robustness stack ON (bounded queue sized
        # from the freshly measured knee depth, shed-by-class victims,
        # degradation ladder). Sustained matters: the 'inf' burst drains
        # in under the batch class's lenient TTFT SLO, so nothing there
        # is ever doomed and shedding can only discard attaining work —
        # past-knee *steady* load is where the unbounded queue grows
        # without bound and late admissions blow their SLOs while a
        # bounded queue keeps every admitted request inside the
        # measured-OK regime. Shedding converts doomed queueing into
        # goodput, so goodput_on >= goodput_off is the tracked (and
        # gated) invariant.
        finite = [p["rate_rps"] for p in points
                  if np.isfinite(p["rate_rps"])]
        ok = [p["rate_rps"] for p in points
              if np.isfinite(p["rate_rps"]) and (knee or {}).get(
                  "last_ok_offered_rps") == p["offered_rps"]]
        base_rate = ok[0] if ok else (max(finite) if finite else None)
        if base_rate is not None:
            # the knee only brackets saturation between its last finite
            # rate and 'inf', so "4x the knee" may still be under true
            # sustained capacity on a fast box — escalate (doubling,
            # shed-off probe each time) until the unbounded-queue run
            # actually drops below the SLO threshold; that measured-
            # saturating rate is the overload point both sides replay
            over_rate = 4.0 * base_rate
            for _ in range(5):
                over_sched = make_open_loop_workload(
                    args.open_loop_seed, args.open_loop_requests * 2,
                    cfg.vocab, over_rate)
                reps = {}
                for arr in over_sched:  # warm unseen prefill buckets
                    reps.setdefault(
                        bucket_len(len(arr.prompt),
                                   ol_ecfg.prefill_bucket,
                                   args.max_len), (arr.prompt, 8))
                run_engine(cfg, params, list(reps.values()), ol_ecfg)
                slo_off, olm_off = run_open_loop(cfg, params, over_sched,
                                                 ol_ecfg)
                if (slo_off["slo_attainment"] or 0) < args.slo_threshold:
                    break
                over_rate *= 2.0
            set_point = admission_set_point(open_loop) \
                or max(2, 2 * args.slots)
            on_ecfg = EngineConfig(**{
                **ol_ecfg.__dict__, "max_queue": set_point,
                "overload_policy": "shed-by-class", "degrade": True})
            slo_on, olm_on = run_open_loop(cfg, params, over_sched,
                                           on_ecfg)
            g_on = slo_on["goodput_tokens_per_s"] or 0.0
            g_off = slo_off["goodput_tokens_per_s"] or 0.0

            def _side(slo, olm):
                return {"slo_attainment": slo["slo_attainment"],
                        "goodput_tokens_per_s":
                            slo["goodput_tokens_per_s"],
                        "throughput_tokens_per_s":
                            slo["throughput_tokens_per_s"],
                        "retire_reasons": olm["retire_reasons"],
                        "requests_shed": olm.get("requests_shed", 0),
                        "degradation_transitions":
                            olm.get("degradation_transitions", 0)}
            open_loop["overload"] = {
                "requests": len(over_sched),
                "rate_rps": over_rate,
                "offered_rps": over_rate * (1 + (4.0 - 1) * 0.25),
                "max_queue": set_point,
                "overload_policy": "shed-by-class",
                "degrade": True,
                "shed_off": _side(slo_off, olm_off),
                "shed_on": _side(slo_on, olm_on),
                "goodput_ratio_shed_on_vs_off":
                    (g_on / g_off) if g_off > 0 else None,
            }
            ratio = open_loop["overload"]["goodput_ratio_shed_on_vs_off"]
            n_shed = open_loop["overload"]["shed_on"]["requests_shed"]
            print(f"overload ({over_rate:g} rps sustained, max_queue="
                  f"{set_point}): goodput shed-on {g_on:.1f} vs "
                  f"shed-off {g_off:.1f} tok/s (ratio "
                  f"{'n/a' if ratio is None else f'{ratio:.2f}x'}), "
                  f"shed {n_shed} requests")

    # ---- crash/recovery (DESIGN.md §13): seeded crash schedule, journal
    # + snapshot recovery, restore latency, token identity vs uncrashed.
    # Not gated by check_regression (recovery latency on a shared box is
    # noisy); the token_identical_vs_uncrashed bool is the number that
    # matters and IS asserted here.
    recovery = None
    if args.recovery_requests:
        recovery = run_recovery_bench(cfg, params, cfg.vocab,
                                      args.recovery_requests,
                                      args.recovery_seed, args.slots,
                                      args.max_len)
        assert recovery["token_identical_vs_uncrashed"], (
            f"crash/recovery bench diverged from the uncrashed "
            f"reference: {recovery}")

    def slim(m):
        # registry snapshots are live-export payloads, not tracked bench
        # numbers — keep BENCH_serve.json diffable across PRs
        return {k: v for k, v in m.items() if k != "registry"}

    result = {
        "provenance": provenance(seed=7),
        "arch": cfg.name,
        "requests": len(workload),
        "slots": args.slots,
        "max_len": args.max_len,
        "wave": wave,
        "engine": slim(eng),
        "engine_int8_kv": slim(eng8),
        "engine_int8_kv_fused": slim(eng8f),
        "speedup_tokens_per_s": eng["tokens_per_s"] / wave["tokens_per_s"],
        "speedup_fused_vs_materialized_int8":
            eng8f["tokens_per_s"] / eng8["tokens_per_s"],
        "greedy_agreement_engine_vs_wave": agree_engine_wave,
        "greedy_agreement_int8kv_vs_fp": agree_int8_fp,
        "greedy_agreement_fused_vs_materialized": agree_fused,
        "trace": trace,
        "metrics_overhead": metrics_overhead,
        "flight_recorder": flight_recorder,
        "soak": soak,
        "open_loop": open_loop,
        "recovery": recovery,
    }

    def steps(m):
        if m.get("decode_step_p50_s") is None:
            return ""
        return (f", step p50 {m['decode_step_p50_s']*1e3:.2f} ms "
                f"p95 {m['decode_step_p95_s']*1e3:.2f} ms")

    print(f"wave    : {wave['tokens_per_s']:8.1f} tok/s "
          f"({wave['total_tokens']} tokens, {wave['wall_s']:.2f}s)")
    print(f"engine  : {eng['tokens_per_s']:8.1f} tok/s "
          f"({eng['total_tokens']} tokens, {eng['wall_s']:.2f}s, "
          f"util {eng['slot_utilization']:.0%}{steps(eng)})")
    print(f"engine8 : {eng8['tokens_per_s']:8.1f} tok/s "
          f"(INT8 KV materialized, {eng8['kv_bytes_per_token']:.0f} "
          f"B/token/layer vs {eng['kv_bytes_per_token']:.0f}{steps(eng8)})")
    print(f"engine8f: {eng8f['tokens_per_s']:8.1f} tok/s "
          f"(INT8 KV fused read{steps(eng8f)})")
    print(f"speedup : engine/wave {result['speedup_tokens_per_s']:.2f}x, "
          f"fused/materialized "
          f"{result['speedup_fused_vs_materialized_int8']:.2f}x")
    print(f"greedy agreement: engine=wave {agree_engine_wave:.1%}, "
          f"int8=fp {agree_int8_fp:.1%}, fused=materialized "
          f"{agree_fused:.1%}")
    print(f"trace   : untraced {a:.1f}/{b:.1f} tok/s "
          f"(noise {noise_frac:.1%}), traced "
          f"{trace['traced_tokens_per_s']:.1f} tok/s (overhead "
          f"{trace['traced_overhead_frac']:.1%}), coverage "
          f"{pa['coverage']:.1%}, dispatch {pa['dispatch_frac']:.0%} / "
          f"wait {pa['device_wait_frac']:.0%}")
    if soak:
        s1, s2 = soak["oneshot"], soak["chunked"]

        def ms(x):
            return f"{x*1e3:.1f} ms" if x is not None else "n/a"
        print(f"soak oneshot: {s1['tokens_per_s']:8.1f} tok/s, ttft p50 "
              f"{ms(s1['ttft_p50_s'])} p95 {ms(s1['ttft_p95_s'])}, "
              f"step-with-prefill p95 {ms(s1['step_with_prefill_p95_s'])}")
        print(f"soak chunked: {s2['tokens_per_s']:8.1f} tok/s, ttft p50 "
              f"{ms(s2['ttft_p50_s'])} p95 {ms(s2['ttft_p95_s'])}, "
              f"step-with-prefill p95 {ms(s2['step_with_prefill_p95_s'])} "
              f"(chunk {soak['prefill_chunk']})")
        imp = soak["step_with_prefill_p95_improvement"]
        print(f"soak: step-with-prefill p95 "
              f"{'n/a' if imp is None else f'{imp:.2f}x'} better "
              f"chunked, tokens/s "
              f"{soak['speedup_chunked_vs_oneshot_tokens_per_s']:.2f}x, "
              f"greedy agreement "
              f"{soak['greedy_agreement_chunked_vs_oneshot']:.1%}")
    print(f"metrics : on {on_tps:.1f} / off {off_tps:.1f} tok/s "
          f"(overhead {mx_overhead_frac:.2%} <= bound "
          f"{metrics_overhead['bound_frac']:.2%})")
    print(f"flight  : on {fr_on_tps:.1f} / off {fr_off_tps:.1f} tok/s "
          f"(overhead {fr_overhead_frac:.2%} <= bound "
          f"{flight_recorder['bound_frac']:.2%})")
    if open_loop:
        k = open_loop["knee"]
        if k is None:
            print(f"open-loop: no saturation knee found (attainment "
                  f"never dropped below {args.slo_threshold:.0%} — "
                  f"raise the sweep's top rate)")
        else:
            lo = k["last_ok_offered_rps"]
            print(f"open-loop knee: attainment holds >= "
                  f"{k['threshold']:.0%} up to "
                  f"{'n/a' if lo is None else f'{lo:g} rps'} offered, "
                  f"saturates at {k['first_saturated_offered_rps']:g} rps "
                  f"({k['first_saturated_attainment']:.0%})")
    if recovery:
        print(f"recovery: crashed at step {recovery['crashed_at_step']} "
              f"(t={recovery['crash_t_s']*1e3:.0f} ms), restore "
              f"{recovery['restore_duration_s']*1e3:.1f} ms, "
              f"{recovery['n_restored_from_snapshot']} restored / "
              f"{recovery['n_requeued_from_journal']} re-enqueued / "
              f"{recovery['n_retired_pre_crash']} pre-crash retires, "
              f"token-identical "
              f"{recovery['token_identical_vs_uncrashed']}")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, default=str)
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
