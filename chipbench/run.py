"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds BENCHMARK.json. One process holds
the chip for its whole life and starts no other. In order:

1. refuses to run unless JAX finds a TPU with as many chips as the cell
   asks for (exit 2, no result);
2. builds the served weights from ``--seed`` through the program's own
   path (``model.init``, ``quantize_tree``) and an ``Engine`` with the
   configuration file's settings;
3. warms every shape the cell uses: one short request per chunk-prefill
   bucket, which also runs decode, sampling and slot release;
4. sends each closed-loop client its first request, drawn as one already
   part-way through its answer, and steps until each has its first token;
5. moves what set-up left on the heap out of the garbage collector's
   reach (``gc.freeze``) and measures for ``--seconds`` (with
   ``--trace 1`` under the JAX profiler, for at most TRACE_SECONDS, and
   the trace is reduced to the per-layer metrics); compiles, cache loads, collector pauses and the
   longest steps inside the window are named on standard error;
6. frees the program's state and checks a sample of what the window
   served against the plain reference of the configuration's
   architecture (``architectures/<name>.py``, ``harness/check.py``).

Set-up (``setup_s``) runs from the start of this process to the first
timed step. The last line of standard output is one JSON object: correct,
attempted, failed, metrics, device, with ``--trace 1`` a breakdown, and
last the numbers that decided ``correct`` beside their limits, which are
also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH_DIR))

#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".trace"
#: the longest window a traced run measures: past about 4M device ops
#: (stablelm-1.6b's decode cell reaches that in about 40 s) the profiler's
#: device trace loses events, and writing and reading a longer trace
#: brings the run near its time limit
TRACE_SECONDS = 30.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def use_compile_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache keeps an access-time file beside each
    # entry, and one gone missing fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


def _entries() -> int:
    return len(list(CACHE_DIR.glob("*"))) if CACHE_DIR.is_dir() else 0


class Pauses:
    """What stops the host inside the window: JAX's compile and
    compile-cache events, and garbage collections of 10 ms or more, each
    with its start (on `clock`) and its seconds."""

    def __init__(self, jax, clock):
        self.jax, self.clock = jax, clock
        self.on = False
        self.events: list[tuple[str, float, float]] = []
        self._gc_t = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._jax)
        gc.callbacks.append(self._gc)

    def close(self) -> None:
        self.jax.monitoring.unregister_event_duration_listener(self._jax)
        gc.callbacks.remove(self._gc)

    def _jax(self, name, secs, **_):
        if self.on and ("compil" in name or "cache" in name):
            self.events.append((name, self.clock() - secs, secs))

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = self.clock()
        elif self.on and self.clock() - self._gc_t >= 0.01:
            self.events.append((f"gc gen{info['generation']}", self._gc_t,
                                self.clock() - self._gc_t))

    def report(self, win, top: int = 3) -> None:
        ends = win.step_ends
        steps = sorted(((b - a, i) for i, (a, b) in
                        enumerate(zip([win.t0] + ends, ends))),
                       reverse=True)[:top]
        log("window: longest steps " + ", ".join(
            f"#{i} {1e3 * d:.1f} ms" for d, i in steps))
        log(f"window: {len(self.events)} compile, cache or collector "
            f"events" + "".join(f"; {n} at +{t - win.t0:.3f} s for "
                                f"{1e3 * d:.1f} ms"
                                for n, t, d in self.events[:10]))


def warm_lengths(conf: dict, traffic: dict) -> list[int]:
    """One prompt length per chunk-prefill bucket shape."""
    chunk, bucket = conf["prefill_chunk"], conf["prefill_bucket"]
    if not chunk:
        raise ValueError("the benchmark drives chunked prefill only")
    return [min(b - bucket // 2, traffic["max_len"] - 2)
            for b in range(bucket, chunk + 1, bucket)]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device, control: bool = False) -> dict:
    """Build, warm, measure and check one cell; returns the result line.
    `device` is the chip whose memory is read; the caller has checked
    that it is the one the cell asks for."""
    import jax

    from harness import build, check, counts, loop, spec, trace as trace_mod
    from harness.traffic import Traffic

    conf, mix = cell["config"], cell["traffic"]
    clock = time.perf_counter
    pk = counts.peaks(device.device_kind) if device.platform == "tpu" \
        else None
    t = clock()
    cfg = build.arch_config(conf)
    qparams = build.build_weights(cfg, conf, seed)
    log(f"build: {clock() - t:.1f} s")
    eng = build.make_engine(cfg, qparams, conf, mix)
    n_slots = eng.ecfg.n_slots
    traffic = Traffic(mix, conf["vocab"], n_slots, seed)
    cl = loop.ClosedLoop(eng, traffic, clock=clock,
                         annotate=jax.profiler.TraceAnnotation if trace
                         else None, spy=trace, log=log)
    t = clock()
    cl.warm(warm_lengths(conf, mix))
    log(f"warm-up: {clock() - t:.1f} s")
    t = clock()
    cl.start()
    log(f"stationary start: {traffic.n_clients} clients, "
        f"{sum(len(s.prompt) for s in cl.win.served)} prompt tokens, "
        f"{clock() - t:.1f} s")
    pauses = Pauses(jax, clock)
    gc.collect()
    gc.freeze()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans, no Python calls
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = clock() - T_START
    pauses.on = True
    win = cl.run(min(seconds, TRACE_SECONDS) if trace else seconds)
    pauses.on = False
    gc.unfreeze()           # so that the program's state can be freed
    if trace:
        jax.profiler.stop_trace()
    peak_bytes = (device.memory_stats() or {}).get("peak_bytes_in_use")
    summary = None
    if trace:
        t = clock()
        summary = trace_mod.summarize(trace_mod.load(
            trace_mod.find_xplane(str(TRACE_DIR))))
        log(f"trace: {len(summary.ops)} device ops read in "
            f"{clock() - t:.1f} s")
    run = loop.Run(conf, mix, n_slots, setup_s, win, pk, summary)
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = spec.metric_reader(BENCH_DIR, m["name"])(run)
        if v is None:
            log(f"metric {m['name']}: nothing to read in this run")
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    gaps, ttft = loop.gaps_in(win), loop.ttft_in(win)
    failed = sum(1 for s in win.served
                 if s.finished and s.reason not in loop.FINISHED_OK)
    retries = eng.n_step_retries
    n_done = sum(1 for s in win.served
                 if s.finished and s.stamps and win.inside(s.stamps[-1]))
    log(f"window: {win.seconds:.3f} s, {win.steps} steps, "
        f"{win.decode_steps_in} decode steps, {loop.tokens_in(win)} tokens, "
        f"{len(gaps)} gaps, {len(ttft)} requests sent, {n_done} finished, "
        f"peak_bytes_in_use {peak_bytes}")
    pauses.report(win)
    pauses.close()

    picked = check.sample(win.served, seed)
    del cl, eng, qparams, traffic
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"left on the device before the reference: {live} bytes")
    t = clock()
    readings = check.compare(conf, seed, picked, mix["max_len"],
                             control=control)
    log(f"reference: {clock() - t:.1f} s, {readings}")
    # with `control` the float8 control stands in the program's place: its
    # gap is the one held to the limit, and it has to come out not correct
    gap = "control_max_gap_sd" if control else "max_gap_sd"
    checks = {
        gap: {"value": readings[gap], "limit": conf["check"]["max_gap_sd"]},
        "failed_requests": {"value": failed, "limit": 0},
        "step_retries": {"value": retries, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": bool(correct), "attempted": len(win.served),
        "failed": failed, "metrics": metrics,
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": peak_bytes},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.gaps}
    result["checks"] = checks
    log(f"compile cache: {_entries()} entries")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from harness import spec

    use_compile_cache(jax)
    cell = spec.load_cell(ROOT, BENCH_DIR, args.workload)
    devices = jax.devices()
    need = cell["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < need:
        log(f"chipbench: this cell needs {need} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    log(f"device: {devices[0].device_kind}, {len(devices)} chip(s); "
        f"compile cache {CACHE_DIR} with {_entries()} entries")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[0])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
