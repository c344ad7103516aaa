"""Self-speculative decoding benchmark → BENCH_spec.json.

Serves one decode-heavy mixed-length workload through the engine twice —
plain greedy (spec_k=0) and speculative (spec_k>0) at EQUAL batch — for a
ladder of SplitQuant draft fidelities, and reports per config:

  * the acceptance-rate histogram (verify calls that accepted exactly a
    draft tokens, a in [0, spec_k]) plus draft/verify token counters;
  * tokens/s vs the non-speculative engine (the tracked speedup), and
  * greedy agreement with the non-speculative run (must be 100% — the
    accept rule is lossless; anything else is a bug, see
    tests/test_spec.py).

The headline draft is a mixed <=2.9-avg-bit QuantRecipe (attention
projections at 4 bits, everything else at 2 — the SplitQuant
faithfulness-per-byte sweet spot the calibration benchmark established),
loaded through the real `engine.spec.load_draft_params` recipe path. The
ladder (INT4, INT8, self-draft upper bound) shows acceptance rising with
draft fidelity; on RANDOM-INIT weights low-bit drafts diverge far more
than on trained checkpoints (the paper's recovery results are post-
training), so treat the absolute acceptance here as a lower bound and
the self-draft row as the harness ceiling. The expected >=1.3x
tokens/s applies when measured acceptance >= 0.7; the number is
reported either way.

    PYTHONPATH=src python benchmarks/spec_bench.py            # full
    PYTHONPATH=src python benchmarks/spec_bench.py --smoke    # CI-sized
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.calib import QuantRecipe  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import QuantConfig, QuantPolicy, quantize_tree  # noqa: E402
from repro.engine import Engine, EngineConfig  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.obs import token_agreement  # noqa: E402

from run import provenance  # noqa: E402

SEED = 11


def make_workload(rng, n_requests, vocab, new_tokens):
    """Short prompts, long generations: speculative decoding attacks the
    DECODE wall, so the workload keeps slots mid-generation ~all the
    time (prefill treatment is identical across configs anyway)."""
    return [(rng.integers(0, vocab, size=int(rng.integers(4, 12))),
             new_tokens) for _ in range(n_requests)]


def allocated_avg_bits(params, per_path) -> float:
    """Parameter-weighted average of the ASSIGNED bit-widths (the number
    the calibration benchmark tracks — codebook/scale overhead is
    reported separately as deployed bytes)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    sizes = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path): leaf.size for path, leaf in flat}
    num = den = 0
    for p, d in per_path.items():
        num += d["bits"] * sizes[p]
        den += sizes[p]
    return num / den


def run_engine(cfg, params, workload, ecfg, draft=None, repeats=1):
    """Best-of-N (greedy: identical outputs across repeats, fastest run
    is the steady-state sample)."""
    best = None
    for _ in range(repeats):
        eng = Engine(cfg, params, ecfg, draft_params=draft)
        for p, b in workload:
            eng.submit(p.copy(), max_new_tokens=b)
        t0 = time.perf_counter()
        fin = eng.drain()
        wall = time.perf_counter() - t0
        m = eng.metrics()
        m["wall_s"] = wall
        m["tokens_per_s"] = m["total_tokens"] / wall
        if best is None or m["tokens_per_s"] > best[1]["tokens_per_s"]:
            best = (fin, m)
    return best


# greedy-token agreement (shared helper: repro.obs.summary)
agreement = token_agreement


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests/repeats, drops "
                         "the INT8 ladder point)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_spec.json"))
    args = ap.parse_args()
    use_compile_cache()
    requests = args.requests or (6 if args.smoke else 16)
    new_tokens = args.new_tokens or (24 if args.smoke else 48)
    repeats = args.repeats or (1 if args.smoke else 3)

    cfg = get_arch(args.arch).reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)
    rng = np.random.default_rng(SEED)
    workload = make_workload(rng, requests, cfg.vocab, new_tokens)

    # ---- draft ladder -----------------------------------------------
    # headline: mixed <=2.9-avg-bit recipe (attention 4-bit, rest 2-bit),
    # loaded through the real QuantRecipe path the engine uses
    _, probe = quantize_tree(key, params, QuantPolicy(
        cfg=QuantConfig(bits=2)))
    mixed_over = {p: {"bits": 4} for p in probe["per_path"]
                  if "/attn/" in p and p.endswith(("wq", "wk"))}
    qp_mixed, rep_mixed = quantize_tree(
        key, params, QuantPolicy(cfg=QuantConfig(bits=2)),
        overrides=mixed_over)
    mixed_bits = allocated_avg_bits(params, rep_mixed["per_path"])
    assert mixed_bits <= 2.9, mixed_bits
    drafts = {}
    with tempfile.TemporaryDirectory() as recipe_dir:
        QuantRecipe(
            name=f"{cfg.name}-spec-draft", arch=cfg.name,
            policies={p: {"bits": d["bits"], "k": d["k"],
                          "method": d["method"]}
                      for p, d in rep_mixed["per_path"].items()},
            meta={"avg_bits": mixed_bits}).save(recipe_dir)
        from repro.engine.spec import load_draft_params
        drafts["mixed2.9"] = (load_draft_params(recipe_dir, params, cfg),
                              mixed_bits, rep_mixed["deployed_bytes"])
    for bits in (4,) if args.smoke else (4, 8):
        qp, rep = quantize_tree(key, params, QuantPolicy(
            cfg=QuantConfig(bits=bits)))
        drafts[f"int{bits}"] = (qp, float(bits), rep["deployed_bytes"])
    drafts["self"] = (params, 32.0, probe["orig_bytes"])

    ecfg0 = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                         prefill_bucket=8, kv_mode="int8")
    ecfgS = EngineConfig(**{**ecfg0.__dict__, "spec_k": args.spec_k})
    print(f"workload: {requests} requests x {new_tokens} tokens, "
          f"{args.slots} slots, spec_k={args.spec_k}, kv=int8")

    # warm every jit bucket (decode, prefill chunks, verify window) so
    # measured walls compare steady state, not XLA compiles
    warm = workload[:min(3, len(workload))]
    run_engine(cfg, params, warm, ecfg0)
    run_engine(cfg, params, warm, ecfgS, draft=drafts["self"][0])

    base_out, base = run_engine(cfg, params, workload, ecfg0,
                                repeats=repeats)
    print(f"spec_k=0 baseline: {base['tokens_per_s']:8.1f} tok/s "
          f"({base['total_tokens']} tokens, {base['wall_s']:.2f}s)")

    configs = {}
    for name, (dp, bits, dbytes) in drafts.items():
        out, m = run_engine(cfg, params, workload, ecfgS, draft=dp,
                            repeats=repeats)
        agree = agreement(out, base_out)
        configs[name] = {
            "draft_avg_bits": bits,
            "draft_deployed_bytes": int(dbytes),
            "tokens_per_s": m["tokens_per_s"],
            "speedup_vs_nonspec": m["tokens_per_s"] / base["tokens_per_s"],
            "acceptance_rate": m["acceptance_rate"],
            "accept_hist": m["accept_hist"],
            "tokens_per_verify_mean": m["tokens_per_verify_mean"],
            "draft_proposed": m["draft_proposed"],
            "draft_accepted": m["draft_accepted"],
            "draft_steps": m["draft_steps"],
            "verify_calls": m["verify_calls"],
            "verify_tokens": m["verify_tokens"],
            "spec_steps": m["spec_steps"],
            "greedy_agreement_vs_nonspec": agree,
            "wall_s": m["wall_s"],
        }
        c = configs[name]
        print(f"{name:>9}: {c['tokens_per_s']:8.1f} tok/s "
              f"({c['speedup_vs_nonspec']:.2f}x), acceptance "
              f"{c['acceptance_rate']:.1%} "
              f"({c['tokens_per_verify_mean']:.2f} tokens/verify, hist "
              f"{c['accept_hist']}), agreement {agree:.1%}")
        assert agree == 1.0, (name, agree)   # the accept rule is lossless

    # ---- traced phase attribution of the headline spec config --------
    # One traced run of the mixed2.9 draft answers WHERE the spec step's
    # wall goes: draft vs verify vs rollback vs host dispatch (the
    # ROADMAP's "is verify dispatch-bound?" question). Coverage of the
    # per-step phase spans must account for >=90% of stepped wall —
    # anything less means an uninstrumented phase is eating time.
    traced_cfg = EngineConfig(**{**ecfgS.__dict__, "trace": True})
    dp_head = drafts["mixed2.9"][0]
    run_engine(cfg, params, warm, traced_cfg, draft=dp_head)  # warm
    _, traced = run_engine(cfg, params, workload, traced_cfg,
                           draft=dp_head, repeats=repeats)
    pa = traced["phase_attribution"]
    ph = pa["phases"]
    step_total = max(pa["step_total_s"], 1e-12)

    def _tot(name):
        return ph.get(name, {}).get("total_s", 0.0)
    trace = {
        "config": "mixed2.9",
        "traced_tokens_per_s": traced["tokens_per_s"],
        "coverage": pa["coverage"],
        "steps": pa["steps"],
        "step_total_s": pa["step_total_s"],
        # the four-way split the ISSUE tracks: draft / verify / rollback
        # / host-dispatch shares of attributed step time
        "draft_frac_of_step": _tot("draft") / step_total,
        "verify_frac_of_step": _tot("verify") / step_total,
        "rollback_frac_of_step": _tot("rollback") / step_total,
        "dispatch_frac": pa["dispatch_frac"],
        "device_wait_frac": pa["device_wait_frac"],
        "phase_attribution": pa,
    }
    assert pa["coverage"] is None or pa["coverage"] >= 0.9, \
        f"spec phase coverage {pa['coverage']} < 0.9 of step wall"
    print(f"trace(mixed2.9): coverage {pa['coverage']:.1%}, "
          f"draft {trace['draft_frac_of_step']:.0%} / verify "
          f"{trace['verify_frac_of_step']:.0%} / rollback "
          f"{trace['rollback_frac_of_step']:.0%} of step wall; "
          f"host dispatch {pa['dispatch_frac']:.0%} / device wait "
          f"{pa['device_wait_frac']:.0%} of attributed time")

    head = configs["mixed2.9"]
    result = {
        "provenance": provenance(seed=SEED),
        "arch": cfg.name,
        "requests": requests,
        "new_tokens": new_tokens,
        "slots": args.slots,
        "max_len": args.max_len,
        "spec_k": args.spec_k,
        "smoke": args.smoke,
        "nonspec": {k: base[k] for k in
                    ("tokens_per_s", "total_tokens", "wall_s",
                     "decode_steps")},
        "configs": configs,
        "trace": trace,
        # the tracked headline pair: a <=2.9-avg-bit draft's acceptance
        # and its tokens/s vs the non-speculative engine at equal batch
        # (>=1.3x expected once acceptance >= 0.7 — random-init weights
        # land far below that; report either way)
        "headline_draft_avg_bits": head["draft_avg_bits"],
        "headline_acceptance_rate": head["acceptance_rate"],
        "headline_speedup_vs_nonspec": head["speedup_vs_nonspec"],
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, default=str)
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
