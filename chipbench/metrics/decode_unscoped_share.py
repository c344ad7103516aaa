"""Share of the decode executable's device time (its jit_step runs) in
ops under none of the scopes ``embed``, ``layers`` and ``lm_head``:
what the names do not account for, such as copies XLA adds with no
op_name."""
from harness import scopes

SCOPES = ("embed", "layers", "lm_head")


def read(run):
    sc = scopes.of_run(run)
    if sc is None:
        return None
    fn = scopes.DECODE_FN
    missing = [s for s in SCOPES if not sc.has(fn, s)]
    if not sc.n_runs(fn) or missing:
        scopes.log(f"decode_unscoped_share: no op of jit_{fn} under the "
                   f"scope(s) {missing} in this trace")
        return None
    rest = sc.seconds_per_run(
        fn, lambda p: not any(scopes.under(p, s) for s in SCOPES))
    return 100.0 * rest / (sum(sc.runs[fn]) / sc.n_runs(fn))
