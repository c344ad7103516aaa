"""Mean device time of one run of the decode executable (jit_step)."""
DECODE_FN = "step"


def read(run):
    runs = run.trace.module_runs(DECODE_FN) if run.trace else []
    return 1e3 * sum(runs) / len(runs) if runs else None
