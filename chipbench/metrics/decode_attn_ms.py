"""Device time of the Pallas decode-attention kernel per decode step (all
layers), from the kernel's ops inside the decode executable."""
DECODE_FN = "step"
KERNEL = r"tpu_custom_call"      # the one Pallas call in jit_step


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.ops_in_module(DECODE_FN, KERNEL)
    steps = len(run.trace.module_runs(DECODE_FN))
    if not n or not steps:
        return None
    return 1e3 * secs / steps
