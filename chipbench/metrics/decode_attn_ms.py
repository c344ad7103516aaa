"""Device time of the Pallas decode-attention kernel per decode step (all
layers), from the ops of the Pallas call named decode_attention
inside the decode executable."""
DECODE_FN = "step"
#: the Pallas call named decode_attention, by its HLO instruction or
#: op_name; another Pallas call in jit_step is not read
KERNEL = r"tpu_custom_call .*\bdecode_attention\b"


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.ops_in_module(DECODE_FN, KERNEL)
    steps = len(run.trace.module_runs(DECODE_FN))
    if not n or not steps:
        return None
    return 1e3 * secs / steps
