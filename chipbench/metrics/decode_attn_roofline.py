"""The decode-attention kernel's least time (the larger of its FLOPs over
the bf16 peak and its bytes over HBM bandwidth, for the live rows only)
over its summed device time."""
from harness import counts

DECODE_FN = "step"
#: the Pallas call named decode_attention, by its HLO instruction or
#: op_name; another Pallas call in jit_step is not read
KERNEL = r"tpu_custom_call .*\bdecode_attention\b"


def read(run):
    calls = run.in_window(run.win.decode_calls)
    if run.trace is None or not calls:
        return None
    n, secs = run.trace.ops_in_module(DECODE_FN, KERNEL)
    steps = len(run.trace.module_runs(DECODE_FN))
    if not n or not steps:
        return None
    least = sum(counts.least_time(*counts.decode_attn_cost(run.conf, pos),
                                  run.peaks) for _, pos in calls)
    # the kernel's time per traced step against the least time per step
    return 100.0 * (least / len(calls)) / (secs / steps)
