"""The prefill-attention kernel's least time (the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth, over the valid prefix and the
chunk) over its summed device time."""
from harness import counts

CHUNK_FN = "chunk"
#: the Pallas call named prefill_attention, by its HLO instruction or
#: op_name; another Pallas call in jit_chunk is not read
KERNEL = r"tpu_custom_call .*\bprefill_attention\b"


def read(run):
    calls = run.in_window(run.win.chunk_calls)
    if run.trace is None or not calls:
        return None
    n, secs = run.trace.ops_in_module(CHUNK_FN, KERNEL)
    chunks = len(run.trace.module_runs(CHUNK_FN))
    if not n or not chunks:
        return None
    least = sum(counts.least_time(*counts.prefill_attn_cost(run.conf, p, n),
                                  run.peaks) for _, p, n in calls)
    return 100.0 * (least / len(calls)) / (secs / chunks)
