"""The chunk-prefill executable's least time (the larger of the chunk's
FLOPs over the bf16 peak and its bytes over HBM bandwidth, for its valid
tokens) over its mean device time."""
from harness import counts

CHUNK_FN = "chunk"


def read(run):
    calls = run.in_window(run.win.chunk_calls)
    runs = run.trace.module_runs(CHUNK_FN) if run.trace else []
    if not calls or not runs:
        return None
    least = sum(counts.least_time(counts.chunk_flops(run.conf, p, n),
                                  counts.chunk_bytes(run.conf, p, n),
                                  run.peaks) for _, p, n in calls)
    return 100.0 * (least / len(calls)) / (sum(runs) / len(runs))
