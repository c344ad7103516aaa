"""Least bytes one decode step must move (weights at bits + ceil(log2 k)
bits each, their cluster constants, embedding rows and norms, the live KV
rows read and the new rows written) over the decode executable's mean
device time, as a share of the chip's HBM bandwidth."""
from harness import counts

DECODE_FN = "step"


def read(run):
    calls = run.in_window(run.win.decode_calls)
    runs = run.trace.module_runs(DECODE_FN) if run.trace else []
    if not calls or not runs:
        return None
    need = sum(counts.decode_step_bytes(run.conf, pos) for _, pos in calls)
    need /= len(calls)
    mean_t = sum(runs) / len(runs)
    return 100.0 * need / mean_t / run.peaks["hbm_bytes_per_s"]
