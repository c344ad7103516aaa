"""Model FLOPs of the window's work (prompt tokens prefilled and tokens
decoded, each 2 per matmul weight element plus attention at its context)
over the traced window times the chip's bf16 peak."""
from harness import counts


def read(run):
    if run.trace is None:
        return None
    c = run.conf
    flops = sum(counts.chunk_flops(c, p, n)
                for _, p, n in run.in_window(run.win.chunk_calls))
    flops += sum(counts.decode_step_flops(c, pos)
                 for _, pos in run.in_window(run.win.decode_calls))
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops"])
