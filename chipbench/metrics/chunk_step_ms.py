"""Mean device time of one run of the chunk-prefill executable (jit_chunk),
over every chunk bucket."""
CHUNK_FN = "chunk"


def read(run):
    runs = run.trace.module_runs(CHUNK_FN) if run.trace else []
    return 1e3 * sum(runs) / len(runs) if runs else None
