"""Device time of the Pallas prefill-attention kernel per chunk (all
layers), from the kernel's ops inside the chunk-prefill executable."""
CHUNK_FN = "chunk"
KERNEL = r"tpu_custom_call"      # the one Pallas call in jit_chunk


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.ops_in_module(CHUNK_FN, KERNEL)
    chunks = len(run.trace.module_runs(CHUNK_FN))
    if not n or not chunks:
        return None
    return 1e3 * secs / chunks
