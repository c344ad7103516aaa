"""Device time of the Pallas prefill-attention kernel per chunk (all
layers), from the ops of the Pallas call named prefill_attention
inside the chunk-prefill executable."""
CHUNK_FN = "chunk"
#: the Pallas call named prefill_attention, by its HLO instruction or
#: op_name; another Pallas call in jit_chunk is not read
KERNEL = r"tpu_custom_call .*\bprefill_attention\b"


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.ops_in_module(CHUNK_FN, KERNEL)
    chunks = len(run.trace.module_runs(CHUNK_FN))
    if not n or not chunks:
        return None
    return 1e3 * secs / chunks
