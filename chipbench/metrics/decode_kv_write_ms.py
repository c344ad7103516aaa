"""Device time per decode step (jit_step run) in the slot cache's row
write: the new token's K/V quantized and scattered into every slot, all
layers (ops under the ``kv_write`` scope)."""
from harness import scopes


def read(run):
    return scopes.decode_scope_ms(run, "decode_kv_write_ms", "kv_write")
