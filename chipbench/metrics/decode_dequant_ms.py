"""Device time per decode step (jit_step run) in the dequant-matmul of
every SplitQuant weight, in the layers and at the head, its per-call
packing included (ops under the ``dequant_matmul`` scope)."""
from harness import scopes


def read(run):
    return scopes.decode_scope_ms(run, "decode_dequant_ms",
                                  "dequant_matmul")
