"""Device time per decode step (jit_step run) in the head: the final
norm, the vocabulary projection and the greedy argmax (ops under the
``lm_head`` scope)."""
from harness import scopes


def read(run):
    return scopes.decode_scope_ms(run, "decode_lm_head_ms", "lm_head")
