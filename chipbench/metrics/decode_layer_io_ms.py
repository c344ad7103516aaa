"""Device time per decode step (jit_step run) in the layer scan's own
ops: under the ``layers`` scope and outside its body, ``layer``. That is
the scan slicing each layer's weights and cache in and stacking the new
cache out."""
from harness import scopes


def read(run):
    sc = scopes.of_run(run)
    if sc is not None and not sc.has(scopes.DECODE_FN, "layer"):
        scopes.log("decode_layer_io_ms: no op of jit_step under the scope "
                   "'layer' in this trace")
        return None
    return scopes.decode_scope_ms(
        run, "decode_layer_io_ms", "layers",
        lambda p: scopes.under(p, "layers") and not scopes.under(p, "layer"))
