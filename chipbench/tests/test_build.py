"""The served weights: the leaf-by-leaf build gives what one quantize_tree
call gives, and the dense decoder's reference derives the same weights
on its own."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import BENCH_DIR

from harness import arch, build
from harness.refquant import seed_key

SEED = 2 ** 31 + 77
reference = arch.load(arch.DEFAULT)


@pytest.fixture(scope="module", params=["rms", "layer"])
def tiny(request):
    with open(BENCH_DIR / "tests" / "data" / "tiny.json") as f:
        return dict(json.load(f), norm=request.param)


def sqt_leaves(tree):
    from repro.core.splitquant import SplitQuantTensor
    return jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, SplitQuantTensor))[0]


def test_leaf_by_leaf_equals_one_call(tiny):
    from repro.core import QuantConfig, QuantPolicy, quantize_tree
    from repro.models import get_model

    cfg = build.arch_config(tiny)
    key = seed_key(SEED)
    params = jax.jit(get_model(cfg).init, static_argnums=1)(key, cfg)
    q = tiny["quant"]
    one, _ = quantize_tree(key, params, QuantPolicy(
        cfg=QuantConfig(bits=q["bits"]), k=q["k"], method=q["method"]))
    per = build.build_weights(cfg, tiny, SEED)
    a, b = jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(per)
    assert jax.tree_util.tree_structure(one) == \
        jax.tree_util.tree_structure(per)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))


def test_reference_derives_the_served_weights(tiny):
    """Codes, cluster ids, per-cluster constants and the embedding the
    reference derives from the seed are the program's, bit for bit."""
    from repro.core.splitquant import SplitQuantTensor

    cfg = build.arch_config(tiny)
    served = build.build_weights(cfg, tiny, SEED)
    ref = reference.derive_weights(tiny, SEED)
    got = {"embed": served["embed"], "lm_head": served["lm_head"]}
    got.update(served["layers"]["attn"])
    got.update(served["layers"]["ffn"])
    assert set(got) == set(ref)
    for name, leaf in got.items():
        if isinstance(leaf, SplitQuantTensor):
            want = (leaf.q, leaf.cid, leaf.scale, leaf.zero)
            for x, y in zip(want, ref[name]):
                assert np.array_equal(np.asarray(x), np.asarray(y)), name
        else:
            assert np.array_equal(np.asarray(leaf), np.asarray(ref[name]))


def test_reference_forward_matches_program(tiny):
    """The reference's logits equal the program's own forward over the
    dequantized weights in float32 (no cache, no kernels)."""
    from repro.core.apply import dequantize_tree
    from repro.models import get_model

    conf = dict(tiny, param_dtype="float32")
    cfg = build.arch_config(conf)
    served = build.build_weights(cfg, conf, SEED)
    dense = dequantize_tree(served)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, conf["vocab"], n).astype(np.int32)
            for n in (37, 300)]
    toks = np.zeros((2, 512), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        want = np.asarray(get_model(cfg).forward(
            dense, cfg, {"tokens": jnp.asarray(toks)})[0])
    got = reference.logits(conf, reference.derive_weights(conf, SEED), seqs)
    for i, s in enumerate(seqs):
        np.testing.assert_allclose(got[i, :len(s)], want[i, :len(s)],
                                   rtol=2e-4, atol=2e-4)
