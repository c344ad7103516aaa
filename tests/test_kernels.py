"""Pallas kernel validation: shape/dtype/bits sweeps against the pure-jnp
oracle (interpret mode on CPU), the paper's literal 3-layer form, and the
packing utilities."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.core import QuantConfig, splitquant_tensor
from repro.kernels import ops, ref
from repro.kernels.packing import (pack_cids, pack_codes, unpack_cids,
                                   unpack_codes)

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_pack_roundtrip(bits):
    q = jax.random.randint(KEY, (64, 32), -(2 ** (bits - 1)),
                           2 ** (bits - 1)).astype(jnp.int8)
    rt = unpack_codes(pack_codes(q, bits), bits)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(q))


def test_cid_pack_roundtrip():
    cid = jax.random.randint(KEY, (64, 32), 0, 4).astype(jnp.uint8)
    np.testing.assert_array_equal(np.asarray(unpack_cids(pack_cids(cid))),
                                  np.asarray(cid))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 8]))
def test_pack_roundtrip_property(seed, bits):
    key = jax.random.PRNGKey(seed)
    q = jax.random.randint(key, (16, 8), -(2 ** (bits - 1)),
                           2 ** (bits - 1)).astype(jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(unpack_codes(pack_codes(q, bits), bits)), np.asarray(q))


def _packed(key, K, N, bits, k=3):
    w = jax.random.normal(key, (K, N)) * 0.1
    w = w.at[0, 0].set(2.0)
    sq = splitquant_tensor(key, w, QuantConfig(bits=bits), k=k)
    return ops.pack_for_kernel(sq), sq


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(8, 512, 256), (16, 1024, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(bits, shape, dtype):
    M, K, N = shape
    (qp, cp, recip, shift), _ = _packed(KEY, K, N, bits)
    x = jax.random.normal(KEY, (M, K), dtype=dtype)
    y_ref = ref.splitquant_matmul_ref(x, qp, cp, recip, shift, bits)
    y_pal = ops.quantized_matmul(x, qp, cp, recip, shift, bits=bits, k=3,
                                 use_pallas=True, interpret=True,
                                 block_m=128, block_n=128, block_k=256)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xla_path_matches_ref(bits, dtype):
    """The serving path off the Pallas kernel (masked-sum cluster select)
    against the oracle's plain per-element gather."""
    (qp, cp, recip, shift), _ = _packed(KEY, 512, 256, bits)
    x = jax.random.normal(KEY, (8, 512), dtype=dtype)
    y_ref = ref.splitquant_matmul_ref(x, qp, cp, recip, shift, bits)
    y_xla = ops.quantized_matmul(x, qp, cp, recip, shift, bits=bits, k=3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y_xla, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [2, 4])
def test_kernel_matches_paper_three_layer_form(bits):
    (qp, cp, recip, shift), _ = _packed(KEY, 512, 256, bits)
    x = jax.random.normal(KEY, (8, 512))
    y_paper = ref.splitquant_matmul_paper(x, qp, cp, recip, shift, bits, k=3)
    y_pal = ops.quantized_matmul(x, qp, cp, recip, shift, bits=bits, k=3,
                                 use_pallas=True, interpret=True,
                                 block_m=128, block_n=128, block_k=256)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_paper),
                               rtol=1e-4, atol=1e-4)


def test_kernel_padding_path():
    """M/N/K not multiples of the block sizes exercise the padding logic."""
    (qp, cp, recip, shift), _ = _packed(KEY, 384, 200, 4)
    x = jax.random.normal(KEY, (5, 384))
    y_ref = ref.splitquant_matmul_ref(x, qp, cp, recip, shift, 4)
    y_pal = ops.quantized_matmul(x, qp, cp, recip, shift, bits=4, k=3,
                                 use_pallas=True, interpret=True,
                                 block_m=128, block_n=128, block_k=256)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_linear_dispatch_quantized_vs_dense():
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (256, 128)) * 0.1
    sq = splitquant_tensor(key, w, QuantConfig(bits=8), k=3)
    x = jax.random.normal(key, (4, 256))
    y_q = ops.linear(x, sq)
    y_d = x @ np.asarray(sq.dequantize())
    np.testing.assert_allclose(np.asarray(y_q), y_d, rtol=1e-4, atol=1e-4)


def test_k1_baseline_through_kernel():
    """k=1 (plain PTQ) must flow through the same kernel."""
    from repro.core import baseline_quant_tensor
    key = jax.random.PRNGKey(2)
    w = jax.random.normal(key, (512, 256))
    bl = baseline_quant_tensor(w, QuantConfig(bits=8))
    qp, cp, recip, shift = ops.pack_for_kernel(bl)
    x = jax.random.normal(key, (8, 512))
    y = ops.quantized_matmul(x, qp, cp, recip, shift, bits=8, k=1,
                             use_pallas=True, interpret=True,
                             block_m=128, block_n=128, block_k=256)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x @ bl.dequantize()),
                               rtol=1e-3, atol=1e-3)


def test_batched_input_reshape():
    (qp, cp, recip, shift), _ = _packed(KEY, 256, 128, 4)
    x = jax.random.normal(KEY, (2, 3, 256))
    y = ops.quantized_matmul(x, qp, cp, recip, shift, bits=4, k=3)
    assert y.shape == (2, 3, 128)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("n_stack", [0, 3])
def test_packed_leaf_matches_per_call_packing(bits, k, n_stack):
    """A weight packed once (`pack_weights`) gives `linear` the product of
    the SplitQuantTensor packed on every call bit for bit, on a 2-D leaf
    and on each (K, N) slice a layer scan takes of a stacked (L, K, N)
    leaf; its dense view (`materialize`) is the SplitQuantTensor's ŵ."""
    from repro.models.common import materialize

    key = jax.random.PRNGKey(10 * bits + k)
    K, N = 256, 128
    w = jax.random.normal(key, (n_stack, K, N) if n_stack else (K, N)) * 0.1
    sq = splitquant_tensor(key, w, QuantConfig(bits=bits), k=k,
                           stack_dims=1 if n_stack else 0)
    tree, counts = ops.pack_weights({"w": sq, "norm": jnp.ones(N)})
    packed = tree["w"]
    assert isinstance(packed, ops.PackedSplitQuantTensor)
    L = max(n_stack, 1)
    assert packed.q_packed.shape == (n_stack,) * bool(n_stack) + (
        K * bits // 8, N)
    assert counts == (1, L * (K * bits // 8 + K // 4 + 2 * 4 * k) * N, 0)

    x = jax.random.normal(key, (4, K), jnp.bfloat16)
    if n_stack:
        def scan_linear(wt):
            return jax.lax.scan(lambda c, wl: (c, ops.linear(x, wl)), 0,
                                wt)[1]
        y_call, y_once = scan_linear(sq), scan_linear(packed)
    else:
        y_call, y_once = ops.linear(x, sq), ops.linear(x, packed)
    np.testing.assert_array_equal(np.asarray(y_once, np.float32),
                                  np.asarray(y_call, np.float32))
    # the packed leaf keeps the kernel's affine form q·(1/s) + (-z/s),
    # which equals (q - z)/s to float32 rounding
    np.testing.assert_allclose(np.asarray(materialize(packed)),
                               np.asarray(sq.dequantize()),
                               rtol=1e-6, atol=1e-6)
