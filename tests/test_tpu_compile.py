"""Compile-only checks of the serving kernels for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot — block shapes off the (8, 128) tiling, scratch that does not fit
VMEM, layouts Mosaic cannot lower — with no chip. Shapes are
stablelm-1.6b's serving widths: 32 query and 32 KV heads of 64 dims, an
8-slot cache of 1024 positions, 96-token prefill chunks, 4 KV sub-channel
chunks (plus one grouped-query prefill case with 8 KV heads), and one
2048x5632 INT2 weight for the dequant-matmul. Each case also checks that
the Pallas kernel is in the compiled program, and each attention kernel
that it carries its own name, which a profiler trace shows.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.prefill_attention import prefill_attention
from repro.kernels.splitquant_matmul import splitquant_matmul

N, T, HQ, HKV, D, C, SQ = 8, 1024, 32, 32, 64, 4, 96
K, NOUT, M, BITS = 2048, 5632, 256, 2


@pytest.fixture(scope="module")
def one_chip():
    """One core of a described v5e:2x2, with the persistent compilation
    cache off (an entry written for a described chip cannot be read back
    without one) and the compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def kernel_names(text: str) -> list[str]:
    """The op_name of each Pallas call in a compiled program."""
    return [m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


DECODE_CASES = {
    "fp": (dict(mode="fp"), jnp.bfloat16, None),
    "int8-dynamic": (dict(mode="int8"), jnp.int8, (N, T, HKV, C)),
    "int8-static": (dict(mode="int8", per_entry_scales=False), jnp.int8,
                    (1, 1, HKV, C)),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_attention_compiles(one_chip, case):
    kw, kv_dtype, sshape = DECODE_CASES[case]
    shapes = [((N, HQ, D), jnp.bfloat16), ((N, T, HKV, D), kv_dtype),
              ((N, T, HKV, D), kv_dtype), ((N, T), jnp.int32),
              ((N,), jnp.int32)]
    if sshape is None:
        def fn(q, k, v, kv_pos, q_pos):
            return decode_attention(q, k, v, kv_pos, q_pos, use_pallas=True,
                                    **kw)
    else:
        shapes += [(sshape, jnp.float32)] * 4

        def fn(q, k, v, kv_pos, q_pos, ks, kz, vs, vz):
            return decode_attention(q, k, v, kv_pos, q_pos, k_scale=ks,
                                    k_zero=kz, v_scale=vs, v_zero=vz,
                                    use_pallas=True, **kw)
    names = kernel_names(compiled_text(fn, one_chip, *shapes))
    assert names and all(n.endswith("/decode_attention/pallas_call")
                         for n in names), names


PREFILL_CASES = {
    "fp": (dict(mode="fp"), jnp.bfloat16, None, HKV),
    "int8-dynamic": (dict(mode="int8"), jnp.int8, (T, HKV, C), HKV),
    "int8-dynamic-verify": (dict(mode="int8", verify=True), jnp.int8,
                            (T, HKV, C), HKV),
    "int8-static": (dict(mode="int8", per_entry_scales=False), jnp.int8,
                    (HKV, C), HKV),
    # grouped queries (4 per KV head): the kernel divides query rows by G
    "fp-gqa": (dict(mode="fp"), jnp.bfloat16, None, HKV // 4),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_prefill_attention_compiles(one_chip, case):
    kw, kv_dtype, sshape, hkv = PREFILL_CASES[case]
    shapes = [((SQ, HQ, D), jnp.bfloat16), ((SQ, hkv, D), jnp.bfloat16),
              ((SQ, hkv, D), jnp.bfloat16), ((T, hkv, D), kv_dtype),
              ((T, hkv, D), kv_dtype), ((T,), jnp.int32), ((), jnp.int32),
              ((), jnp.int32)]
    if sshape is None:
        def fn(q, kn, vn, ck, cv, kv_pos, pos_start, length):
            return prefill_attention(q, kn, vn, ck, cv, kv_pos, pos_start,
                                     length, use_pallas=True, **kw)
    else:
        shapes += [(sshape, jnp.float32)] * 4

        def fn(q, kn, vn, ck, cv, kv_pos, pos_start, length, ks, kz, vs,
               vz):
            return prefill_attention(q, kn, vn, ck, cv, kv_pos, pos_start,
                                     length, k_scale=ks, k_zero=kz,
                                     v_scale=vs, v_zero=vz, use_pallas=True,
                                     **kw)
    names = kernel_names(compiled_text(fn, one_chip, *shapes))
    assert names and all(n.endswith("/prefill_attention/pallas_call")
                         for n in names), names


def test_splitquant_matmul_compiles(one_chip):
    def fn(x, q_packed, cid_packed, recip, shift):
        return splitquant_matmul(x, q_packed, cid_packed, recip, shift,
                                 bits=BITS, k=3)
    text = compiled_text(fn, one_chip, ((M, K), jnp.bfloat16),
                         ((K * BITS // 8, NOUT), jnp.uint8),
                         ((K // 4, NOUT), jnp.uint8),
                         ((3, NOUT), jnp.float32), ((3, NOUT), jnp.float32))
    assert "tpu_custom_call" in text
