"""Names a profiler trace reads: the ``op_name`` scopes of the serving
executables (``jax.named_scope`` in the model, the KV cache and the
dequant-matmul) and the engine's ``repro.*`` host spans, which share the
profiler's clock with the device's ops."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import QuantConfig, QuantPolicy, quantize_tree
from repro.engine import Engine, EngineConfig
from repro.engine.engine import _jitted_chunk_prefill, _jitted_entry_points
from repro.models import get_model

SCOPES = ("layers", "layer", "kv_write", "dequant_matmul", "lm_head")


@pytest.fixture(scope="module")
def tiny():
    """A reduced stablelm with SplitQuant INT2 weights and an int8 slot
    cache, as the chip benchmark serves it."""
    cfg = get_arch("stablelm-1.6b").reduced()
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    qparams, _ = quantize_tree(jax.random.PRNGKey(1), params, QuantPolicy(
        cfg=QuantConfig(bits=2), k=3, method="splitquant"))
    return cfg, qparams


def _op_names(compiled_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _compiled_op_names(cfg, eng, params, entry: str) -> list[str]:
    """op_names of the engine's decode ("step") or chunk executable,
    compiled on the CPU over ``params``."""
    if entry == "step":
        fn = _jitted_entry_points(cfg, True, True)[0]
        args = (params, eng.cache, jnp.zeros((2, 1), jnp.int32),
                jnp.zeros(2, jnp.int32))
    else:
        fn = _jitted_chunk_prefill(cfg)
        args = (params, eng.cache, jnp.zeros((1, 16), jnp.int32),
                jnp.int32(0), jnp.int32(0), jnp.int32(5))
    return _op_names(fn.lower(*args).compile().as_text())


def _under(name: str, scope: str) -> bool:
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", name) \
        is not None


@pytest.mark.parametrize("entry", ["step", "chunk"])
def test_executables_carry_scope_names(tiny, entry):
    """Lowered and compiled on the CPU, every scope the device-trace
    metrics read is in the executable's op_name metadata; the layer
    scan's own slicing lies under ``layers`` and outside ``layer``."""
    cfg, qparams = tiny
    eng = Engine(cfg, qparams, EngineConfig(
        n_slots=2, max_len=32, kv_mode="int8", prefill_chunk=16,
        prefill_bucket=8))
    names = _compiled_op_names(cfg, eng, eng.params, entry)
    assert any(n.startswith(f"jit({entry})/") for n in names)
    for scope in SCOPES + ("embed",):
        assert any(_under(n, scope) for n in names), scope
    assert any(_under(n, "layers") and not _under(n, "layer")
               for n in names)
    # the dequant-matmul of the head counts under both names
    assert any(_under(n, "lm_head") and _under(n, "dequant_matmul")
               for n in names)


@pytest.mark.parametrize("entry", ["step", "chunk"])
def test_served_steps_repack_no_weight(tiny, entry):
    """Over the weights the engine packed at start, every op of the
    dequant-matmul lies in ``jit(quantized_matmul)``: the per-call packing
    of the codes (shifts, ors and converts under ``dequant_matmul``
    outside that jit), which the unpacked tree still compiles, is gone."""
    cfg, qparams = tiny
    eng = Engine(cfg, qparams, EngineConfig(
        n_slots=2, max_len=32, kv_mode="int8", prefill_chunk=16,
        prefill_bucket=8))

    def packing(params):
        return [n for n in _compiled_op_names(cfg, eng, params, entry)
                if _under(n, "dequant_matmul")
                and "jit(quantized_matmul)" not in n]

    assert any(n.endswith("/shift_left") for n in packing(qparams))
    assert packing(eng.params) == []


def _host_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every host event named repro.*"""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def test_engine_spans_on_the_profiler_clock(tmp_path):
    """An untraced engine (EngineConfig.trace off) under the JAX
    profiler: repro.step encloses its decode's readback, repro.decode
    carries the active slots and each chunk its slot and positions."""
    cfg = get_arch("stablelm-1.6b").reduced()
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, max_len=48, max_new_tokens=4, kv_mode="int8",
        prefill_chunk=8, prefill_bucket=8))
    rng = np.random.default_rng(0)
    for n in (5, 12):
        eng.submit(rng.integers(0, cfg.vocab, size=n))
    eng.step()                                    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert {"repro.step", "repro.admit", "repro.decode",
            "repro.decode.stage", "repro.decode.dispatch",
            "repro.decode.readback", "repro.accept_commit",
            "repro.record", "repro.prefill_chunk",
            "repro.prefill_chunk.readback"} <= names
    steps = [(s, e) for n, s, e, _ in spans if n == "repro.step"]
    reads = [(s, e) for n, s, e, _ in spans if n == "repro.decode.readback"]
    assert reads and all(any(a <= s and e <= b for a, b in steps)
                         for s, e in reads)
    decodes = [st for n, _, _, st in spans if n == "repro.decode"]
    assert decodes and all(st.get("slots") in (1, 2) for st in decodes)
    chunk = next(st for n, _, _, st in spans if n == "repro.prefill_chunk")
    assert set(chunk) >= {"slot", "pos_start", "n"}


def test_traced_chunk_prefill_does_not_sync(monkeypatch):
    """Traced mode adds no block_until_ready: a chunk's device time comes
    from the device trace, so the chunk span records no wait_s."""
    cfg = get_arch("stablelm-1.6b").reduced()
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, max_len=48, max_new_tokens=3, kv_mode="int8",
        prefill_chunk=8, prefill_bucket=8, trace=True))
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    eng.submit(np.arange(20) % cfg.vocab)
    eng.drain()
    assert calls == []
    chunks = [r for r in eng.tracer.events if r.get("name") ==
              "prefill_chunk"]
    assert len(chunks) == 3                       # 20 tokens in chunks of 8
    assert all("wait_s" not in r and r["dispatch_s"] >= 0 for r in chunks)
    pa = eng.metrics()["phase_attribution"]
    assert pa["coverage"] >= 0.9
    # the decode's children are listed but not counted twice
    assert "decode.readback" in pa["phases"]
    assert pa["attributed_s"] <= pa["step_total_s"] * (1 + 1e-9)
