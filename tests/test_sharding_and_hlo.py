"""Sharding rules + HLO analyzer tests (single real device; the full-mesh
path is exercised by launch/dryrun.py which forces 512 host devices)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import QuantConfig, QuantPolicy, quantize_tree
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_local_mesh
from repro.launch.shardings import (batch_shardings, param_shardings,
                                    spec_for_param)
from repro.models import get_model

KEY = jax.random.PRNGKey(0)


class FakeMesh:
    shape = {"data": 16, "model": 16}
    axis_names = ("data", "model")


def test_spec_rules():
    m = FakeMesh()
    up = spec_for_param("layers/attn/wq",
                        jnp.zeros((4, 4096, 2048)), m)
    assert up == jax.sharding.PartitionSpec(None, "data", "model")
    down = spec_for_param("layers/ffn/w_down",
                          jnp.zeros((4, 8192, 4096)), m)
    assert down == jax.sharding.PartitionSpec(None, "model", "data")
    emb = spec_for_param("embed", jnp.zeros((32000, 4096)), m)
    assert emb == jax.sharding.PartitionSpec("model", "data")
    bias = spec_for_param("layers/ffn/b_up", jnp.zeros((4, 8192)), m)
    assert bias == jax.sharding.PartitionSpec(None, None)
    exp = spec_for_param("moe_layers/moe/w_gate",
                         jnp.zeros((4, 64, 2048, 1408)), m)
    assert exp == jax.sharding.PartitionSpec(None, "model", "data", None)


def test_divisibility_fallback():
    m = FakeMesh()
    odd = spec_for_param("layers/attn/wk", jnp.zeros((4, 4096, 384)), m)
    assert odd[-1] == "model"          # 384 % 16 == 0
    odd2 = spec_for_param("layers/attn/wk", jnp.zeros((4, 4096, 100)), m)
    assert odd2[-1] is None            # 100 % 16 != 0 → replicate


def test_param_shardings_cover_quantized_leaves():
    mesh = make_local_mesh()
    cfg = get_arch("stablelm-1.6b").reduced()
    model = get_model(cfg)
    params = model.init(KEY, cfg)
    qp, _ = quantize_tree(KEY, params, QuantPolicy(cfg=QuantConfig(bits=4)))
    sh = param_shardings(qp, mesh)
    # structure matches exactly
    jax.tree.map(lambda a, b: None, qp, sh)


def test_sharded_train_step_runs_on_local_mesh():
    """End-to-end jit with in_shardings on the 1×N local mesh."""
    from repro.optim import adamw
    from repro.launch.shardings import opt_shardings
    mesh = make_local_mesh()
    cfg = get_arch("stablelm-1.6b").reduced()
    model = get_model(cfg)
    with jax.set_mesh(mesh):
        params = model.init(KEY, cfg)
        p_sh = param_shardings(params, mesh)
        params = jax.device_put(params, p_sh)
        opt_cfg = adamw.OptConfig(lr=1e-3)
        opt_state = adamw.init(opt_cfg, params)
        o_sh = opt_shardings(opt_state, p_sh, mesh)
        batch = {"tokens": jax.random.randint(KEY, (4, 16), 0, cfg.vocab),
                 "labels": jax.random.randint(KEY, (4, 16), 0, cfg.vocab)}
        b_sh = batch_shardings(batch, mesh)

        def step(p, o, b):
            (l, _), g = jax.value_and_grad(
                lambda pp, bb: model.loss_fn(pp, cfg, bb),
                has_aux=True)(p, b)
            return adamw.update(opt_cfg, o, p, g)[0:2] + (l,)

        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))
        p2, o2, loss = fn(params, opt_state, batch)
        assert bool(jnp.isfinite(loss))


def test_hlo_analyzer_scan_trip_counts():
    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, None, length=10)
        return x
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    r = analyze(txt)
    expected = 10 * 2 * 64 * 128 * 128
    assert abs(r["dot_flops"] - expected) / expected < 1e-6


def test_hlo_analyzer_nested_scan():
    def f(x, w):
        def outer(x, _):
            def inner(x, _):
                return x @ w, None
            x, _ = jax.lax.scan(inner, x, None, length=3)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=5)
        return x
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    r = analyze(txt)
    expected = 15 * 2 * 32 * 64 * 64
    assert abs(r["dot_flops"] - expected) / expected < 1e-6


def test_hlo_analyzer_counts_unlooped_dots():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    txt = jax.jit(f).lower(a, b).compile().as_text()
    r = analyze(txt)
    assert abs(r["dot_flops"] - 2 * 128 * 256 * 64) < 1e-6 * 2**21
