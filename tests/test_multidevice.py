"""Multi-device numeric correctness (subprocess with 8 forced host devices
— XLA device count locks at first jax init, so these cannot run in the
main pytest process):

  * tshard ring decode attention == single-device decode logits,
  * sharded quantized serve step == unsharded,
  * tp_dense == dense under a real (2,4) mesh.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_tshard_ring_decode_matches_dense():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models.attention import attend, tshard_decode_attend
        mesh = make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(0)
        B, T, Hq, Hkv, D = 4, 32, 8, 2, 16
        q = jax.random.normal(key, (B, 1, Hq, D))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, D))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, D))
        kv_pos = jnp.where(jnp.arange(T) < 20, jnp.arange(T), -1)
        q_pos = jnp.asarray([19])
        ref = attend(q, k, v, q_pos, kv_pos, causal=True)
        with jax.set_mesh(mesh):
            ring = jax.jit(lambda *a: tshard_decode_attend(*a))(
                q, k, v, q_pos, kv_pos)
        err = float(jnp.abs(ring - ref).max())
        assert err < 1e-4, err
        print("ring-decode ok", err)
    """)
    assert "ring-decode ok" in out


@pytest.mark.slow
def test_sharded_quantized_serve_matches_unsharded():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.models import get_model
        from repro.core import QuantConfig, QuantPolicy, quantize_tree
        from repro.launch.mesh import make_mesh
        from repro.launch.shardings import param_shardings, cache_shardings
        cfg = get_arch("chatglm3-6b").reduced()   # GQA kv=2 < tp
        model = get_model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key, cfg)
        qp, _ = quantize_tree(key, params, QuantPolicy(cfg=QuantConfig(bits=4)))
        toks = jax.random.randint(key, (8, 8), 0, cfg.vocab)
        logits0, cache = model.prefill(qp, cfg, {"tokens": toks}, max_len=16)
        ref, _ = model.decode_step(qp, cfg, cache, toks[:, :1], jnp.int32(8))
        mesh = make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            p_sh = param_shardings(qp, mesh, fsdp=False)
            c_sh = cache_shardings(cache, mesh)
            qp_s = jax.device_put(qp, p_sh)
            cache_s = jax.device_put(cache, c_sh)
            got, _ = jax.jit(lambda p, c, t: model.decode_step(
                p, cfg, c, t, jnp.int32(8), tshard=True))(
                qp_s, cache_s, toks[:, :1])
        err = float(jnp.abs(got - ref).max())
        rel = err / (float(jnp.abs(ref).max()) + 1e-9)
        assert rel < 1e-3, (err, rel)
        print("sharded serve ok", rel)
    """)
    assert "sharded serve ok" in out


@pytest.mark.slow
def test_tp_dense_matches_dense():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.models.common import tp_dense, dense
        mesh = make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (8, 6, 32))
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
        with jax.set_mesh(mesh):
            got = jax.jit(lambda x, w: tp_dense(x, w))(x, w)
        ref = dense(x, w)
        err = float(jnp.abs(got - ref).max())
        assert err < 1e-4, err
        print("tp_dense ok", err)
    """)
    assert "tp_dense ok" in out


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded_loss():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.models import get_model
        from repro.launch.mesh import make_mesh
        from repro.launch.shardings import param_shardings, batch_shardings
        cfg = get_arch("moonshot-v1-16b-a3b").reduced()
        model = get_model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key, cfg)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab)}
        ref, _ = model.loss_fn(params, cfg, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            p_sh = param_shardings(params, mesh)
            b_sh = batch_shardings(batch, mesh)
            p = jax.device_put(params, p_sh)
            b = jax.device_put(batch, b_sh)
            got, _ = jax.jit(lambda p, b: model.loss_fn(
                p, cfg, b, moe_blocks=2))(p, b)
        err = abs(float(got) - float(ref))
        assert err < 5e-3, (float(got), float(ref))
        print("sharded train ok", err)
    """)
    assert "sharded train ok" in out


@pytest.mark.slow
def test_elastic_restart_across_mesh_shapes():
    """Fault-tolerance + elasticity: checkpoint on a (1,8) mesh, restore
    and continue on a (2,4) mesh — checkpoints are mesh-independent."""
    out = run_sub("""
        import tempfile, jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.models import get_model
        from repro.optim import adamw
        from repro.checkpoint import ckpt
        from repro.launch.mesh import make_mesh
        from repro.launch.shardings import param_shardings, batch_shardings, opt_shardings
        from repro.data import DataConfig, synthetic_lm_batch

        cfg = get_arch("stablelm-1.6b").reduced()
        model = get_model(cfg)
        key = jax.random.PRNGKey(0)
        opt_cfg = adamw.OptConfig(lr=1e-3, warmup_steps=0)
        dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8)

        def make_step(mesh, p_sh, o_sh, b_sh):
            def step(p, o, b):
                (l, _), g = jax.value_and_grad(
                    lambda pp: model.loss_fn(pp, cfg, b), has_aux=True)(p)
                p, o, _ = adamw.update(opt_cfg, o, p, g)
                return p, o, l
            return jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))

        with tempfile.TemporaryDirectory() as d:
            mesh1 = make_mesh((1, 8), ("data", "model"))
            with jax.set_mesh(mesh1):
                params = model.init(key, cfg)
                p_sh = param_shardings(params, mesh1)
                params = jax.device_put(params, p_sh)
                opt = jax.device_put(adamw.init(opt_cfg, params),
                                     opt_shardings(adamw.init(opt_cfg, params), p_sh, mesh1))
                b_sh = batch_shardings(synthetic_lm_batch(dc, 0), mesh1)
                step = make_step(mesh1, p_sh, opt_shardings(opt, p_sh, mesh1), b_sh)
                for s in range(3):
                    params, opt, loss = step(params, opt, jax.device_put(synthetic_lm_batch(dc, s), b_sh))
                ckpt.save(d, 3, (params, opt))
                loss_mesh1 = float(step(params, opt, jax.device_put(synthetic_lm_batch(dc, 3), b_sh))[2])

            # "new fleet": different mesh shape
            mesh2 = make_mesh((2, 4), ("data", "model"))
            with jax.set_mesh(mesh2):
                like = (model.init(key, cfg), adamw.init(opt_cfg, model.init(key, cfg)))
                p_sh2 = param_shardings(like[0], mesh2)
                o_sh2 = opt_shardings(like[1], p_sh2, mesh2)
                (params2, opt2), st = ckpt.restore(d, like, shardings=(p_sh2, o_sh2))
                assert st == 3
                b_sh2 = batch_shardings(synthetic_lm_batch(dc, 0), mesh2)
                step2 = make_step(mesh2, p_sh2, o_sh2, b_sh2)
                loss_mesh2 = float(step2(params2, opt2, jax.device_put(synthetic_lm_batch(dc, 3), b_sh2))[2])
        err = abs(loss_mesh1 - loss_mesh2)
        assert err < 1e-3, (loss_mesh1, loss_mesh2)
        print("elastic restart ok", err)
    """)
    assert "elastic restart ok" in out
