"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell against the production mesh, with no real device allocation
(ShapeDtypeStruct stand-ins), and extract the roofline terms.

MUST set the forced device count before ANY jax import side effects.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse      # noqa: E402
import functools     # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                          # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_arch, cell_is_runnable  # noqa: E402
from repro.core import QuantConfig, QuantPolicy, quantize_tree  # noqa: E402
from repro.models import get_model, init_cache_for  # noqa: E402
from repro.models.transformer import VLM_PATCH_DIM  # noqa: E402
from repro.optim import adamw                    # noqa: E402
from .mesh import data_axes, make_production_mesh  # noqa: E402
from .shardings import (batch_shardings, cache_shardings, opt_shardings,
                        param_shardings)         # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# ----------------------------------------------------------- input specs --
def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = get_arch(arch)
    shp = SHAPES[shape_name]
    B, S = shp.global_batch, shp.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    if shp.kind in ("train", "prefill"):
        batch = {"tokens": sds((B, S), i32)}
        if shp.kind == "train":
            batch["labels"] = sds((B, S), i32)
        if cfg.family == "vlm":
            P_img = cfg.n_prefix_embeds
            batch["tokens"] = sds((B, S - P_img), i32)
            if shp.kind == "train":
                batch["labels"] = sds((B, S - P_img), i32)
            batch["patch_embeds"] = sds((B, P_img, VLM_PATCH_DIM), bf16)
        if cfg.family == "audio":
            batch["frames"] = sds((B, cfg.enc_seq, cfg.d_model), bf16)
        return batch
    # decode: one new token against a cache of S positions
    return {"tokens": sds((B, 1), i32), "pos": sds((), i32)}


def abstract_params(cfg, quantized: bool, bits: int = 4):
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(functools.partial(model.init, cfg=cfg), key)
    if quantized:
        policy = QuantPolicy(cfg=QuantConfig(bits=bits), method="splitquant")
        params = jax.eval_shape(
            lambda p: quantize_tree(key, p, policy)[0], params)
    return params


def abstract_cache(cfg, B, S):
    return jax.eval_shape(
        functools.partial(init_cache_for, cfg, B, S))


# ------------------------------------------------------------- HLO stats --
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _tensor_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-op output bytes, summed from the post-SPMD per-device
    module. `-start` variants counted once (their `-done` pair is skipped)."""
    totals = {op: 0 for op in _COLL_OPS}
    counts = {op: 0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        for op in _COLL_OPS:
            if f" {op}(" in line or f" {op}-start(" in line:
                lhs = line.split(f" {op}")[0]
                b = sum(_tensor_bytes(d, s)
                        for d, s in _SHAPE_RE.findall(lhs))
                totals[op] += b
                counts[op] += 1
                break
    return {"bytes": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


# -------------------------------------------------------------- lowering --
def build_step(cfg, shape_name: str, mesh, quantized: bool,
               opt_dtype: str = "bfloat16", bits: int = 4,
               kv_chunk_train: int = 1024, kv_chunk_prefill: int = 2048,
               serve_fsdp: bool | None = None):
    """Returns (jitted_fn, abstract_args).

    serve_fsdp: None ⇒ FSDP weights for bf16 serving, TP-only for
    quantized serving (the low-bit residency the paper enables).
    """
    model = get_model(cfg)
    shp = SHAPES[shape_name]
    B, S = shp.global_batch, shp.seq_len
    batch = input_specs(cfg.name, shape_name)
    params = abstract_params(cfg, quantized and shp.kind != "train",
                             bits=bits)
    dp_size = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if shp.kind == "train":
        p_sh = param_shardings(params, mesh)
    else:
        fsdp = (not quantized) if serve_fsdp is None else serve_fsdp
        p_sh = param_shardings(params, mesh, fsdp=fsdp)
    b_sh = batch_shardings(batch, mesh)

    if shp.kind == "train":
        opt_cfg = adamw.OptConfig(state_dtype=opt_dtype)
        opt_state = jax.eval_shape(
            functools.partial(adamw.init, opt_cfg), params)
        o_sh = opt_shardings(opt_state, p_sh, mesh)

        def loss_fn(p, b):
            return model.loss_fn(p, cfg, b, kv_chunk=kv_chunk_train,
                                 remat=True, moe_blocks=dp_size)

        step = train_loop_step(loss_fn, opt_cfg)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     donate_argnums=(0, 1))
        return fn, (params, opt_state, batch)

    if shp.kind == "prefill":
        def fn(p, b):
            kw = {"moe_blocks": dp_size} if cfg.family in ("moe", "dense",
                                                           "vlm") else {}
            return model.prefill(p, cfg, b, max_len=S,
                                 kv_chunk=kv_chunk_prefill, **kw)
        jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
        return jfn, (params, batch)

    # decode
    cache = abstract_cache(cfg, B, S)
    c_sh = cache_shardings(cache, mesh)
    tok_sh = batch_shardings({"tokens": batch["tokens"]}, mesh)["tokens"]
    rep = NamedSharding(mesh, P())
    tp = mesh.shape.get("model", 1)
    # time-sharded ring decode: cache T over "model" when kv heads can't be
    use_tshard = (cfg.family in ("dense", "moe", "vlm") and S >= 16384 and
                  S % tp == 0 and cfg.n_kv_heads < tp)

    def fn(p, c, t, pos):
        if cfg.family in ("dense", "moe", "vlm"):
            return model.decode_step(p, cfg, c, t, pos, tshard=use_tshard)
        return model.decode_step(p, cfg, c, t, pos)

    jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh, rep),
                  donate_argnums=(1,))
    return jfn, (params, cache, batch["tokens"], batch["pos"])


def train_loop_step(loss_fn, opt_cfg):
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, om = adamw.update(opt_cfg, opt_state, params,
                                             grads)
        return params, opt_state, {**metrics, **om}
    return step


def run_cell(arch: str, shape_name: str, multi_pod: bool, quantized: bool,
             opt_dtype: str = "bfloat16", bits: int = 4,
             save: bool = True, verbose: bool = True,
             kv_chunk_train: int = 1024,
             kv_chunk_prefill: int = 2048,
             tag: str = "") -> dict:
    cfg = get_arch(arch)
    shp = SHAPES[shape_name]
    ok, reason = cell_is_runnable(cfg, shp)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "quantized": quantized, "status": "skip", "reason": reason}
    if not ok:
        if verbose:
            print(f"[skip] {arch} × {shape_name}: {reason}")
        if save:
            _save(result, tag)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(mesh):
            fn, args = build_step(cfg, shape_name, mesh, quantized,
                                  opt_dtype=opt_dtype, bits=bits,
                                  kv_chunk_train=kv_chunk_train,
                                  kv_chunk_prefill=kv_chunk_prefill)
            lowered = fn.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_text = compiled.as_text()
            coll = collective_bytes(hlo_text)
            from .hlo_analysis import analyze as hlo_analyze
            weighted = hlo_analyze(hlo_text)
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "n_devices": mesh.size,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes":
                    getattr(mem, "generated_code_size_in_bytes", None),
            },
            "flops_xla_raw": cost.get("flops"),
            "bytes_accessed_xla_raw": cost.get("bytes accessed"),
            "collectives_raw": coll,
            "dot_flops": weighted["dot_flops"],
            "dot_bytes": weighted["dot_bytes"],
            "collectives": {
                "bytes": weighted["collective_bytes"],
                "counts": weighted["collective_counts"],
                "total_bytes": weighted["collective_total_bytes"],
                "f32_bytes": weighted["collective_f32_bytes"],
                "total_bytes_tpu": weighted["collective_total_bytes_tpu"]},
        })
        if quantized:
            result["bits"] = bits
        if verbose:
            print(f"[ok] {arch} × {shape_name} × {mesh_name}"
                  f"{' ×int'+str(bits) if quantized else ''}: "
                  f"lower {t_lower:.0f}s compile {t_compile:.0f}s  "
                  f"dotflops/dev {weighted['dot_flops']:.3e}  "
                  f"coll {weighted['collective_total_bytes']/2**20:.1f} "
                  f"MiB/dev")
    except Exception as e:  # noqa: BLE001 — dry-run failures are data
        result.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]})
        if verbose:
            print(f"[ERROR] {arch} × {shape_name} × {mesh_name}: "
                  f"{type(e).__name__}: {str(e)[:300]}")
    if save:
        _save(result, tag)
    return result


def _save(result: dict, tag: str = ""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    q = "_q" + str(result.get("bits", "")) if result.get("quantized") else ""
    t = f"_{tag}" if tag else ""
    name = (f"{result['arch']}_{result['shape']}_{result['mesh']}{q}{t}"
            ".json")
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--opt-dtype", default="bfloat16")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from repro.configs import ASSIGNED
    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, args.quantized,
                             opt_dtype=args.opt_dtype, bits=args.bits,
                             tag=args.tag)
                n_err += r["status"] == "error"
    print(f"done; {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
