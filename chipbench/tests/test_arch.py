"""A configuration's architecture: the program fields a file states reach
the program's ArchConfig, the counts the metric readers call come from
the file's architecture module and read as they did when they were the
harness's own, and ``load_cell`` refuses a file it cannot build as
stated before anything is built."""
import dataclasses
import inspect
import json
import shutil

import pytest
from conftest import BENCH_DIR

from harness import arch, build, counts, spec

ROOT = BENCH_DIR.parent

#: the ArchConfig each cell's configuration file gave before the harness
#: copied every program field (the registry entry's own name and source)
ARCH_CONFIGS = {
    "stablelm-1.6b": {
        "name": "stablelm-1.6b", "family": "dense", "n_layers": 24,
        "d_model": 2048, "n_heads": 32, "n_kv_heads": 32, "d_ff": 5632,
        "vocab": 100352, "n_experts": 0, "top_k": 0, "n_shared_experts": 0,
        "first_k_dense": 0, "dense_d_ff": 0, "capacity_factor": 1.25,
        "rope_variant": "half", "rope_theta": 10000.0, "window": None,
        "head_dim_override": 0, "ffn_type": "swiglu", "block_pattern": (),
        "conv_width": 4, "lru_width": 0, "rwkv_head_dim": 64,
        "n_enc_layers": 0, "enc_seq": 1500, "stub_frontend": False,
        "n_prefix_embeds": 0, "tie_embeddings": False, "norm_type": "layer",
        "param_dtype": "bfloat16", "bias": False,
        "source": "hf:stabilityai/stablelm-2-1_6b"},
    "chatglm3-6b": {
        "name": "chatglm3-6b", "family": "dense", "n_layers": 7,
        "d_model": 4096, "n_heads": 32, "n_kv_heads": 2, "d_ff": 13696,
        "vocab": 65024, "n_experts": 0, "top_k": 0, "n_shared_experts": 0,
        "first_k_dense": 0, "dense_d_ff": 0, "capacity_factor": 1.25,
        "rope_variant": "half", "rope_theta": 10000.0, "window": None,
        "head_dim_override": 0, "ffn_type": "swiglu", "block_pattern": (),
        "conv_width": 4, "lru_width": 0, "rwkv_head_dim": 64,
        "n_enc_layers": 0, "enc_seq": 1500, "stub_frontend": False,
        "n_prefix_embeds": 0, "tie_embeddings": False, "norm_type": "rms",
        "param_dtype": "bfloat16", "bias": False,
        "source": "arXiv:2406.12793"},
}


def conf(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(ARCH_CONFIGS))
def test_arch_config_of_the_cells(name):
    cfg = build.arch_config(conf(name))
    want = ARCH_CONFIGS[name]
    got = dataclasses.asdict(cfg)
    assert set(got) == set(want)
    for field, value in want.items():
        assert got[field] == value, field
    assert cfg.head_dim == conf(name)["head_dim"]


def test_arch_config_copies_any_program_field():
    c = dict(conf("stablelm-1.6b"), capacity_factor=2.0, window=512,
             block_pattern=["attn", "attn"], head_dim=128)
    cfg = build.arch_config(c)
    assert (cfg.capacity_factor, cfg.window, cfg.block_pattern) == \
        (2.0, 512, ("attn", "attn"))
    assert cfg.head_dim_override == 128 and cfg.head_dim == 128
    assert cfg.source == "hf:stabilityai/stablelm-2-1_6b"   # not the file's


# ------------------------------------------------------------------ counts --
POSITIONS = {"one": [0], "deep": [699] * 16,
             "spread": list(range(0, 2048, 128))}
CHUNKS = [(0, 96), (5, 3), (1024, 96), (1952, 96)]
KEYS = [1, 100, 2048]

#: what counts.py gave, when the counts were its own, for each cell's
#: configuration
COUNTS = {
    "stablelm-1.6b": {
        "weight_elements": 1438646272, "weight_bytes": 719327192.0,
        "kv_bytes_per_token": 147456,
        "attn_flops": [196608.0, 19660800.0, 402653184.0],
        "token_flops": [2877489152.0, 2896953344.0, 3279945728.0],
        "decode_step_bytes": {"one": 720027608.0, "deep": 2373660632.0,
                              "spread": 2989436888.0},
        "decode_step_flops": {"one": 2877489152.0, "deep": 48238690304.0,
                              "spread": 49059725312.0},
        "decode_attn_cost": {"one": (196608.0, 344064),
                             "deep": (2202009600.0, 1654652928),
                             "spread": (3023044608.0, 2270429184)},
        "chunk_flops": [238086520832.0, 7813922816.0, 257413873664.0,
                        274929287168.0],
        "chunk_bytes": [733876184.0, 720519128.0, 884871128.0,
                        1021710296.0],
        "prefill_attn_cost": [(915406848.0, 51904512),
                              (4128768.0, 2359296),
                              (20242759680.0, 202899456),
                              (37758173184.0, 339738624)]},
    "chatglm3-6b": {
        "weight_elements": 1693974528, "weight_bytes": 846988464.0,
        "kv_bytes_per_token": 4480,
        "attn_flops": [114688.0, 11468800.0, 234881024.0],
        "token_flops": [3388063744.0, 3399417856.0, 3622830080.0],
        "decode_step_bytes": {"one": 847128496.0, "deep": 897490096.0,
                              "spread": 916198576.0},
        "decode_step_flops": {"one": 3388063744.0, "deep": 55491690496.0,
                              "spread": 55970627584.0},
        "decode_attn_cost": {"one": (114688.0, 119168),
                             "deep": (1284505600.0, 52011008),
                             "spread": (1763442688.0, 70719488)},
        "chunk_flops": [275172818944.0, 9100902400.0, 286447108096.0,
                        296664432640.0],
        "chunk_bytes": [848204976.0, 847048880.0, 852792496.0,
                        856949936.0],
        "prefill_attn_cost": [(533987328.0, 12128256),
                              (2408448.0, 401408),
                              (11808276480.0, 16715776),
                              (22025601024.0, 20873216)]},
}


def _reading(c, fn):
    f = getattr(counts, fn)
    if fn in ("weight_elements", "weight_bytes", "kv_bytes_per_token"):
        return f(c)
    if fn in ("attn_flops", "token_flops"):
        return [f(c, k) for k in KEYS]
    if fn.startswith("decode"):
        return {n: f(c, p) for n, p in POSITIONS.items()}
    return [f(c, p, n) for p, n in CHUNKS]


@pytest.mark.parametrize("fn", sorted(COUNTS["stablelm-1.6b"]))
@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_unchanged(name, fn):
    """Every count, exactly as the harness gave it before it became the
    dense decoder's."""
    assert _reading(conf(name), fn) == COUNTS[name][fn]


def test_counts_come_from_the_module(monkeypatch):
    """counts.py hands each count to the configuration's module."""
    mod = arch.load(arch.DEFAULT)
    c = conf("chatglm3-6b")
    for fn in arch.EXPORTS[3:]:
        monkeypatch.setattr(mod, fn, lambda *a, fn=fn: (fn, a))
        f = getattr(counts, fn)
        args = [1] * (len(inspect.signature(f).parameters) - 1)
        assert f(c, *args) == (fn, (c, *args))


# --------------------------------------------------------------- refusals --
def test_the_cells_load():
    bench = spec.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        got = spec.load_cell(ROOT, BENCH_DIR, cell["name"])
        assert arch.name_of(got["config"]) == arch.DEFAULT


@pytest.fixture
def tree(tmp_path):
    """A checkout with the benchmark's traffic and architectures and one
    cell, whose configuration file each test writes."""
    bench_dir = tmp_path / "chipbench"
    for d in ("traffic", "architectures"):
        shutil.copytree(BENCH_DIR / d, bench_dir / d)
    (bench_dir / "configs").mkdir()
    bench = {"configs": [{"name": "c", "file": "chipbench/configs/c.json"}],
             "workloads": [{"name": "c.decode", "config": "c",
                            "traffic": "decode", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    def load(config, module=None):
        (bench_dir / "configs" / "c.json").write_text(json.dumps(config))
        if module is not None:
            (bench_dir / "architectures" / "partial.py").write_text(module)
        return spec.load_cell(tmp_path, bench_dir, "c.decode")
    return load


def test_refuses_an_unknown_key(tree):
    c = conf("stablelm-1.6b")
    with pytest.raises(ValueError, match="'n_expert'"):
        tree(dict(c, n_expert=8))
    # a field the dense decoder never reads, the harness's keys and
    # documentation
    got = tree(dict(c, capacity_factor=2.0, architecture="dense_decoder",
                    published={"n_layers": 24}))
    assert build.arch_config(got["config"]).capacity_factor == 2.0


@pytest.mark.parametrize("change", [
    {"n_experts": 8}, {"window": 512}, {"family": "moe"},
    {"dense_d_ff": 1024}, {"first_k_dense": 1}, {"tie_embeddings": True},
    # the registry entry's own fields count as well as the file's
    {"arch": "moonshot-v1-16b-a3b"}])
def test_refuses_a_field_the_module_does_not_model(tree, change):
    """The program would serve another model than the dense decoder's
    reference and counts describe."""
    with pytest.raises(ValueError, match="'dense_decoder' does not model"):
        tree(dict(conf("stablelm-1.6b"), **change))


def test_refuses_a_missing_module(tree):
    with pytest.raises(FileNotFoundError, match="no_such_arch.py"):
        tree(dict(conf("stablelm-1.6b"), architecture="no_such_arch"))
    with pytest.raises(ValueError, match="not a module name"):
        tree(dict(conf("stablelm-1.6b"), architecture="../configs/c"))


def test_refuses_a_module_short_of_the_contract(tree):
    module = ("def derive_weights(conf, seed):\n    pass\n\n\n"
              "def gaps(*a):\n    pass\n")
    with pytest.raises(AttributeError, match="partial.py does not export "
                                             "logits, weight_elements"):
        tree(dict(conf("stablelm-1.6b"), architecture="partial"), module)
