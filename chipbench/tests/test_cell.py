"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: correct as served, not correct with a token altered where one slot
produces it, a sample with a request from every slot, the float8 control
failing the limit, a cell and an architecture added as new files only,
and the refusal to run without a TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from conftest import BENCH_DIR

import run
from harness import check, loop, spec

ROOT = BENCH_DIR.parent
SECONDS = 2.0


def tiny_cell(workload="tiny.tiny"):
    with open(BENCH_DIR / "tests" / "data" / "tiny.json") as f:
        conf = json.load(f)
    with open(BENCH_DIR / "tests" / "data" / "tiny_mix.json") as f:
        mix = json.load(f)
    bench = spec.load_benchmark(ROOT)
    return {"cell": {"name": workload, "chips": 1}, "config": conf,
            "traffic": mix, "end_to_end": bench["end_to_end"],
            "per_layer": []}


def test_tiny_cell_is_correct():
    """Correct as served, and every end-to-end reader finds its number."""
    res = run.run_cell(tiny_cell(), 2 ** 31 + 5, SECONDS, False,
                       jax.devices()[0])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 8
    bench = spec.load_benchmark(ROOT)
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_altered_token_is_caught(monkeypatch):
    """Every decode step serves the token after the one the model chose
    in slot 0: the check has to say not correct."""
    from repro.engine.engine import Engine

    dispatch = Engine._dispatch_decode

    def altered(self, n_active):
        toks = dispatch(self, n_active).copy()
        toks[0] = (toks[0] + 1) % self.cfg.vocab
        return toks

    monkeypatch.setattr(Engine, "_dispatch_decode", altered)
    res = run.run_cell(tiny_cell(), 11, SECONDS, False, jax.devices()[0])
    assert not res["correct"]
    assert res["checks"]["max_gap_sd"]["value"] > \
        res["checks"]["max_gap_sd"]["limit"]


def _served(client, slot, n, finished):
    return loop.Served(client, np.zeros(4, np.int32), 8, 0.0,
                       stamps=[0.0] * n, finished=finished, slot=slot)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 33 + 1])
def test_sample_takes_every_slot(seed):
    """The most-served request, then one from every other slot that
    served a token, a finished one where the slot has one; the same seed
    draws the same sample."""
    served = [_served(c, c % 4, n, f) for c, (n, f) in enumerate(
        [(5, True), (30, False), (7, False), (2, True), (9, True),
         (3, True), (4, False), (0, False), (6, False), (1, True)])]
    picked = check.sample(served, seed)
    assert picked[0] is served[1]
    assert sorted(s.slot for s in picked) == [0, 1, 2, 3]
    by_slot = {s.slot: s for s in picked}
    assert by_slot[0].finished and by_slot[3].finished
    assert by_slot[2].client in (2, 6)      # none finished there
    again = check.sample(served, seed)
    assert [s.client for s in again] == [s.client for s in picked]


def test_control_fails_the_limit():
    """The float8 control in the program's place comes out not correct by
    the harness's own verdict."""
    res = run.run_cell(tiny_cell(), 12, SECONDS, False, jax.devices()[0],
                       control=True)
    assert not res["correct"]
    c = res["checks"]["control_max_gap_sd"]
    assert c["value"] > c["limit"]
    assert "max_gap_sd" not in res["checks"]


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


WRAPPED = textwrap.dedent('''
    """The dense decoder under another name. Its reference says on
    standard error that it ran, and scales its gaps by GAP_SCALE."""
    import sys

    from harness import arch

    _dense = arch.load("dense_decoder")
    globals().update({n: getattr(_dense, n) for n in arch.EXPORTS})
    ASSUMED = _dense.ASSUMED
    GAP_SCALE = 1.0


    def gaps(conf, wts, seqs, rows, served, control, shape=None):
        print("wrapped_dense reference ran", file=sys.stderr)
        g, gc = _dense.gaps(conf, wts, seqs, rows, served, control, shape)
        return g * GAP_SCALE, gc
''')


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a traffic mix and a metric added as new files plus
    new BENCHMARK.json entries run without a change to any file that was
    there; so does a configuration of a new architecture: its module, a
    new file, decides `correct`, and a program field the harness never
    named (`capacity_factor`) reaches the program's ArchConfig."""
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace",
                                                  "tests"))
    before = _digest(tmp_path / "chipbench")
    data = BENCH_DIR / "tests" / "data"
    shutil.copy(data / "tiny.json", tmp_path / "chipbench" / "configs"
                / "dummy.json")
    with open(data / "tiny.json") as f:
        wrapped = dict(json.load(f), architecture="wrapped_dense",
                       capacity_factor=2.0)
    (tmp_path / "chipbench" / "configs" / "dummy_arch.json").write_text(
        json.dumps(wrapped))
    (tmp_path / "chipbench" / "architectures" / "wrapped_dense.py"
     ).write_text(WRAPPED)
    shutil.copy(data / "tiny_mix.json", tmp_path / "chipbench" / "traffic"
                / "dummy_mix.json")
    (tmp_path / "chipbench" / "metrics" / "dummy_requests.py").write_text(
        textwrap.dedent('''
            """Requests the clients sent, over the window's seconds."""


            def read(run):
                return len(run.win.served) / run.win.seconds
        '''))
    bench = spec.load_benchmark(ROOT)
    cells = ["dummy.dummy_mix", "dummy_arch.dummy_mix"]
    for name in ("dummy", "dummy_arch"):
        bench["configs"].append({"name": name, "source": "tiny",
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.dummy_mix",
                                   "config": name, "traffic": "dummy_mix",
                                   "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "dummy_requests", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": cells})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = textwrap.dedent(f'''
        import json, sys
        sys.path[:0] = [{str(tmp_path / "chipbench")!r}, {str(ROOT / "src")!r}]
        import jax, run
        from harness import arch, build, spec
        make_engine = build.make_engine

        def spy(cfg, *a):
            print("engine capacity_factor", cfg.capacity_factor,
                  file=sys.stderr)
            return make_engine(cfg, *a)

        build.make_engine = spy
        out = []
        for name in {cells!r}:
            cell = spec.load_cell(run.ROOT, run.BENCH_DIR, name)
            out.append(run.run_cell(cell, 3, {SECONDS}, False,
                                    jax.devices()[0]))
        arch.of(cell["config"]).GAP_SCALE = 1e6
        out.append(run.run_cell(cell, 3, {SECONDS}, False, jax.devices()[0]))
        print(json.dumps(out))
    ''')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    dense, wrapped, scaled = json.loads(p.stdout.strip().splitlines()[-1])
    for res in (dense, wrapped):
        assert res["correct"], res["checks"]
        assert set(res["metrics"]) == {"dummy_requests", "setup_s"}
    assert not scaled["correct"]
    assert scaled["checks"]["max_gap_sd"]["value"] \
        > scaled["checks"]["max_gap_sd"]["limit"]
    assert p.stderr.count("wrapped_dense reference ran") == 2
    assert p.stderr.count("engine capacity_factor 1.25") == 1
    assert p.stderr.count("engine capacity_factor 2.0") == 2
    after = _digest(tmp_path / "chipbench")
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("root", ["repo", "bare"])
def test_refuses_without_a_tpu(tmp_path, root):
    """No TPU: exit non-zero and print no result, in the repository and in
    a directory holding only BENCHMARK.json and the benchmark's files."""
    cwd = ROOT
    if root == "bare":
        shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      ".trace"))
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm-1.6b.decode", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
