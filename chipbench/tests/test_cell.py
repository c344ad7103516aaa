"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: correct as served, not correct with a token altered where it is
produced, the float8 control failing the limit, a cell added as new files
only, and the refusal to run without a TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest
from conftest import BENCH_DIR

import run
from harness import spec

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


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a traffic mix and a metric added as new files plus
    new BENCHMARK.json entries run without a change to any file that was
    there."""
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace",
                                                  "tests"))
    before = _digest(tmp_path / "chipbench")
    data = BENCH_DIR / "tests" / "data"
    shutil.copy(data / "tiny.json", tmp_path / "chipbench" / "configs"
                / "dummy.json")
    shutil.copy(data / "tiny_mix.json", tmp_path / "chipbench" / "traffic"
                / "dummy_mix.json")
    (tmp_path / "chipbench" / "metrics" / "dummy_requests.py").write_text(
        textwrap.dedent('''
            """Requests the clients sent, over the window's seconds."""


            def read(run):
                return len(run.win.served) / run.win.seconds
        '''))
    bench = spec.load_benchmark(ROOT)
    bench["configs"].append({"name": "dummy", "source": "tiny",
                             "file": "chipbench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.dummy_mix", "config": "dummy",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "dummy_requests", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["dummy.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = textwrap.dedent(f'''
        import json, sys
        sys.path[:0] = [{str(tmp_path / "chipbench")!r}, {str(ROOT / "src")!r}]
        import jax, run
        from harness import spec
        cell = spec.load_cell(run.ROOT, run.BENCH_DIR, "dummy.dummy_mix")
        res = run.run_cell(cell, 3, {SECONDS}, False, jax.devices()[0])
        print(json.dumps(res))
    ''')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"]
    assert set(res["metrics"]) == {"dummy_requests", "setup_s"}
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
