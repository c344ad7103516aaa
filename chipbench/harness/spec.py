"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own under the benchmark's directory:

    configs/<file named in BENCHMARK.json>   sizes, quantization, engine
    traffic/<mix>.json                       parameters of the generator
    metrics/<metric>.py                      ``read(run) -> float | None``

so a new cell needs new files and new ``BENCHMARK.json`` entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(root: Path, bench_dir: Path, workload: str) -> dict:
    """The cell's workload entry, configuration, traffic and the metrics it
    reports with tracing off (``end_to_end``) and on (``per_layer``)."""
    bench = load_benchmark(root)
    cell = _named(bench["workloads"], workload, "workload")
    conf_entry = _named(bench["configs"], cell["config"], "configuration")
    with open(root / conf_entry["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def metric_reader(bench_dir: Path, name: str):
    """``read`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
