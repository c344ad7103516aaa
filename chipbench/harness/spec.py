"""Find a cell's configuration, architecture, traffic mix and metric
readers by name.

Everything that belongs to one configuration, one architecture, one
traffic mix or one metric is a file of its own under the benchmark's
directory:

    configs/<file named in BENCHMARK.json>   sizes, quantization, engine
    architectures/<name>.py                  plain reference and counts
                                             (``harness/arch.py``)
    traffic/<mix>.json                       parameters of the generator
    metrics/<metric>.py                      ``read(run) -> float | None``

so a new cell, and a new architecture, needs new files and new
``BENCHMARK.json`` entries only: one configuration file (naming its
module under ``"architecture"``, and stating any program field of the
program's ``ArchConfig``), one architecture module, a traffic mix and
metric readers.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from . import arch, build

BENCH_DIR = Path(__file__).resolve().parents[1]
#: configuration-file keys the harness reads itself
HARNESS_KEYS = {"arch", "quant", "kv_mode", "kv_qchunks", "kv_cache_tokens",
                "prefill_chunk", "prefill_bucket", "check", "architecture"}
#: configuration-file keys that document it and reach nothing
DOC_KEYS = {"name", "source", "reduced", "published", "deployment",
            "assumed", "departures"}


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
    check_config(config, conf_entry["file"], bench_dir)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def check_config(config: dict, file: str, bench_dir: Path) -> None:
    """Refuse, before anything is built, a configuration whose
    architecture module is missing or lacks a name of the contract, that
    has a key neither the program, the harness nor its documentation
    knows (a misspelt field would else build another model than the file
    states), or whose program sets a field the module does not model to
    another value than the one it assumes (its reference and counts would
    describe another model than the one served)."""
    name = arch.name_of(config)
    mod = arch.load(name, bench_dir)
    unknown = sorted(set(config)
                     - (build.program_keys() | HARNESS_KEYS | DOC_KEYS))
    if unknown:
        raise ValueError(f"{file}: no program field or harness key is "
                         f"named {', '.join(map(repr, unknown))}")
    program = build.arch_config(config)
    off = {k: getattr(program, k) for k, v in mod.ASSUMED.items()
           if getattr(program, k) != v}
    if off:
        raise ValueError(f"{file}: architecture {name!r} does not model "
                         + ", ".join(f"{k}={v!r} (it assumes "
                                     f"{mod.ASSUMED[k]!r})"
                                     for k, v in off.items()))


def metric_reader(bench_dir: Path, name: str):
    """``read`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
