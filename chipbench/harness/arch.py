"""Find a configuration's architecture module by name.

A configuration file names its architecture under ``"architecture"``;
a file that names none is a ``dense_decoder``. The module is
``architectures/<name>.py`` and exports the plain reference of the
served model (``derive_weights``, ``gaps``, ``logits``) and every count
that depends on the model's shape (the functions ``harness/counts.py``
hands on), and ``ASSUMED``: each field of the program's ``ArchConfig``
that its reference and counts do not model, with the one value they
assume (``spec.load_cell`` refuses a cell whose program differs).
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
DEFAULT = "dense_decoder"
#: what every architecture module exports
EXPORTS = ("derive_weights", "gaps", "logits", "weight_elements",
           "weight_bytes", "kv_bytes_per_token", "attn_flops", "token_flops",
           "decode_step_bytes", "decode_step_flops", "decode_attn_cost",
           "chunk_flops", "chunk_bytes", "prefill_attn_cost")
_NAME = re.compile(r"[A-Za-z0-9_]+")
_loaded: dict = {}


def name_of(conf: dict) -> str:
    return conf.get("architecture", DEFAULT)


def load(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``architectures/<name>.py``, imported once per process
    (its compiled reference is reused by every later call); a name that
    is not a plain word, a missing file or a missing export is an
    error that names the file."""
    if not _NAME.fullmatch(name):
        raise ValueError(f"architecture {name!r} is not a module name "
                         f"(letters, digits and _)")
    path = Path(bench_dir) / "architectures" / f"{name}.py"
    if path in _loaded:
        return _loaded[path]
    if not path.is_file():
        raise FileNotFoundError(f"architecture {name!r}: no module {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_architecture_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in EXPORTS if not callable(getattr(mod, n, None))]
    if not isinstance(getattr(mod, "ASSUMED", None), dict):
        missing.append("ASSUMED")
    if missing:
        raise AttributeError(f"architecture {name!r}: {path} does not "
                             f"export {', '.join(missing)}")
    _loaded[path] = mod
    return mod


def of(conf: dict):
    """The architecture module of a configuration file."""
    return load(name_of(conf))
