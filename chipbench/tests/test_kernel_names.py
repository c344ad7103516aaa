"""The attention kernels' readers find their kernel by its Pallas name.
On a one-second window of stablelm-1.6b.decode recorded on a TPU v5e
after the kernels were named (decode_1s_scoped.xplane.pb.gz) they read
what any ``tpu_custom_call`` in the executable read there; another
Pallas call in the same executable is not counted; and on the window
recorded before the names (decode_1s.xplane.pb.gz) they read nothing."""
import dataclasses
import gzip
import importlib.util
import json

import pytest
from conftest import BENCH_DIR

from harness import counts, loop, trace

DATA = BENCH_DIR / "tests" / "data"
#: reader -> the executable its kernel runs in
READERS = {"decode_attn_ms": "step", "decode_attn_roofline": "step",
           "prefill_attn_ms": "chunk", "prefill_attn_roofline": "chunk"}
ANY_PALLAS_CALL = r"tpu_custom_call"


def _summary(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    return trace.summarize(trace.load(str(path)))


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return _summary("decode_1s_scoped", tmp_path_factory)


@pytest.fixture(scope="module")
def unnamed(tmp_path_factory):
    return _summary("decode_1s", tmp_path_factory)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"kernel_reader_{name}", BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(summary):
    """The recorded window with one decode call of 16 slots 700 deep and
    one 96-token chunk at 1024 for the rooflines' counts."""
    with open(BENCH_DIR / "configs" / "stablelm-1.6b.json") as f:
        conf = json.load(f)
    win = loop.Window(t0=0.0, t1=1.0, decode_calls=[(0.5, [699] * 16)],
                      chunk_calls=[(0.5, 1024, 96)])
    return loop.Run(conf=conf, traffic={}, n_slots=16, setup_s=0.0, win=win,
                    peaks=counts.peaks("TPU v5 lite"), trace=summary)


def _with_other_pallas_call(summary, fn):
    """The summary with one more Pallas call, of another name, lasting
    1 ms inside the first run of jit_`fn`."""
    _, s, _ = next(m for m in summary.modules
                   if m[0].startswith(f"jit_{fn}("))
    extra = ("custom-call tpu_custom_call %splitquant_matmul.3", s, s + 10**6)
    return dataclasses.replace(summary, ops=summary.ops + [extra])


@pytest.mark.parametrize("name", sorted(READERS))
def test_by_name_reads_what_any_pallas_call_read(scoped, name):
    mod = _reader(name)
    by_name = mod.read(_run(scoped))
    assert by_name is not None and by_name > 0
    fn = READERS[name]
    assert scoped.ops_in_module(fn, mod.KERNEL) \
        == scoped.ops_in_module(fn, ANY_PALLAS_CALL)
    mod.KERNEL = ANY_PALLAS_CALL
    assert mod.read(_run(scoped)) == by_name


@pytest.mark.parametrize("name", sorted(READERS))
def test_another_pallas_call_is_not_counted(scoped, name):
    mod = _reader(name)
    want = mod.read(_run(scoped))
    other = _with_other_pallas_call(scoped, READERS[name])
    assert mod.read(_run(other)) == want
    mod.KERNEL = ANY_PALLAS_CALL
    assert mod.read(_run(other)) != want


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_kernel_names_no_reading(unnamed, name):
    assert _reader(name).read(_run(unnamed)) is None
