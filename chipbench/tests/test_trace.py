"""The trace reduction: busy time, idle gaps with their host spans,
executable and kernel times, against a hand-made trace and against a
trace recorded on a TPU v5e."""
import gzip
import json

import pytest
from conftest import BENCH_DIR

from harness import trace

MS = 1_000_000


def synthetic():
    ops = {0: [("fusion.1", 0 * MS, 4 * MS), ("kernel_a", 3 * MS, 6 * MS),
               ("fusion.2", 10 * MS, 12 * MS), ("kernel_a", 20 * MS, 25 * MS),
               ("fusion.1", 40 * MS, 60 * MS)]}
    modules = {0: [("jit_step(1)", 0, 6 * MS), ("jit_chunk(2)", 10 * MS,
                                                 25 * MS),
                   ("jit_step(1)", 40 * MS, 60 * MS)]}
    host = [("chipbench.window", 2 * MS, 50 * MS),
            ("engine.step", 2 * MS, 30 * MS), ("clients", 30 * MS, 40 * MS),
            ("Wait", 12 * MS, 18 * MS)]
    return trace.Trace(ops, modules, host, (2 * MS, 50 * MS))


def test_busy_and_gaps():
    s = trace.summarize(synthetic())
    # busy inside [2, 50] ms: [2, 6] + [10, 12] + [20, 25] + [40, 50]
    assert s.window_s == pytest.approx(0.048)
    assert s.busy_s == pytest.approx(0.021)
    # holes by midpoint: [25, 40] clients, [12, 20] Wait, [6, 10] step
    assert s.gaps == [["clients", 0.015], ["Wait", 0.008],
                      ["engine.step", 0.004]]


def test_executables_and_kernels():
    s = trace.summarize(synthetic())
    assert s.module_runs("step") == pytest.approx([0.004, 0.010])
    assert s.module_runs("chunk") == pytest.approx([0.015])
    assert s.ops_in_module("chunk", "kernel_a") == (1, pytest.approx(0.005))
    assert s.ops_in_module("step", "kernel_a") == (1, pytest.approx(0.003))
    assert s.top_ops(2) == [["fusion.1", pytest.approx(0.012)],
                            ["kernel_a", pytest.approx(0.008)]]


def test_busy_intervals_merge():
    ev = [("a", 5, 9), ("b", 0, 3), ("c", 2, 4), ("d", 9, 10)]
    assert trace.busy_intervals(ev) == [(0, 4), (5, 10)]


DATA = BENCH_DIR / "tests" / "data"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A one-second traced window of stablelm-1.6b.decode on a TPU v5e
    (tests/data/decode_1s.xplane.pb.gz), with what the run itself counted
    (decode_1s.json)."""
    path = tmp_path_factory.mktemp("trace") / "decode_1s.xplane.pb"
    with gzip.open(DATA / "decode_1s.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    with open(DATA / "decode_1s.json") as f:
        meta = json.load(f)
    return trace.load(str(path)), meta


def test_recorded_window_and_busy(recorded):
    tr, meta = recorded
    s = trace.summarize(tr)
    lo, hi = tr.window
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.window_s >= meta["seconds"]
    # busy time: the union, checked by marking every microsecond
    us = bytearray(int((hi - lo) // 1000) + 1)
    for _, a, b in s.ops:
        us[(a - lo) // 1000:(b - lo) // 1000] = b"\x01" * len(
            us[(a - lo) // 1000:(b - lo) // 1000])
    assert s.busy_s == pytest.approx(sum(us) / 1e6, rel=0.01)
    assert 0 < s.busy_s <= s.window_s
    assert s.gaps and all(g[1] > 0 for g in s.gaps)


def test_recorded_executables_and_kernel(recorded):
    """Every decode step the run counted is one jit_step run on the
    device, and each runs the decode-attention Pallas call once a layer."""
    tr, meta = recorded
    s = trace.summarize(tr)
    runs = s.module_runs("step")
    assert abs(len(runs) - meta["decode_steps"]) <= 1
    n, secs = s.ops_in_module("step", r"tpu_custom_call")
    assert n == pytest.approx(len(runs) * meta["layers"], abs=meta["layers"])
    assert 0 < secs < sum(runs)
    top = s.top_ops(10)
    assert len(top) == 10 and not top[0][0].startswith("while")
