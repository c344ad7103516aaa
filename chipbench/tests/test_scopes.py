"""Device time by op_name scope and the engine's host spans, read from
the XPlane protobuf: against two one-second windows of
stablelm-1.6b.decode recorded on a TPU v5e, before the program named its
parts (decode_1s.xplane.pb.gz) and after (decode_1s_scoped.xplane.pb.gz),
and against hand-made readings for the arithmetic of the six readers."""
import gzip
import json

import pytest
from conftest import BENCH_DIR

from harness import loop, scopes, spec, trace

DATA = BENCH_DIR / "tests" / "data"
READERS = ("decode_layer_io_ms", "decode_kv_write_ms", "decode_dequant_ms",
           "decode_lm_head_ms", "decode_unscoped_share", "step_host_ms")


@pytest.fixture(scope="module")
def recorded_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(DATA / "decode_1s.xplane.pb.gz") as f:
        (d / "decode_1s.xplane.pb").write_bytes(f.read())
    return d


@pytest.fixture(scope="module")
def recorded(recorded_dir):
    return scopes.load(str(recorded_dir / "decode_1s.xplane.pb"))


@pytest.fixture(scope="module")
def scoped_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("scoped")
    with gzip.open(DATA / "decode_1s_scoped.xplane.pb.gz") as f:
        (d / "decode_1s_scoped.xplane.pb").write_bytes(f.read())
    return d


def test_recorded_runs_and_op_paths(recorded):
    """The runs inside the window are the ones the run counted, and the
    ops inside them add up to the runs' device time."""
    with open(DATA / "decode_1s.json") as f:
        meta = json.load(f)
    assert recorded.n_runs("step") == meta["decode_steps"]
    assert recorded.n_runs("chunk") == meta["chunk_calls"]
    step = sum(recorded.runs["step"]) / recorded.n_runs("step")
    assert step == pytest.approx(0.2137, abs=5e-4)
    ops = recorded.seconds_per_run("step", lambda p: True)
    assert 0.99 * step < ops <= step


def test_recorded_dequant_matmul_by_path(recorded):
    """The jit(quantized_matmul) ops: 27.1 ms per decode step inside the
    layer loop, 8.2 ms at the head."""
    def qmm(in_loop):
        return lambda p: ("jit(quantized_matmul)" in p
                          and scopes.under(p, "while") == in_loop)
    assert 1e3 * recorded.seconds_per_run("step", qmm(True)) \
        == pytest.approx(27.1, abs=0.05)
    assert 1e3 * recorded.seconds_per_run("step", qmm(False)) \
        == pytest.approx(8.2, abs=0.05)


def test_recorded_has_no_scopes_or_engine_spans(recorded):
    for scope in ("embed", "layers", "layer", "kv_write", "dequant_matmul",
                  "lm_head"):
        assert not recorded.has("step", scope)
    assert {s.name for s in recorded.spans} == {"engine.step", "clients"}


def test_under_matches_whole_components():
    p = "jit(step)/layers/while/body/closed_call/layer/kv_write/scatter"
    assert scopes.under(p, "layers") and scopes.under(p, "layer")
    assert scopes.under(p, "kv_write")
    assert not scopes.under("jit(step)/layers/while/body/add", "layer")
    assert scopes.under("jit(step)/vmap(lm_head)/dot", "lm_head")
    assert scopes.under("a/b:fusion;jit(step)/embed/gather", "embed")
    assert not scopes.under("jit(step)/embedding/gather", "embed")


def _run(summary):
    return loop.Run(conf={}, traffic={}, n_slots=16, setup_s=0.0,
                    win=loop.Window(), peaks={}, trace=summary)


def test_readers_return_none_before_the_names(recorded_dir, monkeypatch,
                                              capsys):
    """On the pre-scope trace each new reader says what is missing and
    returns None, never 0."""
    monkeypatch.setattr(scopes, "TRACE_DIR", recorded_dir)
    scopes._cache.clear()
    summary = trace.summarize(trace.load(
        str(recorded_dir / "decode_1s.xplane.pb")))
    run = _run(summary)
    for name in READERS:
        assert spec.metric_reader(BENCH_DIR, name)(run) is None, name
    err = capsys.readouterr().err
    assert "under the scope 'layer'" in err and "repro.step" in err
    assert spec.metric_reader(BENCH_DIR, "decode_step_ms")(run) > 0
    assert spec.metric_reader(BENCH_DIR, "decode_layer_io_ms")(
        _run(None)) is None                      # an untraced run


def test_readers_on_the_named_trace(scoped_dir, monkeypatch):
    """The six readers on a window recorded after the change read what
    the run that recorded it printed, and the parts add up: no sum of
    disjoint parts exceeds the decode step."""
    with open(DATA / "decode_1s_scoped.json") as f:
        meta = json.load(f)
    monkeypatch.setattr(scopes, "TRACE_DIR", scoped_dir)
    scopes._cache.clear()
    summary = trace.summarize(trace.load(
        str(scoped_dir / "decode_1s_scoped.xplane.pb")))
    run = _run(summary)
    got = {n: spec.metric_reader(BENCH_DIR, n)(run)
           for n in READERS + ("decode_step_ms",)}
    for name, value in meta["metrics"].items():
        assert got[name] == pytest.approx(value, abs=1e-4), name
    step = got["decode_step_ms"]
    assert got["decode_layer_io_ms"] + got["decode_kv_write_ms"] \
        + got["decode_lm_head_ms"] < step
    assert got["decode_layer_io_ms"] + got["decode_kv_write_ms"] \
        + got["decode_dequant_ms"] < step
    assert got["decode_unscoped_share"] < 10
    sc = scopes.of_run(run)
    assert sc.n_runs("step") == meta["decode_steps"]
    assert sc.n_runs("chunk") == meta["chunk_calls"]
    assert all(s.args["slots"] > 0 for s in sc.named("repro.decode"))
    assert {"slot", "pos_start", "n"} <= set(
        sc.named("repro.prefill_chunk")[0].args)


MS = 1_000_000


def _scoped():
    """Two decode runs of 100 ms; per run 40 ms of scan slicing, 30 ms of
    dequant-matmul in the layers, 10 ms of KV write, 5 ms of head matmul
    inside 12 ms of head, 1 ms of embedding and 2 ms with no name."""
    L = "jit(step)/layers/while/body/closed_call/layer/"
    per_run = {"jit(step)/layers/while/body/dynamic_slice": 40,
               L + "dequant_matmul/jit(quantized_matmul)/dot_general": 30,
               L + "kv_write/vmap(upd)/scatter": 10,
               L + "rsqrt": 0.5,
               "jit(step)/lm_head/dequant_matmul/slice": 5,
               "jit(step)/lm_head/rsqrt": 7,
               "jit(step)/embed/jit(_take)/gather": 1,
               "": 2}
    spans = []
    for t in (0, 300):
        b = t * MS
        spans += [scopes.Span("repro.step", b, b + 210 * MS, {}),
                  scopes.Span("repro.decode", b + 2 * MS, b + 205 * MS,
                              {"slots": 16}),
                  scopes.Span("repro.decode.readback", b + 4 * MS,
                              b + 204 * MS, {})]
    # the first step also completes a prompt, 1 ms longer: it waits 1 ms
    # for the chunk to read the first token
    spans[0] = scopes.Span("repro.step", 0, 211 * MS, {})
    spans[1:1] = [scopes.Span("repro.prefill_chunk", 1 * MS, 2 * MS, {}),
                  scopes.Span("repro.prefill_chunk.readback", 1 * MS,
                              2 * MS, {})]
    return scopes.Scoped(
        window=(0, 600 * MS), runs={"step": [0.1, 0.1]},
        paths={"step": {p: 2 * ms / 1e3 for p, ms in per_run.items()}},
        spans=spans)


def test_readers_on_named_parts(monkeypatch):
    sc = _scoped()
    monkeypatch.setattr(scopes, "of_run", lambda run: sc)
    summary = trace.Summary(window_s=0.6, busy_s=0.2,
                            ops=[("x", 0, 100 * MS), ("x", 300 * MS,
                                                      400 * MS)],
                            modules=[], gaps=[])
    run = _run(summary)
    got = {n: spec.metric_reader(BENCH_DIR, n)(run) for n in READERS}
    assert got["decode_layer_io_ms"] == pytest.approx(40)
    assert got["decode_kv_write_ms"] == pytest.approx(10)
    assert got["decode_dequant_ms"] == pytest.approx(35)
    assert got["decode_lm_head_ms"] == pytest.approx(12)
    assert got["decode_unscoped_share"] == pytest.approx(2.0)
    assert got["step_host_ms"] == pytest.approx(10)


def test_idle_gaps_named_by_innermost_span():
    sc = _scoped()
    summary = trace.Summary(window_s=0.6, busy_s=0.2,
                            ops=[("x", 0, 100 * MS), ("x", 100 * MS,
                                                      300 * MS),
                                 ("x", 300 * MS, 400 * MS)],
                            modules=[], gaps=[])
    # one gap, [400, 600] ms: its midpoint lies in the second decode's
    # readback, the innermost span there
    assert scopes.idle_gaps_by_span(sc, summary) == {
        "repro.decode.readback": [1, pytest.approx(0.2)]}
