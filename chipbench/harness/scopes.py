"""Device time by the names the program gives its parts, and the
program's own host spans, from the profiler trace of a traced run.

`trace.py` reads the trace through ``jax.profiler.ProfileData``, which
gives a device op its HLO instruction and the op event's stats, but not
the stats of the op's event metadata. There lies ``tf_op``: the op's
``op_name`` path, e.g. ``jit(step)/layers/while/body/closed_call/layer/
kv_write/vmap(upd)/scatter:``. The program names its parts with
``jax.named_scope`` (``embed``, ``layers`` and its body ``layer``,
``kv_write``, ``dequant_matmul``, ``lm_head``), so a path says which part
an op belongs to. This module parses the ``.xplane.pb`` itself, through a
minimal descriptor of the few XPlane fields it reads: the protobuf
runtime's C parser reads it in about a second per 100 MB, and
TensorFlow's generated ``xplane_pb2`` is not imported (seconds to
import).

From the trace it keeps, for device 0: the runs of each executable that
lie wholly inside the benchmark's ``chipbench.window`` span, and the
summed time of the ops inside those runs by ``op_name`` path (container
ops such as the layer loop's ``while`` left out: their contents count).
From the host planes it keeps every ``repro.*`` span (the engine's
phases, DESIGN.md §10) with its arguments, and the harness's own
``engine.step`` and ``clients`` spans. All planes share one clock.

A trace of a program that names no part has paths without any scope:
every reader of a scope then finds nothing and returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import sys
import time
from pathlib import Path

from . import trace

TRACE_DIR = Path(__file__).resolve().parents[1] / ".trace"
#: the engine's span names (repro.obs.tracer.ANNOTATION_PREFIX)
SPAN_PREFIX = "repro."
#: the harness's own spans (loop.ClosedLoop), kept beside the engine's
HARNESS_SPANS = ("engine.step", "clients")
#: the decode executable: jitted from the engine's `step`
DECODE_FN = "step"
_MODULE = re.compile(r"^jit_([^(]+)")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------- XPlane, minimally --
def _xspace_class():
    """The XSpace message class, from a descriptor holding only the
    fields read here (tsl/profiler/protobuf/xplane.proto numbering);
    every other field is skipped by the parser."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")

    def message(name, fields):
        m = fp.message_type.add(name=name)
        for fname, number, kind, ref in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if ref and ref[0] == "*"
                            else F.LABEL_OPTIONAL)
            if kind is None:
                f.type = F.TYPE_MESSAGE
                f.type_name = ".chipbench_xplane." + ref.lstrip("*")
            else:
                f.type = kind

    I64, U64, DBL, STR = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE, \
        F.TYPE_STRING
    message("XStat", [("metadata_id", 1, I64, ""),
                      ("double_value", 2, DBL, ""),
                      ("uint64_value", 3, U64, ""),
                      ("int64_value", 4, I64, ""),
                      ("str_value", 5, STR, ""),
                      ("ref_value", 7, U64, "")])
    message("XEvent", [("metadata_id", 1, I64, ""),
                       ("offset_ps", 2, I64, ""),
                       ("duration_ps", 3, I64, ""),
                       ("stats", 4, None, "*XStat")])
    message("XLine", [("name", 2, STR, ""), ("timestamp_ns", 3, I64, ""),
                      ("events", 4, None, "*XEvent")])
    message("XEventMetadata", [("id", 1, I64, ""), ("name", 2, STR, ""),
                               ("stats", 5, None, "*XStat")])
    message("XStatMetadata", [("id", 1, I64, ""), ("name", 2, STR, "")])
    # map<int64, ...> fields are repeated key/value entries on the wire
    message("EventMetadataEntry", [("key", 1, I64, ""),
                                   ("value", 2, None, "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, I64, ""),
                                  ("value", 2, None, "XStatMetadata")])
    message("XPlane", [("name", 2, STR, ""), ("lines", 3, None, "*XLine"),
                       ("event_metadata", 4, None, "*EventMetadataEntry"),
                       ("stat_metadata", 5, None, "*StatMetadataEntry")])
    message("XSpace", [("planes", 1, None, "*XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def _stat_value(s, stat_names):
    """An XStat's value; a ref_value names a stat metadata entry."""
    if s.ref_value:
        return stat_names.get(s.ref_value, "")
    for f in ("str_value", "int64_value", "uint64_value", "double_value"):
        v = getattr(s, f)
        if v:
            return v
    return 0


# ---------------------------------------------------------- the reading --
def under(path: str, scope: str) -> bool:
    """Whether an op_name path (or a ';'-joined list of them) has
    ``scope`` as a component, also inside a transform such as
    ``vmap(scope)``."""
    return re.search(rf"(?:^|[/(;]){re.escape(scope)}(?:[/);:]|$)",
                     path) is not None


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    args: dict


@dataclasses.dataclass
class Scoped:
    window: tuple                 # (start_ns, end_ns) of chipbench.window
    runs: dict                    # function -> [seconds] of whole runs
    paths: dict                   # function -> {op_name path: seconds}
    spans: list                   # host spans inside the window, by start

    def n_runs(self, function: str) -> int:
        return len(self.runs.get(function, ()))

    def has(self, function: str, scope: str) -> bool:
        """Whether any op of the executable runs under ``scope``."""
        return any(under(p, scope) for p in self.paths.get(function, {}))

    def seconds_per_run(self, function: str, keep) -> float:
        """Seconds per run of the executable in ops whose path passes
        ``keep(path)``."""
        tot = sum(s for p, s in self.paths.get(function, {}).items()
                  if keep(p))
        return tot / self.n_runs(function)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def load(path: str) -> Scoped:
    """The `Scoped` reading of one ``.xplane.pb``."""
    space = _xspace_class().FromString(Path(path).read_bytes())
    devices = sorted((p for p in space.planes
                      if re.fullmatch(r"/device:TPU:\d+", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    hosts = [p for p in space.planes if p.name.startswith("/host:")]
    spans, window = [], None
    for plane in hosts:
        names = {e.key: e.value.name for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for e in line.events:
                name = names.get(e.metadata_id, "")
                if name == trace.WINDOW_SPAN:
                    window = ((base + e.offset_ps) // 1000,
                              (base + e.offset_ps + e.duration_ps) // 1000)
                elif name.startswith(SPAN_PREFIX) or name in HARNESS_SPANS:
                    s = (base + e.offset_ps) // 1000
                    spans.append(Span(name, s, s + e.duration_ps // 1000, {
                        stat_names.get(st.metadata_id, ""):
                            _stat_value(st, stat_names) for st in e.stats}))
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = window
    spans = sorted((s for s in spans if lo <= s.start_ns and s.end_ns <= hi),
                   key=lambda s: s.start_ns)
    runs, paths = _device(devices[0], lo, hi)
    return Scoped(window, runs, paths, spans)


def _device(plane, lo: int, hi: int):
    """Whole executable runs inside [lo, hi] ns, and the seconds of the
    non-container ops inside them summed by (function, op_name path)."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    meta = {e.key: e.value for e in plane.event_metadata}
    lines = {line.name: line for line in plane.lines}
    runs, spans = {}, []
    mods = lines.get(trace.MODULES_LINE)
    for e in (mods.events if mods is not None else ()):
        m = _MODULE.match(meta[e.metadata_id].name)
        s = mods.timestamp_ns * 1000 + e.offset_ps
        if m and lo * 1000 <= s and s + e.duration_ps <= hi * 1000:
            runs.setdefault(m.group(1), []).append(e.duration_ps / 1e12)
            spans.append((s, s + e.duration_ps, m.group(1)))
    spans.sort()
    starts = [s for s, _, _ in spans]
    path_of, by_id = {}, {}
    for k, m in meta.items():
        if trace.short_name(m.name).split(" ", 1)[0] in trace.CONTAINERS:
            continue
        tf = next((_stat_value(st, stat_names) for st in m.stats
                   if st.metadata_id == tf_op), "")
        path_of[k] = tf.rpartition(":")[0] if ":" in tf else tf
    ops = lines.get(trace.OPS_LINE)
    if ops is not None:
        base = ops.timestamp_ns * 1000
        for e in ops.events:
            if e.metadata_id not in path_of:
                continue
            s = base + e.offset_ps
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                key = (spans[i][2], e.metadata_id)
                by_id[key] = by_id.get(key, 0) + e.duration_ps
    paths = {}
    for (fn, k), ps in by_id.items():
        d = paths.setdefault(fn, {})
        d[path_of[k]] = d.get(path_of[k], 0.0) + ps / 1e12
    return runs, paths


_cache: dict = {}


def of_run(run):
    """The traced run's `Scoped` reading (parsed once, shared by every
    reader), or None with a message on standard error."""
    if run.trace is None:
        return None
    try:
        path = trace.find_xplane(str(TRACE_DIR))
        key = (path, Path(path).stat().st_mtime_ns)
        if key not in _cache:
            t = time.perf_counter()
            _cache.clear()
            _cache[key] = load(path)
            sc = _cache[key]
            log(f"scopes: {sum(map(len, sc.paths.values()))} op paths in "
                f"{sum(map(len, sc.runs.values()))} executable runs, "
                f"{len(sc.spans)} host spans, read in "
                f"{time.perf_counter() - t:.1f} s")
        return _cache[key]
    except (OSError, ValueError) as e:
        log(f"scopes: the trace cannot be read by name ({e})")
        return None


def decode_scope_ms(run, metric: str, scope: str, keep=None):
    """Milliseconds per decode-executable run in ops under ``scope`` (or
    passing ``keep``), or None with a message when the scope is absent."""
    sc = of_run(run)
    if sc is None:
        return None
    if not sc.n_runs(DECODE_FN) or not sc.has(DECODE_FN, scope):
        log(f"{metric}: no op of jit_{DECODE_FN} under the scope "
            f"{scope!r} in this trace")
        return None
    keep = keep or (lambda p: under(p, scope))
    return 1e3 * sc.seconds_per_run(DECODE_FN, keep)


def idle_gaps_by_span(sc: Scoped, summary, min_s: float = 1e-3) -> dict:
    """Device idle gaps longer than ``min_s`` inside the window, by the
    innermost kept host span at each gap's midpoint ("(none)" if none):
    {span: [count, seconds]}. ``summary`` is the run's `trace.Summary`,
    whose ops give the device's busy time."""
    lo, hi = sc.window
    edges = [lo] + [x for iv in trace.busy_intervals(summary.ops)
                    for x in iv] + [hi]
    out = {}
    starts = [s.start_ns for s in sc.spans]
    for a, b in zip(edges[::2], edges[1::2]):
        if (b - a) / 1e9 <= min_s:
            continue
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid)
        inner = [s for s in sc.spans[:i] if s.end_ns > mid]
        name = min(inner, key=lambda s: s.end_ns - s.start_ns).name \
            if inner else "(none)"
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (b - a) / 1e9
    return out
