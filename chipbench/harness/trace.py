"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
executable and kernel times, and the longest idle gaps with what the host
was doing in each.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation run and ``XLA Modules`` one per executable run
(named ``jit_<function>(<id>)``). Host planes hold the TraceMe spans of
every host thread, the benchmark's own annotations among them. All
planes share the trace's clock, so the benchmark's ``chipbench.window``
span bounds the measured window on the device lines too.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Trace:
    ops: dict          # device index -> [(name, start_ns, end_ns)]
    modules: dict      # device index -> [(name, start_ns, end_ns)]
    host: list         # [(name, start_ns, end_ns)] over every host thread
    window: tuple      # (start_ns, end_ns) of the benchmark's window span


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


_HLO = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
#: ops that only contain other ops (the scan over layers is a while)
CONTAINERS = ("while", "call", "conditional")


def short_name(name: str) -> str:
    """An op event's name is its HLO instruction; keep the op kind, a
    custom call's target and the op_name the program gave it, e.g.
    ``custom-call tpu_custom_call jit(step)/while/body/pallas_call``."""
    m = _HLO.match(name)
    if not m:
        return name
    parts = [m.group(2)]
    t = _TARGET.search(name)
    if t:
        parts.append(t.group(1))
    o = _OP_NAME.search(name)
    parts.append(o.group(1) if o else m.group(1))
    return " ".join(parts)


def _events(line, shorten=False):
    return [(short_name(e.name) if shorten else e.name, int(e.start_ns),
             int(e.start_ns + e.duration_ns)) for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line, shorten=True)
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(spans)}")
    if not ops:
        raise ValueError("no device operations in the trace")
    return Trace(ops, modules, host, spans[0])


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_intervals(events) -> list[tuple[int, int]]:
    """Union of the events' intervals, merged and sorted."""
    out = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices of busy time
    ops: list                     # device 0's ops in the window
    modules: list                 # device 0's executable runs in the window
    gaps: list                    # [(host span name, seconds)], longest first

    def module_runs(self, function: str) -> list[float]:
        """Seconds of each run of the executable jitted from `function`."""
        rx = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
        return [(e - s) / 1e9 for n, s, e in self.modules if rx.search(n)]

    def ops_in_module(self, function: str, pattern: str) -> tuple[int, float]:
        """(count, seconds) of ops matching `pattern` inside runs of the
        executable jitted from `function`."""
        rx = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
        spans = sorted((s, e) for n, s, e in self.modules if rx.search(n))
        prx = re.compile(pattern)
        count, total, i = 0, 0, 0
        for n, s, e in sorted(self.ops, key=lambda x: x[1]):
            while i < len(spans) and spans[i][1] <= s:
                i += 1
            if i < len(spans) and spans[i][0] <= s and prx.search(n):
                count += 1
                total += e - s
        return count, total / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ops that took most device time, by name; container ops
        (the layer loop's while) are left out, their contents count."""
        tot = {}
        for name, s, e in self.ops:
            if name.split(" ", 1)[0] in CONTAINERS:
                continue
            tot[name] = tot.get(name, 0) + (e - s)
        top = sorted(tot.items(), key=lambda x: -x[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]


def summarize(tr: Trace, n_gaps: int = 10) -> Summary:
    lo, hi = tr.window
    busy = []
    for dev, evs in tr.ops.items():
        busy.append(sum(e - s for s, e in
                        busy_intervals(_clip(evs, lo, hi))))
    dev0 = min(tr.ops)
    ops = _clip(tr.ops[dev0], lo, hi)
    merged = busy_intervals(ops)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = [[_host_at(tr.host, (s + e) // 2), (e - s) / 1e9]
            for s, e in holes[:n_gaps]]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9,
                   ops=ops, modules=_clip(tr.modules.get(dev0, []), lo, hi),
                   gaps=gaps)


def _host_at(host, t) -> str:
    """The innermost host span covering time t."""
    best = None
    for n, s, e in host:
        if s <= t < e and n != WINDOW_SPAN and (best is None
                                                or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "(no host span)"
