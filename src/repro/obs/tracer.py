"""Monotonic-clock tracer with a bounded ring buffer (DESIGN.md §10).

Every engine phase is a `Span`: it always opens a JAX profiler
annotation ``repro.<phase>`` (so a profiler trace shows the host's
phases on the same clock as the device's ops), and with a tracer it also
becomes a ring-buffer record. The serving stack is instrumented with
three record kinds:

* ``span``    — a timed phase (``name`` ∈ `schema.PHASES`) with ``ts``
  (seconds since the tracer epoch), ``dur``, and optional attribution
  fields: ``dispatch_s`` (host time until the jitted call returned —
  dispatch is asynchronous on every jax backend) and ``wait_s`` (the
  host-transfer wait for the device result). ``dur - wait_s`` is
  therefore host time, of which ``dispatch_s`` is the jit-call share.
  Which device work a wait covers is read from a profiler trace, where
  the device's ops and these spans share one clock.
* ``event``   — an instantaneous per-request lifecycle point
  (``name`` ∈ `schema.LIFECYCLE`: submit → admit → first_token →
  retire, plus rollback), carrying ``uid`` and usually ``slot``.
* ``counter`` — a sampled value series (e.g. the KV quantization-quality
  counters from `engine.kvcache.kv_quality_counters`).

The buffer is a fixed-capacity deque: once full, the OLDEST records drop
(``dropped`` counts them), so a long soak keeps the most recent window
instead of growing without bound. A disabled tracer is falsy — callers
hold ``None`` (or a falsy tracer) and guard every record site with one
branch; a `Span` still writes its profiler annotation (about a
microsecond).

Exporters: `to_jsonl` (one header record + one record per line — the
format `launch.trace_report` and `schema.validate_events` consume) and
`to_chrome` (Chrome ``trace.json``, loadable in Perfetto / chrome://
tracing: one track per slot, one per engine phase).
"""
from __future__ import annotations

import collections
import json
import time

from jax.profiler import TraceAnnotation

from repro.obs.atomic import atomic_write_text

SCHEMA_VERSION = 1

#: profiler annotation of phase ``p`` is ``ANNOTATION_PREFIX + p``
ANNOTATION_PREFIX = "repro."

#: Chrome-trace thread ids: slots get 1 + slot, un-slotted lifecycle
#: events a "requests" track, un-slotted phase spans one track per phase
#: name (stable order from schema.PHASES), counters their own track.
#: These are *minimum* tids — `chrome_trace` shifts them above the
#: highest slot tid, so engines with >= 59 slots don't alias the slot
#: tracks onto the requests/counters/phase tracks.
_TID_REQUESTS = 60
_TID_COUNTERS = 61
_TID_PHASE0 = 64


class Tracer:
    """Span/event/counter recorder. All timestamps come from ``clock``
    (host-monotonic; the engine passes its own clock so trace time and
    engine metrics share one axis).

    ``enabled=False`` makes the tracer falsy and every record call a
    no-op — engines normalize a falsy tracer to ``None`` so the serving
    hot path pays exactly one predictable branch per site.
    """

    def __init__(self, capacity: int = 1 << 16, clock=time.perf_counter,
                 enabled: bool = True, meta: dict | None = None):
        self.clock = clock
        self.enabled = enabled
        self.capacity = int(capacity)
        self.t0 = clock()
        self.events: collections.deque = collections.deque(
            maxlen=self.capacity)
        self.dropped = 0
        self.meta = dict(meta or {})

    def __bool__(self) -> bool:
        return self.enabled

    # ------------------------------------------------------- recording --
    def _push(self, rec: dict) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1                  # deque drops the oldest
        self.events.append(rec)

    def now(self) -> float:
        return self.clock()

    def begin(self) -> float:
        """Timestamp helper for the begin/`span_end` pair — records
        nothing (so a span abandoned on an exception costs nothing)."""
        return self.clock()

    def span_end(self, name: str, t_begin: float, **fields) -> None:
        """Record a span from ``t_begin`` (a `begin`/clock timestamp) to
        now. Extra ``fields`` ride along (slot/uid/step/dispatch_s/...)."""
        self.record_span(name, t_begin, self.clock() - t_begin, **fields)

    def record_span(self, name: str, t_begin: float, dur: float,
                    **fields) -> None:
        """Record a span of ``dur`` seconds from ``t_begin``."""
        if not self.enabled:
            return
        self._push({"kind": "span", "name": name,
                    "ts": t_begin - self.t0, "dur": dur, **fields})

    def span(self, name: str, **fields) -> "Span":
        """A `Span` of phase ``name`` recorded into this tracer."""
        return Span(self, name, self.clock, **fields)

    def event(self, name: str, **fields) -> None:
        if not self.enabled:
            return
        self._push({"kind": "event", "name": name,
                    "ts": self.clock() - self.t0, **fields})

    def counter(self, name: str, value, **fields) -> None:
        """``value``: a number or a flat dict of numbers (one series per
        key in the Chrome export)."""
        if not self.enabled:
            return
        self._push({"kind": "counter", "name": name,
                    "ts": self.clock() - self.t0, "value": value, **fields})

    # ------------------------------------------------------- exporting --
    def header(self) -> dict:
        return {"kind": "header", "schema": SCHEMA_VERSION,
                "capacity": self.capacity, "dropped": self.dropped,
                **self.meta}

    def records(self):
        """Header + buffered records, oldest first."""
        yield self.header()
        yield from self.events

    def to_jsonl(self, path: str) -> int:
        """Write the JSONL event log atomically (tmp + fsync + rename —
        a crash mid-export never truncates the artifact); returns the
        record count (header included)."""
        lines = [json.dumps(rec, default=float) for rec in self.records()]
        atomic_write_text(path, "\n".join(lines) + "\n" if lines else "")
        return len(lines)

    def to_chrome(self, path: str) -> None:
        atomic_write_text(
            path, json.dumps(chrome_trace(list(self.records()))))


class Span:
    """One phase on the profiler's clock, used as a context manager.

    Entering opens the profiler annotation ``repro.<name>`` with ``args``
    as its arguments (one cheap call when no profiler session is
    running); leaving closes it and sets ``dur`` from ``clock``. With a
    truthy ``tracer`` the span is also recorded into its ring buffer,
    on the tracer's clock, with ``args`` plus whatever `note` added —
    also when the body raises. There is no other way the engine records
    a span, so every ring-buffer span is also a profiler annotation.
    """

    __slots__ = ("tracer", "name", "clock", "args", "fields", "t0", "dur",
                 "_ann")

    def __init__(self, tracer, name: str, clock=time.perf_counter,
                 **args):
        self.tracer = tracer if tracer else None
        self.name = name
        self.clock = tracer.clock if self.tracer else clock
        self.args = args
        self.fields = None
        self.t0 = self.dur = 0.0

    def note(self, **fields) -> None:
        """Fields for the ring-buffer record that are known only inside
        the span (dispatch_s, wait_s, ...)."""
        if self.fields is None:
            self.fields = fields
        else:
            self.fields.update(fields)

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + self.name,
                                    **self.args)
        self._ann.__enter__()
        self.t0 = self.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = self.clock() - self.t0
        if self.tracer is not None:
            self.tracer.record_span(self.name, self.t0, self.dur,
                                    **self.args, **(self.fields or {}))
        self._ann.__exit__(*exc)
        return False


def load_jsonl(path: str) -> list[dict]:
    """Load a `to_jsonl` event log (header record first)."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _chrome_tid(rec: dict, phase_tids: dict, tid_requests: int,
                tid_phase0: int) -> int:
    if rec.get("slot") is not None:
        return 1 + int(rec["slot"])
    if rec["kind"] == "span":
        return phase_tids.setdefault(rec["name"],
                                     tid_phase0 + len(phase_tids))
    return tid_requests


def chrome_trace(records: list[dict]) -> dict:
    """Chrome trace-event JSON (Perfetto-loadable) from trace records:
    one track per slot (slot-attributed spans + lifecycle instants), one
    track per un-slotted engine phase, one counter track. Times in µs.

    Slot tids are ``1 + slot``, so the fixed requests/counters/phase
    tids would alias slot tracks at >= 59 slots; the non-slot tids are
    therefore shifted above the highest slot seen in ``records``."""
    max_slot = -1
    for rec in records:
        if (rec.get("kind") in ("span", "event", "counter")
                and rec.get("slot") is not None):
            max_slot = max(max_slot, int(rec["slot"]))
    tid_requests = max(_TID_REQUESTS, max_slot + 2)
    tid_counters = tid_requests + (_TID_COUNTERS - _TID_REQUESTS)
    tid_phase0 = tid_requests + (_TID_PHASE0 - _TID_REQUESTS)
    out = []
    phase_tids: dict[str, int] = {}
    for rec in records:
        kind = rec.get("kind")
        if kind not in ("span", "event", "counter"):
            continue
        ts_us = rec["ts"] * 1e6
        args = {k: v for k, v in rec.items()
                if k not in ("kind", "name", "ts", "dur", "value")}
        if kind == "span":
            out.append({"ph": "X", "pid": 0,
                        "tid": _chrome_tid(rec, phase_tids, tid_requests,
                                           tid_phase0),
                        "name": rec["name"], "ts": ts_us,
                        "dur": rec["dur"] * 1e6, "args": args})
        elif kind == "event":
            out.append({"ph": "i", "s": "t", "pid": 0,
                        "tid": _chrome_tid(rec, phase_tids, tid_requests,
                                           tid_phase0),
                        "name": rec["name"], "ts": ts_us, "args": args})
        else:                                   # counter
            val = rec.get("value")
            series = (val if isinstance(val, dict) else {"value": val})
            series = {k: v for k, v in series.items()
                      if isinstance(v, (int, float))}
            if series:
                out.append({"ph": "C", "pid": 0, "tid": tid_counters,
                            "name": rec["name"], "ts": ts_us,
                            "args": series})
    names = [(1 + s, f"slot {s}") for s in range(max_slot + 1)]
    names += [(tid_requests, "requests"), (tid_counters, "counters")]
    names += [(tid, f"phase:{name}") for name, tid in phase_tids.items()]
    meta = [{"ph": "M", "pid": 0, "tid": tid, "name": "thread_name",
             "args": {"name": label}} for tid, label in names]
    meta.append({"ph": "M", "pid": 0, "name": "process_name",
                 "args": {"name": "repro-engine"}})
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}
