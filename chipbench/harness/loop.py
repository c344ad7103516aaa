"""The closed loop: C clients, each sending its next request the moment the
engine returns its last one, driving ``Engine.submit`` / ``Engine.step``
and nothing else.

Every token is stamped on the client's side when ``Engine.step()``
returns with it; a request's slot is read with its first token, so that
the output check can take a request from every slot. The window is
[t0, t1]: t0 just before its first step, t1 when the first step ending
at or past ``t0 + seconds`` returns.

In a traced run two spies record what the program's jitted entry points
are asked to do, for the per-layer FLOP and byte counts and the decode
occupancy: each decode call's active slots and their positions, and each
chunk-prefill call's start and length. They forward the call unchanged.
No end-to-end metric reads them. Where an entry point is missing or its
call no longer matches, the spy says so on standard error and records
nothing, so the metrics that read it are left out of the line.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext

import numpy as np

from .traffic import Request, Traffic

FINISHED_OK = ("budget", "max_len", "eos")


@dataclasses.dataclass
class Served:
    """One request as its client saw it."""
    client: int
    prompt: np.ndarray
    max_new: int
    due: float                          # when the client sent it
    req: object = None                  # the engine's EngineRequest
    stamps: list = dataclasses.field(default_factory=list)
    finished: bool = False
    reason: str | None = None
    slot: int | None = None             # the engine's slot that served it


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    steps: int = 0
    step_ends: list = dataclasses.field(default_factory=list)
    served: list = dataclasses.field(default_factory=list)   # every request
    decode_calls: list = dataclasses.field(default_factory=list)
    chunk_calls: list = dataclasses.field(default_factory=list)
    decode_steps_in: int = 0            # engine counter over the window

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def inside(self, t: float) -> bool:
        return self.t0 < t <= self.t1


def _spy(eng, win: Window, clock, log):
    """Wrap the engine's decode and chunk-prefill entry points."""

    def wrap(attr, calls, record):
        fn = getattr(eng, attr, None)
        if fn is None:
            log(f"spy: the engine has no {attr}; the metrics that read "
                f"its calls are left out")
            return

        def spy(*a, **k):
            try:
                calls.append((clock(), *record(*a, **k)))
            except (TypeError, IndexError, AttributeError) as e:
                log(f"spy: {attr} no longer takes the call it was written "
                    f"for ({e!r}); the metrics that read it are left out")
                calls.clear()
                setattr(eng, attr, fn)
            return fn(*a, **k)

        setattr(eng, attr, spy)

    wrap("_decode", win.decode_calls,
         lambda p, c, toks, pos: ([int(eng._pos[s])
                                   for s in eng.sched.active_slots()],))
    wrap("_chunk_prefill", win.chunk_calls,
         lambda p, c, toks, slot, pos_start, length: (int(pos_start),
                                                      int(length)))


class ClosedLoop:
    def __init__(self, eng, traffic: Traffic, clock=time.perf_counter,
                 annotate=None, spy=False, log=print):
        self.eng = eng
        self.traffic = traffic
        self.clock = clock
        # profiler host spans around each step and the clients' work
        self.annotate = annotate or (lambda name: nullcontext())
        self.win = Window()
        self.live: dict[int, Served] = {}         # uid -> request
        if spy:
            _spy(eng, self.win, clock, log)

    def _send(self, client: int, r: Request, now: float) -> Served:
        uid = self.eng.submit(r.prompt, r.max_new)
        req = self.eng.sched.queue[-1]
        if req.uid != uid:
            raise RuntimeError(f"submitted uid {uid} is not at the queue's "
                               f"tail ({req.uid})")
        s = Served(client, r.prompt, r.max_new, now, req)
        self.live[uid] = s
        self.win.served.append(s)
        return s

    def _slot_of(self, req) -> int | None:
        """The slot that holds `req`, read once, with its first token."""
        for i, r in enumerate(self.eng.sched.slots):
            if r is req:
                return i
        return None

    def _step(self) -> float:
        with self.annotate("engine.step"):
            done = self.eng.step()
        now = self.clock()
        with self.annotate("clients"):
            for s in self.live.values():
                new = len(s.req.out) - len(s.stamps)
                if new > 0:
                    s.stamps.extend([now] * new)
                    if s.slot is None:
                        s.slot = self._slot_of(s.req)
            for req in done:
                s = self.live.pop(req.uid, None)
                if s is None:
                    continue
                s.finished, s.reason = True, req.finish_reason
                self._send(s.client, self.traffic.next(), now)
        return now

    def warm(self, lengths) -> None:
        """Serve one 2-token request per prompt length and drain; these
        are not clients' requests and leave nothing behind."""
        uids = {self.eng.submit(r.prompt, r.max_new)
                for r in self.traffic.warmup(lengths)}
        while not self.eng.sched.idle:
            self.eng.step()
        bad = [r for r in self.eng.sched.finished
               if r.uid in uids and r.finish_reason not in FINISHED_OK]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")

    def start(self) -> None:
        """Send every client's first request and step until each of those
        has its first token: the loop's stationary state. (Follow-ups sent
        meanwhile may still be waiting: so they would be at any time.)"""
        now = self.clock()
        first = [self._send(c, r, now)
                 for c, r in enumerate(self.traffic.initial())]
        while any(not s.stamps for s in first):
            self._step()

    def run(self, seconds: float) -> Window:
        win = self.win
        n_dec0 = self.eng.n_decode_steps
        with self.annotate("chipbench.window"):
            win.t0 = self.clock()
            now = win.t0
            while now - win.t0 < seconds:
                now = self._step()
                win.steps += 1
                win.step_ends.append(now)
            win.t1 = now
        win.decode_steps_in = self.eng.n_decode_steps - n_dec0
        return win


# ------------------------------------------------------------ readings --
def tokens_in(win: Window) -> int:
    return sum(1 for s in win.served for t in s.stamps if win.inside(t))


def gaps_in(win: Window) -> list[float]:
    """Gaps between consecutive tokens of a request, for gaps ending in
    the window."""
    out = []
    for s in win.served:
        st = s.stamps
        out.extend(b - a for a, b in zip(st, st[1:]) if win.inside(b))
    return out


def ttft_in(win: Window) -> list[float]:
    """Time to first token of each request sent in the window; one still
    waiting at t1 counts with its wait so far."""
    return [(s.stamps[0] if s.stamps else win.t1) - s.due
            for s in win.served if win.t0 <= s.due < win.t1]


def percentile(xs, q: float):
    """The q-th percentile (0-100) by linear interpolation; None if empty."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


@dataclasses.dataclass
class Run:
    """What a metric reader (``metrics/<name>.py``) reads."""
    conf: dict                  # the configuration file
    traffic: dict               # the traffic mix
    n_slots: int
    setup_s: float
    win: Window
    peaks: dict                 # the device's row of counts.PEAKS
    trace: object = None        # trace.Summary of a traced run, else None

    def in_window(self, calls):
        return [c for c in calls if self.win.inside(c[0])]
