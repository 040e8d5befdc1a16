"""LTTng-style event tracer for the UMT runtime (paper §IV-A uses LTTng +
Babeltrace + Trace Compass; we record the same state transitions in-process
and derive the same metrics: per-core utilisation, oversubscription
periods, context-switch counts).

Besides the runtime's state transitions the tracer records *spans*: named
intervals of program work (a decode tick, one admission), nested per
thread, each tagged with the request ids it serves.  A span is one event
``(start, "span", thread, -1, (end, name, span_id, parent_id, rids))``
on the same clock as every other event.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict, namedtuple

Span = namedtuple("Span", "name start end thread span_id parent rids")


class _NoSpan:
    """What :meth:`Tracer.span` returns when tracing is off: one shared
    object whose enter and exit do nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _OpenSpan:
    __slots__ = ("tracer", "name", "rids", "t", "sid", "parent")

    def __init__(self, tracer, name, rids):
        self.tracer, self.name, self.rids = tracer, name, rids

    def __enter__(self):
        tr = self.tracer
        stack = tr._stack()
        self.parent = stack[-1] if stack else None
        self.sid = next(tr._ids)
        stack.append(self.sid)
        self.t = time.monotonic() - tr.t0
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        end = time.monotonic() - tr.t0
        tr._stack().pop()
        with tr._lock:
            tr.events.append((self.t, "span", threading.get_ident(), -1,
                              (end, self.name, self.sid, self.parent,
                               self.rids)))
        return False


class Tracer:
    def __init__(self, enabled=True):
        self.enabled = enabled
        self.events: list[tuple] = []
        self._lock = threading.Lock()
        self.t0 = time.monotonic()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def ev(self, kind: str, wid: int, core: int, info=None):
        if not self.enabled:
            return
        t = time.monotonic() - self.t0
        with self._lock:
            self.events.append((t, kind, wid, core, info))

    # ------------------------------------------------------------- spans
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, rids=()):
        """Context manager timing one named interval of program work on
        this thread; a span opened inside another is its child.
        ``rids``: the request ids it serves.  With tracing off this is
        the shared :data:`NO_SPAN`: no clock read, no lock, no record."""
        if not self.enabled:
            return NO_SPAN
        return _OpenSpan(self, name, rids)

    def spans(self) -> list:
        """Every closed span, in start order."""
        with self._lock:
            evs = [e for e in self.events if e[1] == "span"]
        out = [Span(info[1], t, info[0], wid, info[2], info[3], info[4])
               for t, _, wid, _, info in evs]
        return sorted(out, key=lambda s: s.start)

    def span_self_s(self) -> dict:
        """Seconds per span name of its own work: each span's duration
        less the part its child spans cover."""
        spans = self.spans()
        kids = defaultdict(list)
        for s in spans:
            if s.parent is not None:
                kids[s.parent].append((s.start, s.end))
        out = defaultdict(float)
        for s in spans:
            covered, reach = 0.0, s.start
            for a, b in sorted(kids.get(s.span_id, ())):
                a, b = max(a, reach), min(b, s.end)
                if b > a:
                    covered += b - a
                    reach = b
            out[s.name] += (s.end - s.start) - covered
        return dict(out)

    # ------------------------------------------------------------- analysis
    def core_timelines(self):
        """Per-core runnable-worker-count timeline: [(t, count), ...]."""
        deltas = defaultdict(list)
        for t, kind, wid, core, _ in sorted(self.events,
                                            key=lambda e: e[0]):
            if kind in ("spawn", "wake", "unblock"):
                deltas[core].append((t, +1))
            elif kind in ("park", "block"):
                deltas[core].append((t, -1))
        out = {}
        for core, ds in deltas.items():
            count = 0
            tl = []
            for t, d in ds:
                count += d
                tl.append((t, count))
            out[core] = tl
        return out

    def stats(self, n_cores: int, t_end: float | None = None) -> dict:
        """Fractions of wall-time each core spent busy (>=1 runnable
        worker) and oversubscribed (>=2), plus context-switch counts."""
        if t_end is None:
            t_end = max((e[0] for e in self.events), default=0.0)
        tls = self.core_timelines()
        busy = {}
        oversub = {}
        for core in range(n_cores):
            tl = tls.get(core, [])
            b = o = 0.0
            prev_t, prev_c = 0.0, 0
            for t, c in tl:
                dt = t - prev_t
                if prev_c >= 1:
                    b += dt
                if prev_c >= 2:
                    o += dt
                prev_t, prev_c = t, c
            dt = max(0.0, t_end - prev_t)
            if prev_c >= 1:
                b += dt
            if prev_c >= 2:
                o += dt
            busy[core] = b / t_end if t_end > 0 else 0.0
            oversub[core] = o / t_end if t_end > 0 else 0.0
        switches = steals = 0
        for e in self.events:
            if e[1] == "block":
                switches += 1
            elif e[1] == "steal":
                steals += 1
        return {
            "makespan_s": t_end,
            "cpu_util": sum(busy.values()) / max(n_cores, 1),
            "oversub_frac": sum(oversub.values()) / max(n_cores, 1),
            "ctx_switches": switches,
            "traced_steals": steals,
            "n_events": len(self.events),
            "per_core_busy": busy,
        }
