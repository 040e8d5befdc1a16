"""Device traces: capture a window with the JAX profiler, reduce the
``.xplane.pb`` to plain interval lists, and compute from them what the
per-layer metrics read: busy union, idle share, per-module device time,
exposed collective time and idle gaps labelled with the host task that
was running.

The reduced form is JSON (``Trace.to_json``), so a recorded trace can be
kept as a test fixture and reduced again by the same code.  Times are
nanoseconds on the trace's clock; ``offset_ns`` maps the host's
monotonic clock onto it (``trace_ns = monotonic_ns + offset_ns``), from
an annotation written at a known monotonic time.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time

ANCHOR = "bench.anchor"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.I)


def _union(iv):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(iv):
    return sum(e - s for s, e in iv)


def _intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """'jit_decode_step(12)' -> 'jit_decode_step'."""
    return re.sub(r"\(\d+\)$", "", name).strip()


class Trace:
    """Per-device op and module intervals inside one traced window."""

    def __init__(self, window, offset_ns, devices, tasks=()):
        self.window = [float(window[0]), float(window[1])]
        self.offset_ns = float(offset_ns)
        # {device: {"ops": [[start, dur, name]], "modules": [...],
        #           "async": [...]}}: ops are the TensorCore's leaf ops
        # (loops that contain others left out), async the collectives
        # of the device's asynchronous line
        self.devices = devices
        self.tasks = [list(t) for t in tasks]     # [start, end, name]

    # ------------------------------------------------------------ storage
    def to_json(self) -> dict:
        return {"window": self.window, "offset_ns": self.offset_ns,
                "devices": self.devices, "tasks": self.tasks}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["window"], d["offset_ns"], d["devices"],
                   d.get("tasks", ()))

    @classmethod
    def load(cls, path) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    def save(self, path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    # ---------------------------------------------------------- reduction
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, events):
        w0, w1 = self.window
        out = []
        for s, d, _ in events:
            a, b = max(s, w0), min(s + d, w1)
            if a < b:
                out.append([a, b])
        return out

    def busy(self, dev) -> list:
        return _union(self._clip(self.devices[dev]["ops"]))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        devs = list(self.devices)
        return sum(_length(self.busy(d)) for d in devs) / len(devs) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_time(self, dev, names) -> tuple[float, int]:
        """(seconds, executions) of the modules named ``names`` that ran
        wholly inside the window."""
        w0, w1 = self.window
        tot, n = 0.0, 0
        for s, d, name in self.devices[dev]["modules"]:
            if module_name(name) in names and w0 <= s and s + d <= w1:
                tot += d
                n += 1
        return tot / 1e9, n

    def module_time_mean(self, names) -> tuple[float, float]:
        """Module seconds and executions, averaged over devices."""
        devs = list(self.devices)
        got = [self.module_time(d, names) for d in devs]
        return (sum(g[0] for g in got) / len(devs),
                sum(g[1] for g in got) / len(devs))

    def exposed_collective_s(self, dev) -> float:
        """Collective-op time with no other op running on the device."""
        coll, other = list(self.devices[dev].get("async", [])), []
        for s, d, name in self.devices[dev]["ops"]:
            (coll if COLLECTIVE.search(name) else other).append((s, d, name))
        c = _union(self._clip(coll))
        o = _union(self._clip(other))
        return (_length(c) - _length(_intersect(c, o))) / 1e9

    def gaps(self, dev) -> list:
        """Idle intervals of ``dev`` inside the window."""
        w0, w1 = self.window
        out, t = [], w0
        for s, e in self.busy(dev):
            if s > t:
                out.append([t, s])
            t = max(t, e)
        if t < w1:
            out.append([t, w1])
        return out

    def label(self, start, end) -> str:
        """The host tasks running during [start, end), by overlap."""
        over = {}
        for s, e, name in self.tasks:
            o = min(e, end) - max(s, start)
            if o > 0:
                key = name.split(":")[0].split("@")[0]
                over[key] = over.get(key, 0.0) + o
        if not over:
            return "no host task running"
        return "+".join(sorted(over, key=lambda k: -over[k]))

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (averaged over devices)
        and the longest idle gaps, labelled by the host task."""
        devs = list(self.devices)
        tot = {}
        for dev in devs:
            w0, w1 = self.window
            for s, d, name in self.devices[dev]["ops"]:
                if w0 <= s and s + d <= w1:
                    tot[name] = tot.get(name, 0.0) + d / 1e9 / len(devs)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(devs[0]), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self.label(s, e), (e - s) / 1e9]
                              for s, e in gaps]}


# --------------------------------------------------------------- capture
class Capture:
    """Profile from ``start()`` to ``stop()``; both write an annotation at
    a known monotonic time, which ties the host clock to the trace's."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.marks = []

    def _mark(self):
        import jax
        t = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        self.marks.append(t)

    def start(self):
        import jax
        # no Python-function tracing (every call on every host thread
        # would be recorded) and no HLO dumps; host annotations stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark()

    def stop(self):
        import jax
        self._mark()
        jax.profiler.stop_trace()

    def reduce(self, tasks=()) -> Trace:
        """``tasks``: [(start_mono_ns, end_mono_ns, name)] host spans."""
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no profiler trace under {self.dir}")
        return reduce_xplane(max(paths, key=os.path.getmtime), self.marks,
                             tasks)


def _lines(plane):
    return {line.name: line for line in plane.lines}


def reduce_xplane(path, marks, tasks=()) -> Trace:
    """Reduce one ``.xplane.pb``: device op and module events, and the
    anchor annotations (``marks`` are their monotonic times)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    anchors, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                anchors += [e.start_ns for e in line.events
                            if e.name == ANCHOR]
        elif plane.name.startswith("/device:"):
            lines = _lines(plane)
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            if ops is None:
                continue
            dev = plane.name.split(":")[-1]
            leaf = []
            for e in ops.events:
                n = op_name(e.name)
                if not n.startswith(CONTAINERS):
                    leaf.append([e.start_ns, e.duration_ns, n])
            asyn = lines.get("Async XLA Ops")
            devices[dev] = {
                "ops": leaf,
                "modules": ([[e.start_ns, e.duration_ns, e.name]
                             for e in mods.events] if mods else []),
                "async": ([[e.start_ns, e.duration_ns, op_name(e.name)]
                           for e in asyn.events
                           if COLLECTIVE.search(op_name(e.name))]
                          if asyn else [])}
    if len(anchors) < 2 or len(marks) < 2:
        raise RuntimeError(f"trace {path}: {len(anchors)} anchors found")
    anchors.sort()
    offset = anchors[0] - marks[0]
    tr_tasks = [[s + offset, e + offset, n] for s, e, n in tasks]
    return Trace([anchors[0], anchors[-1]], offset, devices, tr_tasks)


def describe(path) -> dict:
    """Planes, lines, event counts and a few sample events of a trace
    (for looking at one by hand)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "events": len(evs),
                          "sample": [[e.name, e.start_ns, e.duration_ns,
                                      [[str(s[0]), str(s[1])]
                                       for s in e.stats][:6]]
                                     for e in evs[:4]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"path": str(path), "bytes": os.path.getsize(path),
            "planes": out}
