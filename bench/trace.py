"""Reduce a JAX profiler trace to what the benchmark reports.

A trace is reduced from a flat list of `Event(plane, line, name, start_ns,
dur_ns)`, so that the arithmetic can be checked on a hand-built list:

- device busy time: the union of the intervals in which an operation ran
  on a device, clipped to the traced window, per device;
- per-op device time of the ops that contain no other op, summed over the
  window and averaged over devices;
- device time inside host spans (the benchmark's own `TraceAnnotation`s,
  found on the host plane by name);
- the idle gaps of the first device, each named by the innermost host span
  open at its middle.

Device planes are those named `/device:<KIND>:<n>`; their operations are
the events of the line named `XLA Ops`, named `module:op` after the line
`XLA Modules`.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
COLLECTIVE = ("all-gather", "all-reduce", "collective-permute", "ppermute",
              "all-to-all", "reduce-scatter", "send", "recv")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(log_dir: str) -> list[Event]:
    """Every event of the newest `.xplane.pb` under `log_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the part of [lo, hi) that disjoint `intervals` cover."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


def op_name(hlo: str, module: str = "") -> str:
    """`module:op` from an op event's HLO text ("%fusion.3 = f32[...]
    fusion(...)" -> "fusion.3") and its module ("jit_f(1234)" -> "jit_f")."""
    op = hlo.split(" = ", 1)[0].strip().lstrip("%")
    mod = module.split("(", 1)[0]
    return f"{mod}:{op}" if mod else op


def _named_ops(device_events: list[Event], mods: dict) -> list[Event]:
    """The device ops, each renamed `module:op` by the module running on
    its device when it started (`mods`: plane -> module events by start)."""
    starts = {p: [e.start_ns for e in v] for p, v in mods.items()}
    out = []
    for e in device_events:
        if e.line != OPS_LINE:
            continue
        m, i = "", bisect.bisect_right(starts.get(e.plane, []), e.start_ns)
        if i and e.start_ns < mods[e.plane][i - 1].end_ns:
            m = mods[e.plane][i - 1].name
        out.append(e._replace(name=op_name(e.name, m)))
    return out


def leaves(ops: list[Event]) -> list[Event]:
    """The ops of one device that contain no other op: a loop or a call
    is listed with the ops it runs, and is dropped so that no time counts
    twice."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns
            or nxt.end_ns > e.end_ns]


class Summary:
    """The reduced trace of one traced window."""

    def __init__(self, events: list[Event], window: str = "window"):
        host = [e for e in events if not _DEVICE_PLANE.match(e.plane)]
        marks = [e for e in host if e.name == window]
        if not marks:
            raise ValueError(f"the trace holds no host span {window!r}")
        self.lo = min(e.start_ns for e in marks)
        self.hi = max(e.end_ns for e in marks)
        self.host = [e for e in host if e.end_ns > self.lo
                     and e.start_ns < self.hi]
        dev = [e for e in events if _DEVICE_PLANE.match(e.plane)]
        self.modules: dict[str, list[Event]] = {}
        for e in sorted(dev, key=lambda e: e.start_ns):
            if e.line == MODULES_LINE:
                self.modules.setdefault(e.plane, []).append(e)
        ops = _named_ops(dev, self.modules)
        self.devices = sorted({e.plane for e in ops})
        self.busy = {d: union(clip([(e.start_ns, e.end_ns) for e in ops
                                    if e.plane == d], self.lo, self.hi))
                     for d in self.devices}
        self.ops = {d: leaves([e for e in ops if e.plane == d])
                    for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        return sum(covered(b, self.lo, self.hi) for b in self.busy.values()
                   ) * 1e-9 / len(self.devices)

    def spans(self, name: str) -> list[tuple[float, float]]:
        return union((e.start_ns, e.end_ns) for e in self.host
                     if e.name == name)

    def busy_in(self, name: str) -> float:
        """Device-busy seconds inside host spans `name`, mean over
        devices."""
        if not self.devices:
            return 0.0
        spans = self.spans(name)
        tot = sum(covered(b, s, e) for b in self.busy.values()
                  for s, e in spans)
        return tot * 1e-9 / len(self.devices)

    def module_runs(self, prefix: str) -> tuple[float, float]:
        """Device seconds and number of runs of the compiled programs
        whose name starts with `prefix`, started inside the window, mean
        over devices."""
        if not self.devices:
            return 0.0, 0.0
        runs = [e for v in self.modules.values() for e in v
                if e.name.startswith(prefix) and self.lo <= e.start_ns
                < self.hi]
        n = len(self.devices)
        return sum(e.dur_ns for e in runs) * 1e-9 / n, len(runs) / n

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per op name inside the window, mean over
        devices."""
        out: dict[str, float] = {}
        for v in self.ops.values():
            for e in v:
                d = covered([(e.start_ns, e.end_ns)], self.lo, self.hi)
                out[e.name] = out.get(e.name, 0.0) + d * 1e-9
        n = max(len(self.devices), 1)
        return {k: s / n for k, s in out.items()}

    def collective_s(self) -> float:
        """Device seconds of collective operations, mean over devices."""
        return sum(s for k, s in self.op_seconds().items()
                   if any(c in k.lower() for c in COLLECTIVE))

    def gaps(self, span_names) -> list[tuple[str, float]]:
        """Idle gaps of the first device inside the window, each named by
        the innermost of the host spans `span_names` open at its middle
        ('none' where none is)."""
        if not self.devices:
            return []
        busy = self.busy[self.devices[0]]
        edges = [self.lo] + [t for s, e in busy for t in (s, e)] + [self.hi]
        marks = [e for e in self.host if e.name in set(span_names)]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            open_ = [m for m in marks if m.start_ns <= mid < m.end_ns]
            name = min(open_, key=lambda m: m.dur_ns).name if open_ else "none"
            out.append((name, (e - s) * 1e-9))
        return out

    def breakdown(self, span_names, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(span_names), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}
