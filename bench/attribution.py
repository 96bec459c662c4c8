#!/usr/bin/env python3
"""Where the device sat idle, what the host was doing then, and which
program scopes the device's time went to.

Reads a reduced trace (`trace.Summary`), and the scopes of its device ops
(`bench/scopes.py`, which importing this module puts in place).  The
host marks are the events
of the host threads that carry the benchmark's or the program's spans,
in three kinds:

- the program's own spans (`repro.obs.span`): names that start with one
  of `PROGRAM_LAYERS`;
- the benchmark's spans around calls into the program (`run.Spans`),
  `window` left out, since it is open over everything;
- JAX's own events on those threads: dispatch of a jitted call
  (`PjitFunction(f)`), transfers, reads of an array to the host, and
  tracing, lowering and compiling (`is_compile`, which also takes the
  profiler's Python-tracer event of JAX's persistent-cache lookup).

    python3 bench/attribution.py --workload ds67b-chat --seed 1 --seconds 51

runs one traced cell as `run.py --trace 1` does, prints its result line
with `idle_by_span` (idle seconds of the first device by the innermost
mark open over them, `none` where no mark is) and `scopes` (device
seconds per program scope, per compiled program) added, and the tables on
stderr.
"""
from __future__ import annotations

import bisect
import heapq
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import scopes as S  # noqa: E402
from bench import trace as T  # noqa: E402

PROGRAM_LAYERS = ("codec.", "engine.", "transport.")
SCOPE_LAYERS = ("codec.", "transport.", "grads.", "serve.")
JAX_COMPILE = ("trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
               "lower_sharding_computation", "backend_compile_and_load",
               "backend_compile")
# JAX's persistent-cache lookup has no annotation of its own; the
# profiler's Python tracer names it "$compiler.py:<line> <function>"
JAX_PY_COMPILE = ("compile_or_get_cached",)


def is_compile(name: str) -> bool:
    """A JAX host event of tracing, lowering, compiling or a cache
    lookup."""
    return name in JAX_COMPILE or (
        name.startswith("$") and name.rsplit(" ", 1)[-1] in JAX_PY_COMPILE)


class Busy:
    """One device's disjoint sorted busy intervals, for covered-length
    queries in log time."""

    def __init__(self, intervals):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.cum = [0.0]
        for s, e in intervals:
            self.cum.append(self.cum[-1] + (e - s))

    def covered(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)
        j = bisect.bisect_left(self.starts, hi)
        if j <= i:
            return 0.0
        tot = self.cum[j] - self.cum[i]
        tot -= max(0.0, lo - self.starts[i])
        tot -= max(0.0, self.ends[j - 1] - hi)
        return tot


def idle_in(t, intervals) -> float:
    """Device-idle seconds of the window inside `intervals` (disjoint),
    mean over devices."""
    if not t.devices:
        return 0.0
    tot = 0.0
    for d in t.devices:
        busy = Busy(t.busy[d])
        for s, e in intervals:
            s, e = max(s, t.lo), min(e, t.hi)
            if e > s:
                tot += (e - s) - busy.covered(s, e)
    return tot * 1e-9 / len(t.devices)


def scope_share(t, name: str, layers) -> float | None:
    """Leaf-op seconds under scope `name` over all leaf-op seconds of the
    window, in %; None where no op carries a scope part that starts with
    one of `layers` (a program without these scopes)."""
    if t is None or not t.devices:
        return None
    total = sum(t.op_seconds().values())
    seen = {S.scope_of(e) for v in t.ops.values() for e in v}
    if total <= 0 or not any(p.startswith(layers) for s in seen
                             for p in S.scope_parts(s)):
        return None
    return 100.0 * S.scope_seconds(t, name) / total


def span_count(t, name: str) -> int:
    """Host events `name` that start inside the window."""
    return sum(1 for e in t.host if e.name == name and t.lo <= e.start_ns
               < t.hi)


def kind(name: str, driver: set) -> str:
    """'program', 'driver' or 'jax' for a host mark."""
    if name.startswith(PROGRAM_LAYERS):
        return "program"
    if name in driver:
        return "driver"
    return "jax"


def marks(t, driver: set) -> list:
    """The host marks of the window: every event, Python-tracer events
    (`$...`) aside, of the threads that carry a benchmark or program span,
    and JAX's compile events wherever they are; `window` left out."""
    lines = {e.line for e in t.host if e.name != "window" and (
        e.name in driver or e.name.startswith(PROGRAM_LAYERS))}
    return [e for e in t.host if e.name != "window" and (
        (e.line in lines and not e.name.startswith("$"))
        or is_compile(e.name))]


def idle_by_span(t, driver_spans) -> dict:
    """Idle seconds of the first device inside the window, by the
    innermost (shortest) mark open over each stretch; `none` where no
    mark is open."""
    if not t.devices:
        return {}
    found = [(max(e.start_ns, t.lo), min(e.end_ns, t.hi), e.dur_ns, e.name)
             for e in marks(t, set(driver_spans))]
    busy = t.busy[t.devices[0]]
    edges = [t.lo] + [x for s, e in busy for x in (s, e)] + [t.hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    # sweep over every boundary; at equal times, ends before starts
    pts = []
    for i, (s, e, _, _) in enumerate(found):
        if e > s:
            pts += [(s, 1, i), (e, 0, i)]
    for s, e in gaps:
        pts += [(s, 1, -1), (e, 0, -1)]
    pts.sort()
    out: dict[str, float] = {}
    heap, ended, idle, prev = [], set(), 0, None
    for x, start, i in pts:
        if prev is not None and x > prev and idle:
            while heap and heap[0][1] in ended:
                heapq.heappop(heap)
            name = found[heap[0][1]][3] if heap else "none"
            out[name] = out.get(name, 0.0) + (x - prev) * 1e-9
        prev = x
        if i < 0:
            idle += 1 if start else -1
        elif start:
            heapq.heappush(heap, (found[i][2], i))
        else:
            ended.add(i)
    return out


def scope_seconds_by_module(t, layers=SCOPE_LAYERS) -> dict:
    """Device seconds of leaf ops, mean over devices, per compiled program
    (the `module` of `module:op`): the total, and per program scope (a
    scope part that starts with one of `layers`) the seconds of ops under
    it.  Nested scopes count in each of them."""
    out: dict[str, dict] = {}
    n = max(len(t.devices), 1)
    for v in t.ops.values():
        for e in v:
            d = T.covered([(e.start_ns, e.end_ns)], t.lo, t.hi) * 1e-9 / n
            mod = out.setdefault(e.name.split(":", 1)[0],
                                 {"total": 0.0, "scoped": 0.0, "scopes": {}})
            mod["total"] += d
            ours = [p for p in S.scope_parts(S.scope_of(e))
                    if p.startswith(layers)]
            if ours:
                mod["scoped"] += d
            for p in ours:
                mod["scopes"][p] = mod["scopes"].get(p, 0.0) + d
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    logs = ROOT / run.OUT_DIR / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    kept = []
    drive = run.drive

    def keep(*a, **k):
        kept.append(drive(*a, **k))
        return kept[-1]

    run.drive = keep
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True)
    except run.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    finally:
        run.drive = drive
    r = kept[-1]
    t = r.summary
    idle = idle_by_span(t, r.spans.names())
    scopes = scope_seconds_by_module(t)
    result["idle_by_span"] = idle
    result["scopes"] = scopes
    tot = sum(idle.values())
    print(f"idle of the first device: {tot:.6f} s", file=sys.stderr)
    driver = set(r.spans.names())
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1])[:25]:
        k = "-" if name == "none" else kind(name, driver)
        print(f"  {k:8s} {name[:48]:48s} {s:12.6f} s "
              f"{100 * s / max(tot, 1e-12):7.2f}%", file=sys.stderr)
    for mod, m in sorted(scopes.items(), key=lambda kv: -kv[1]["total"]):
        print(f"{mod}: {m['total']:.6f} s, under program scopes "
              f"{100 * m['scoped'] / max(m['total'], 1e-12):.2f}%",
              file=sys.stderr)
        for p, s in sorted(m["scopes"].items(), key=lambda kv: -kv[1]):
            print(f"  {p:40s} {s:12.6f} s", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
