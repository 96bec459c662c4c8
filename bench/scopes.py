"""The program scope of each device op of a trace.

An op's scope is the `op_name` of its HLO metadata: the program's
`jax.named_scope`s among the parts of its name-stack path.  An `XLA Ops`
event carries only the instruction's name; the trace keeps each program's
HLO (`Hlo Proto` stats of the `/host:metadata` plane, keyed by the
module's name), where the instruction's metadata holds it
(`hlo_op_names`).

`bench/trace.py` reduces a trace to events that carry no scope, and
`bench/run.py` removes the trace's directory once it has reduced it.  So
importing this module puts `load` in place of `trace.load`: it returns the
same events and notes the scope of every device op on the side, where
`scope_of` finds it again for an op of the `Summary` made from them.
`bench/run.py` imports each metric's reader before it loads the trace, so
a reader that imports this module (through `bench/attribution.py`) sees
the scopes of the run it reads.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os

from bench import trace as T

# (device plane, start_ns, instruction) -> scope, for the last trace loaded
_SCOPES: dict[tuple, str] = {}


def key(plane: str, start_ns: float, name: str) -> tuple:
    """An op event's key: its device, its start and its instruction (from
    the raw HLO text or from the `module:op` name a `Summary` gives it)."""
    return plane, float(start_ns), T.op_name(name).rsplit(":", 1)[-1]


def note(events, hlo: dict) -> None:
    """Note the scope of each device op in `events`, in place of what was
    noted before: the op's module is the `XLA Modules` event running on its
    device when it started, its scope `hlo[module][instruction]`."""
    _SCOPES.clear()
    mods: dict[str, list] = {}
    for e in events:
        if e.line == T.MODULES_LINE:
            mods.setdefault(e.plane, []).append((e.start_ns, e.end_ns, e.name))
    for v in mods.values():
        v.sort()
    starts = {p: [m[0] for m in v] for p, v in mods.items()}
    for e in events:
        if e.line != T.OPS_LINE or not T._DEVICE_PLANE.match(e.plane):
            continue
        v = mods.get(e.plane, [])
        i = bisect.bisect_right(starts.get(e.plane, []), e.start_ns)
        mod = v[i - 1][2] if i and e.start_ns < v[i - 1][1] else ""
        scope = hlo.get(mod, {}).get(T.op_name(e.name), "")
        if scope:
            _SCOPES[key(e.plane, e.start_ns, e.name)] = scope


def scope_of(e) -> str:
    """The noted scope of a device op event, "" where none was noted."""
    return _SCOPES.get(key(e.plane, e.start_ns, e.name), "")


@functools.wraps(T.load)
def load(log_dir: str):
    events = load.__wrapped__(log_dir)
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    try:
        with open(files[-1], "rb") as f:
            hlo = hlo_op_names(f.read())
    except (ValueError, IndexError, UnicodeDecodeError):
        hlo = {}                # unreadable: every op reads as unscoped
    note(events, hlo)
    return events


load.__doc__ = ("`trace.load`, with the scope of each device op noted "
                "(`note`) from the trace's own HLO.")
T.load = load


def _varint(buf, i: int):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """The (field number, value) pairs of one serialized protobuf message
    in buf[lo:hi]; a length-delimited value is its (start, end)."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        k, i = _varint(buf, i)
        wire = k & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield k >> 3, v


def _sub(buf, span, field: int):
    """Every value of `field` in the message at buf[span[0]:span[1]]."""
    return [v for f, v in _fields(buf, *span) if f == field]


def hlo_op_names(xspace: bytes) -> dict:
    """{module name: {instruction name: op_name}} from the `Hlo Proto`
    stats of a serialized XSpace's `/host:metadata` plane (XSpace.planes
    1; XPlane name 2, event_metadata 4, stat_metadata 5; XEventMetadata
    name 2, stats 5; XStat metadata_id 1, bytes_value 6; HloProto
    hlo_module 1; HloModuleProto computations 3; HloComputationProto
    instructions 2; HloInstructionProto name 1, metadata 7; OpMetadata
    op_name 2)."""
    buf = memoryview(xspace)
    text = lambda span: bytes(buf[span[0]:span[1]]).decode()
    out: dict[str, dict] = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        names = _sub(buf, plane, 2)
        if not names or text(names[0]) != "/host:metadata":
            continue
        hlo_stat = set()
        for entry in _sub(buf, plane, 5):
            for md in _sub(buf, entry, 2):
                n = _sub(buf, md, 2)
                if n and text(n[0]) == "Hlo Proto":
                    hlo_stat.update(_sub(buf, md, 1))
        for entry in _sub(buf, plane, 4):
            for md in _sub(buf, entry, 2):
                n = _sub(buf, md, 2)
                for stat in _sub(buf, md, 5):
                    if not set(_sub(buf, stat, 1)) & hlo_stat:
                        continue
                    for proto in _sub(buf, stat, 6):
                        out[text(n[0]) if n else ""] = _instruction_scopes(
                            buf, proto, text)
    return out


def _ids(buf, v) -> list:
    """A repeated int64 field's values: packed (a span) or one varint."""
    if not isinstance(v, tuple):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _instruction_scopes(buf, proto, text) -> dict:
    """{instruction name: scope} of one HloProto.  An instruction whose
    op_name holds no path (`jit(f)/...`) was made by the compiler (a
    layout copy, a cached lowering's inner op): it takes the scope of the
    computation it calls (a fusion's root), else of its first operand
    that has one."""
    insts, roots = {}, {}
    for module in _sub(buf, proto, 1):
        for comp in _sub(buf, module, 3):
            cid, root = None, None
            for f, v in _fields(buf, *comp):
                if f == 5:
                    cid = v
                elif f == 6:
                    root = v
                elif f == 2:
                    name, scope, iid, operands, calls = None, "", None, [], []
                    for g, w in _fields(buf, *v):
                        if g == 1:
                            name = text(w)
                        elif g == 7:
                            for op in _sub(buf, w, 2):
                                scope = text(op)
                        elif g == 35:
                            iid = w
                        elif g == 36:
                            operands += _ids(buf, w)
                        elif g == 38:
                            calls += _ids(buf, w)
                    insts[iid] = (name, scope, operands, calls)
            roots[cid] = root
    resolved: dict = {}

    def resolve(iid, depth=0):
        if iid in resolved:
            return resolved[iid]
        if iid not in insts or depth > 64:
            return ""
        resolved[iid] = ""                     # a cycle resolves to none
        _, scope, operands, calls = insts[iid]
        if "/" not in scope:
            scope = ""
            for nxt in [roots.get(c) for c in calls] + operands:
                scope = resolve(nxt, depth + 1)
                if scope:
                    break
        resolved[iid] = scope
        return scope

    return {name: resolve(iid) for iid, (name, *_) in insts.items()
            if name is not None}


def scope_parts(scope: str) -> frozenset:
    """The names in a scope path: "jit(f)/vmap(serve.attn)/dot_general"
    -> {"f", "serve.attn", "dot_general"}; a transformation's wrapper
    (`vmap(...)`, `jvp(...)`, `jit(...)`) is taken off its name."""
    parts, depth, cur = [], 0, []
    for ch in scope:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    out = set()
    for p in parts:
        while p.endswith(")") and "(" in p:
            p = p[p.index("(") + 1:-1]
        if p:
            out.add(p)
    return frozenset(out)


def scope_seconds(t, name: str) -> float:
    """Device seconds of the leaf ops of Summary `t` whose scope has `name`
    among its parts (`scope_parts`), inside the window, mean over
    devices."""
    parts: dict[str, frozenset] = {}
    tot = 0.0
    for v in t.ops.values():
        for e in v:
            s = scope_of(e)
            if s not in parts:
                parts[s] = scope_parts(s)
            if name in parts[s]:
                tot += T.covered([(e.start_ns, e.end_ns)], t.lo, t.hi)
    return tot * 1e-9 / max(len(t.devices), 1)
