"""Share of the time inside the program's `engine.prefill` spans in which
no operation ran on the device (1 - busy / length, over all of them)."""
from bench.attribution import idle_in


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    spans = t.spans("engine.prefill")
    length = sum(min(e, t.hi) - max(s, t.lo) for s, e in spans
                 if min(e, t.hi) > max(s, t.lo)) * 1e-9
    if length <= 0:
        return None
    return 100.0 * idle_in(t, spans) / length
