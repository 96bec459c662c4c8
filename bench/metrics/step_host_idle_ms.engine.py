"""Device-idle milliseconds per decode step inside the program's
`engine.generate_step` spans: the host work of a step (the per-slot checks
and the dispatch) that the device waits for."""
from bench.attribution import idle_in, span_count


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    n = span_count(t, "engine.generate_step")
    if not n:
        return None
    return 1e3 * idle_in(t, t.spans("engine.generate_step")) / n
