"""Device-idle milliseconds of the window under JAX's own host events for
tracing, lowering and compiling a program and for its persistent-cache
lookup (`attribution.is_compile`): work that a cache miss puts in the
window, which a count of backend compilations does not see."""
from bench.attribution import idle_in, is_compile
from bench.trace import union


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    spans = union((e.start_ns, e.end_ns) for e in t.host
                  if is_compile(e.name))
    return 1e3 * idle_in(t, spans)
