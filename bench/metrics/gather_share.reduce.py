"""Share of the window's device op time spent in the gather path of the
compressed reduce: the all-gather of the wires and the per-chip decodes
and sum (leaf ops under the program's `transport.gather` scope), over all
leaf-op time, mean over chips."""
from bench.attribution import scope_share


def read(run):
    return scope_share(run.trace, "transport.gather", ("transport.", "grads."))
