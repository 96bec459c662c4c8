"""Share of the window's device op time spent compacting: the capped
outlier table and the chunk payload (leaf ops under the program's
`codec.compact` scope, wherever it sits), over all leaf-op time."""
from bench.attribution import scope_share


def read(run):
    return scope_share(run.trace, "codec.compact", "codec.")
