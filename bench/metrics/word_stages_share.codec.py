"""Share of the window's device op time spent in the lossless word stages,
encode and decode (leaf ops under the program's `codec.word_stages`
scope), over all leaf-op time."""
from bench.attribution import scope_share


def read(run):
    return scope_share(run.trace, "codec.word_stages", "codec.")
