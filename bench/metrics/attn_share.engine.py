"""Share of the window's device op time spent in attention: projections,
the KV history's dequantize, both attention pieces and the page close
(leaf ops under the program's `serve.attn` scope), over all leaf-op time."""
from bench.attribution import scope_share


def read(run):
    return scope_share(run.trace, "serve.attn", "serve.")
