"""Mean host-clock time per gradient bucket: the window's passes over the
layer (each ended by a block on every bucket's result) over the buckets
they reduced."""


def read(run):
    s = run.spans.seconds("pass")
    n = run.counters["buckets"] * len(s)
    return 1e3 * sum(s) / n if n else None
