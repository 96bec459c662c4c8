"""Device time of collective operations (all-gather, all-reduce,
collective-permute, ...) over device-busy time, mean over chips."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.collective_s() / t.busy_s
