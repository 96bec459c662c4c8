"""Mean host-clock time of one decode step: `DecodeEngine.generate_step`
and the read of its tokens to the host."""


def read(run):
    s = run.spans.seconds("generate_step") + run.spans.seconds("host_tokens")
    n = run.counters["steps"]
    return 1e3 * sum(s) / n if n else None
