"""Host-clock time of `DecodeEngine.prefill` (ended by the first token on
the host) per prompt token, over the window's prefills."""


def read(run):
    s = run.spans.seconds("prefill")
    n = run.counters["prefill_tokens"]
    return 1e3 * sum(s) / n if s and n else None
