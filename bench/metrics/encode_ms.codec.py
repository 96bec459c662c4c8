"""Mean host-clock time of one `Pipeline.encode` call, ended by a block
(the benchmark's `encode` spans)."""


def read(run):
    s = run.spans.seconds("encode")
    return 1e3 * sum(s) / len(s) if s else None
