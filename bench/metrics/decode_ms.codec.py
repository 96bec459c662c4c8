"""Mean host-clock time of one `Pipeline.decode` call, ended by a block
(the benchmark's `decode` spans)."""


def read(run):
    s = run.spans.seconds("decode")
    return 1e3 * sum(s) / len(s) if s else None
