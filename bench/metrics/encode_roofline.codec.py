"""Share of the HBM roofline that one encode reaches.

The least time of an encode is the bytes the work must move over the
chip's HBM bandwidth: the f32 input read once (4 bytes a value) and the
wire written once.  That count is of the work, not of the kernel that does
it.  It is divided by the device-busy time inside the `encode` spans, per
call.
"""


def least_bytes(values: int, wire_bytes: float) -> float:
    return 4.0 * values + wire_bytes


def read(run):
    calls = len(run.spans.seconds("encode"))
    busy = run.trace.busy_in("encode") if run.trace else 0.0
    if not calls or busy <= 0:
        return None
    c = run.counters
    least_s = least_bytes(c["values"], c["wire_bytes"]) / run.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least_s / (busy / calls)
