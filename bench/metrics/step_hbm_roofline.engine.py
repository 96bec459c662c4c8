"""Share of the HBM roofline that one batched decode step reaches.

A step must read every weight matrix once (bf16, the tied head included)
and, for each live slot, the int8 K and V bins of its filled positions and
their per-page f32 scales.  Those bytes over the chip's HBM bandwidth are
the step's least time; it is divided by the device time of one run of the
engine's batched step program (`jit__slots_step` in the trace's modules).
"""

PAGE = 128


def weight_bytes(c: dict) -> float:
    d, f = c["hidden_size"], c["intermediate_size"]
    h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    per_layer = d * h * hd * 2 + d * 2 * g * hd + 3 * d * f
    return 2.0 * (c["num_hidden_layers"] * per_layer + c["vocab_size"] * d)


def kv_bytes(c: dict, positions: int, slot_steps: int) -> float:
    """K and V bins (1 byte) of `positions` filled positions summed over
    slots and steps, and one f32 scale per page and kv head."""
    g, hd, nl = c["num_key_value_heads"], c["head_dim"], c["num_hidden_layers"]
    bins = 2.0 * nl * g * hd * positions
    scales = 2.0 * nl * g * 4 * (positions / PAGE + slot_steps)
    return bins + scales


STEP_PROGRAM = "jit__slots_step"


def read(run):
    c, n = run.config, run.counters
    steps = n["steps"]
    dev_s, runs = (run.trace.module_runs(STEP_PROGRAM) if run.trace
                   else (0.0, 0))
    if not steps or not runs or dev_s <= 0:
        return None
    least = (weight_bytes(c) + kv_bytes(c, n["kv_ctx"], n["slot_steps"])
             / steps)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / (dev_s / runs)
