"""Model FLOP/s utilization of the whole serving loop: the operations the
model needs for every token it processed in the window (prompt tokens in
prefill and decoded tokens alike) over the window, against the chip's bf16
peak.

Per token: 2 operations per matmul weight (the tied head included), and
4 * heads * head_dim per layer for each position it attends to (scores
and the weighted sum of values).
"""


def flops(c: dict, tokens: int, attn_ctx: int) -> float:
    d, f = c["hidden_size"], c["intermediate_size"]
    h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    per_layer = d * h * hd * 2 + d * 2 * g * hd + 3 * d * f
    params = c["num_hidden_layers"] * per_layer + c["vocab_size"] * d
    return (2.0 * params * tokens
            + 4.0 * h * hd * c["num_hidden_layers"] * attn_ctx)


def read(run):
    n = run.counters
    tokens = n["decode_tokens"] + n["prefill_tokens"]
    if not tokens:
        return None
    f = flops(run.config, tokens, n["attn_ctx"])
    return 100.0 * f / run.window_s / (run.peaks["bf16_flops"] * run.chips)
