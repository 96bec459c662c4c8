"""Serving through `models.engine.DecodeEngine` over the quantized KV cache:
a closed loop of clients, one request each at a time, no think time.

Configuration keys (a dense LLaMA-family model, HF names):
`hidden_size`, `intermediate_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `num_hidden_layers`, `vocab_size`,
`rms_norm_eps`, `rope_theta`, and `kv` (`chain`, `eb_rel`: the KV pages'
bound relative to each page's largest value).

Traffic keys: `slots`, `seq`, `requests` (the size of the set of
lengths), `order_seed`, `prompt` and `output` (each `{"dist": "lognormal", "median",
"sigma", "min", "max"}` or `{"dist": "uniform", "min", "max"}`, where an
output's `max` is also held to `seq` less its prompt), `check_tokens`,
and the check's limits `gap_limit` and `mean_gap_limit`.

Every seed serves the same lengths in the same order: `requests`
stratified draws (the quantiles at (i + 1/2) / requests) of each
distribution, shuffled once by `order_seed`, and cycled.  The seed draws
the prompts' tokens.  So every seed asks for the same work, and a tail
over the few tens of requests a window holds repeats from run to run.  Set-up
makes the weights, fills every slot (one prefill each) and runs one step;
in the window each client sends its next request as soon as its last one
has its final token, and the request is prefilled at once.  Outputs are
greedy, to the drawn length.

The check runs the plain reference (`bench/reference/llama.py`) over a
sample of the requests drawn from the seed, the one with most served
tokens among them, and compares the gaps by which the served tokens'
logits lie below the reference's best: the widest (a token altered where
it is produced fails it) and the mean (the fp8 control fails it).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time

import numpy as np


def quantiles(n: int) -> np.ndarray:
    """The n stratified points (i + 1/2) / n of the unit interval."""
    return (np.arange(n) + 0.5) / n


def length_at(spec: dict, u: float, cap: int) -> int:
    """The length at quantile u of one distribution, held to `cap`."""
    hi = min(int(spec["max"]), int(cap))
    if spec["dist"] == "lognormal":
        v = spec["median"] * math.exp(
            spec["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (hi - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(round(v), spec["min"]), hi))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    out_len: int
    tokens: list = dataclasses.field(default_factory=list)


class Traffic:
    """The seed's request stream: the same prompt and output quantiles in
    the same order for every seed, cycled; the prompts' tokens drawn from
    the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.spec, self.vocab = traffic, vocab
        self.n = int(traffic["requests"])
        order = np.random.default_rng(int(traffic["order_seed"]))
        self.u_prompt = order.permutation(quantiles(self.n))
        self.u_out = order.permutation(quantiles(self.n))
        self.rng = np.random.default_rng(seed)
        self.rng_warm = np.random.default_rng([seed, 1])
        self.next_id = 0

    def prompt_lengths(self) -> list[int]:
        seq = int(self.spec["seq"])
        return [length_at(self.spec["prompt"], u, seq - 1)
                for u in self.u_prompt]

    def next(self) -> Request:
        i = self.next_id % self.n
        seq = int(self.spec["seq"])
        m = length_at(self.spec["prompt"], self.u_prompt[i], seq - 1)
        out = length_at(self.spec["output"], self.u_out[i], seq - m)
        r = Request(self.next_id, self.rng.integers(
            0, self.vocab, size=m).astype(np.int32), out)
        self.next_id += 1
        return r


def arch(config: dict):
    """The program's configuration object for these widths."""
    from repro.configs.base import ArchConfig
    c = config
    return ArchConfig(name=c["name"], family="dense",
                      n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                      n_heads=c["num_attention_heads"],
                      n_kv_heads=c["num_key_value_heads"],
                      d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                      head_dim=c["head_dim"], norm_eps=c["rms_norm_eps"])


def make_weights(config: dict, seed: int):
    """Random weights in the served dtype (bf16 matrices, f32 norm
    scales), in the program's layout, built on the device in one call.
    Each matrix has std 1/sqrt(fan in), so every layer adds to the
    residual stream as much as the embedding puts in (a smaller scale makes
    a model that only copies its last token)."""
    import jax
    import jax.numpy as jnp

    c = config
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    nl = c["num_hidden_layers"]
    mats = {"wq": (d, h * hd), "wkv": (d, 2 * g * hd), "wo": (h * hd, d),
            "w1": (d, f), "w2": (f, d), "w3": (d, f)}

    def normal(key, shape):
        std = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * std
                ).astype(jnp.bfloat16)

    def scale(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 16))
        layers = {k: normal(next(ks), (nl,) + s) for k, s in mats.items()}
        layers["ln1"] = scale(next(ks), (nl, d))
        layers["ln2"] = scale(next(ks), (nl, d))
        return {"emb": normal(next(ks), (v, d)),
                "final_norm": scale(next(ks), (d,)), "layers": layers}

    return build(jax.random.PRNGKey(seed))


def setup(ctx):
    import jax

    from repro.compression.kv import kv_quantizer_config
    from repro.configs.registry import get_kv_chain
    from repro.models.engine import DecodeEngine

    c, tr = ctx.config, ctx.traffic
    st = dict(config=c, traffic=tr, seed=ctx.seed)
    with ctx.spans("make_weights"):
        st["params"] = make_weights(c, ctx.seed)
        jax.block_until_ready(st["params"])
    st["traffic_gen"] = tg = Traffic(tr, c["vocab_size"], ctx.seed)
    st["eng"] = DecodeEngine(arch(c), st["params"], n_slots=tr["slots"],
                             seq=tr["seq"],
                             kv_cfg=kv_quantizer_config(c["kv"]["eb_rel"]),
                             stages=get_kv_chain(c["kv"]["chain"]))
    st["live"] = {}                       # slot -> Request
    st["done"] = []
    with ctx.spans("fill"):
        for _ in range(tr["slots"]):
            admit(st, tg.next(), time.perf_counter(), ctx.spans)
    with ctx.spans("warmup"):
        step(st, ctx.spans)
        # one prefill of every prompt length the stream holds that the
        # fill did not: the program compiles per prompt length
        seen = {len(r.prompt) for r in st["live"].values()}
        for m in sorted(set(tg.prompt_lengths()) - seen):
            pre = st["eng"].prefill(tg.rng_warm.integers(
                0, c["vocab_size"], size=m).astype(np.int32))
            int(np.asarray(pre.next_token).reshape(()))
    return st


def admit(st, req: Request, issued: float, spans) -> float:
    """Prefill a request into a free slot; returns its time to first
    token (from `issued` to the first token on the host)."""
    eng = st["eng"]
    slot = eng.allocate()
    with spans("prefill"):
        pre = eng.prefill(req.prompt)
        first = int(np.asarray(pre.next_token).reshape(()))
    ttft = time.perf_counter() - issued
    if not eng.insert(slot, pre, request=req.rid):
        raise RuntimeError(f"request {req.rid}: insert refused")
    req.tokens.append(first)
    st["live"][slot] = req
    return ttft


def step(st, spans) -> list:
    """One batched decode step; delivers each live slot's token to its
    request and returns the requests that finished."""
    eng = st["eng"]
    with spans("generate_step"):
        _, toks = eng.generate_step()
    with spans("host_tokens"):
        toks = np.asarray(toks)
    done = []
    for slot, req in list(st["live"].items()):
        req.tokens.append(int(toks[slot]))
        if len(req.tokens) >= req.out_len:
            eng.release(slot)
            del st["live"][slot]
            done.append(req)
    st["done"] += done
    return done


def window(st, seconds: float, spans) -> dict:
    tg = st["traffic_gen"]
    eng = st["eng"]
    ttfts, issued, delivered, steps = [], 0, 0, 0
    served = len(st["live"])
    kv_ctx = prefill_tokens = prefill_ctx = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        # positions each live slot's step attends to, its new one included
        kv_ctx += sum(len(r.prompt) + len(r.tokens)
                      for r in st["live"].values())
        live = len(st["live"])
        done = step(st, spans)
        steps += 1
        delivered += live
        now = time.perf_counter()
        queue = [(tg.next(), now) for _ in done]      # closed loop
        with spans("admit"):
            for req, at in queue:
                issued += 1
                ttfts.append(admit(st, req, at, spans))
                delivered += 1
                m = len(req.prompt)
                prefill_tokens += m
                prefill_ctx += m * (m + 1) // 2
    elapsed = time.perf_counter() - t0
    metrics = {"tokens_per_s": delivered / elapsed}
    if ttfts:
        metrics["ttft_p95_ms"] = 1e3 * float(np.percentile(ttfts, 95))
    return {"window_s": elapsed, "attempted": served + issued,
            "failed": 0, "metrics": metrics,
            "counters": {"steps": steps, "tokens": delivered,
                         "decode_tokens": delivered - issued,
                         "requests": issued, "ttft_samples": len(ttfts),
                         "prefill_tokens": prefill_tokens,
                         "attn_ctx": kv_ctx + prefill_ctx,
                         "kv_ctx": kv_ctx, "slot_steps": delivered - issued,
                         "engine": dict(eng.stats())}}


def release(st):
    """Free the engine (its caches) before the reference runs; the weights
    are the benchmark's own and stay."""
    st["requests"] = st["done"] + list(st["live"].values())
    st["eng"] = None
    st["live"] = {}


def sample(st) -> list[Request]:
    """Requests to check: the one with most served tokens, then others
    drawn from the seed until `check_tokens` served tokens are in."""
    reqs = sorted(st["requests"], key=lambda r: (-len(r.tokens), r.rid))
    rng = np.random.default_rng(st["seed"] + 1)
    out, rest = [reqs[0]], list(rng.permutation(len(reqs) - 1) + 1)
    while rest and sum(len(r.tokens) for r in out) < \
            st["traffic"]["check_tokens"]:
        out.append(reqs[int(rest.pop(0))])
    return out


def _sequence(r: Request):
    return np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])


def _numbers(st, gaps: list) -> list[dict]:
    g = np.concatenate(gaps)
    tr = st["traffic"]
    return [{"name": "widest served-token gap", "value": float(np.max(g)),
             "limit": tr["gap_limit"]},
            {"name": "mean served-token gap", "value": float(np.mean(g)),
             "limit": tr["mean_gap_limit"]}]


def check(st) -> list[dict]:
    from bench.reference import llama

    gaps = []
    for r in sample(st):
        g = llama.token_gaps(st["config"], st["params"], _sequence(r),
                             _targets(r), st["traffic"]["seq"])
        gaps.append(g[len(r.prompt) - 1:])
    return _numbers(st, gaps)


def _targets(r: Request) -> np.ndarray:
    """Target per position of the sequence: the served token that
    followed it, -1 on prompt positions."""
    t = np.full((len(r.prompt) + len(r.tokens) - 1,), -1, np.int32)
    t[len(r.prompt) - 1:] = r.tokens
    return t


def control(st) -> list[dict]:
    """The fp8 reference in the program's place: at every served position
    of the same sample, the gap of the token the fp8 reference puts
    first; the check's numbers over those gaps.  Also read, under names of
    their own: K and V rounded to the configuration's KV bound and to four
    times it (widest gap), and a served token altered where it is produced
    (the median gap of the token after it in the vocabulary)."""
    from bench.reference import llama

    c, tr = st["config"], st["traffic"]
    eb = c["kv"]["eb_rel"]
    ways = {"fp8": dict(fp8=True), "kv at the stated bound":
            dict(fp8=False, kv_eb=eb), "kv at 4x the stated bound":
            dict(fp8=False, kv_eb=4 * eb)}
    gaps = {k: [] for k in ways}
    altered = []
    for r in sample(st):
        seq = _sequence(r)
        m = len(r.prompt)
        for name, kw in ways.items():
            low = llama.low_argmax(c, st["params"], seq, len(seq),
                                   tr["seq"], **kw)
            t = np.full((len(seq),), -1, np.int32)
            t[m - 1:] = low[m - 1:]
            gaps[name].append(llama.token_gaps(
                c, st["params"], seq, t, tr["seq"])[m - 1:])
        t = _targets(r)
        t[m - 1:] = (t[m - 1:] + 1) % c["vocab_size"]
        altered.append(llama.token_gaps(c, st["params"], seq, t,
                                        tr["seq"])[m - 1:])
    out = _numbers(st, gaps.pop("fp8"))
    out += [{"name": k, "value": float(np.max(np.concatenate(v))),
             "limit": tr["gap_limit"]} for k, v in gaps.items()]
    out.append({"name": "one served token altered",
                "value": float(np.median(np.concatenate(altered))),
                "limit": tr["gap_limit"]})
    return out
