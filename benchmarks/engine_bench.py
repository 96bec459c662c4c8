"""Continuous-batching decode microbenchmark (DESIGN.md §10).

Drives `models.engine.DecodeEngine` over a stream of requests — prefill,
slot insert through the PackedKV wire, batched generate steps, slot churn
— and reports the three serving numbers the perf trajectory tracks:

    tokens/s                batched decode throughput (greedy, all slots)
    ms/step                 wall time of one vmapped generate_step
    wire bytes vs raw       per-slot hand-off wire vs the raw-bf16 cache

    PYTHONPATH=src python -m benchmarks.engine_bench --smoke
    PYTHONPATH=src python -m benchmarks.engine_bench --stream  # 2-device
                                      # streaming-migration row (sets
                                      # XLA_FLAGS before jax imports)

Writes rows (roofline-style list of dicts, the format
`benchmarks/roofline.py --decode-bench` consumes) to --out; the committed
BENCH_decode.json at the repo root is the `--smoke` artifact — CPU
numbers, there to pin the format and the trajectory's first point, not to
impress.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import zlib

if "--stream" in sys.argv:                  # must precede the jax import
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np                                       # noqa: E402
import jax                                               # noqa: E402

from repro.configs import registry                       # noqa: E402
from repro.configs.registry import get_kv_chain          # noqa: E402
from repro.launch.cache import use_compile_cache         # noqa: E402
from repro.models import build                           # noqa: E402
from repro.models import engine as E                     # noqa: E402
from repro.models import serve as S                      # noqa: E402

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _bench_engine(cfg, params, *, n_slots, seq, prompts, new_tokens,
                  stages):
    """Timed continuous-batching loop (the engine.run scheduler with
    phase timers).  Returns the measured row fields."""
    eng = E.DecodeEngine(cfg, params, n_slots=n_slots, seq=seq,
                         stages=stages)
    # warmup: compile prefill step + vmapped generate step outside timers
    pre = eng.prefill(np.zeros(1, np.int32))
    eng.insert(0, pre)
    eng.generate_step()
    eng.release(0)
    base = eng.stats()

    t_prefill = t_decode = 0.0
    pending = collections.deque(enumerate(prompts))
    budget = {}
    while pending or any(r is not None for r in eng.requests):
        while pending:
            slot = eng.allocate()
            if slot is None:
                break
            rid, prompt = pending.popleft()
            t0 = time.perf_counter()
            pre = eng.prefill(prompt)
            eng.insert(slot, pre, request=rid)
            jax.block_until_ready(eng._cache)
            t_prefill += time.perf_counter() - t0
            budget[rid] = new_tokens - 1
        if not any(r is not None for r in eng.requests):
            continue
        t0 = time.perf_counter()
        _, toks = eng.generate_step()
        toks = np.asarray(toks)                 # sync — honest step time
        t_decode += time.perf_counter() - t0
        for slot, rid in enumerate(list(eng.requests)):
            if rid is None:
                continue
            budget[rid] -= 1
            if budget[rid] <= 0 or int(eng._pos[slot]) >= seq:
                eng.release(slot)               # slot churn
    st = eng.stats()
    steps = st["steps"] - base["steps"]
    gen = st["generated_tokens"] - base["generated_tokens"]
    pre_toks = st["prefill_tokens"] - base["prefill_tokens"]
    inserts = st["inserts"] - base["inserts"]
    wire = st["wire_bytes"] - base["wire_bytes"]
    return {
        "decode_steps": steps,
        "generated_tokens": gen + inserts,      # prefill yields token 1
        "tokens_per_s": (gen + inserts) / max(t_decode + t_prefill, 1e-9),
        "decode_tokens_per_s": gen / max(t_decode, 1e-9),
        "ms_per_step": 1e3 * t_decode / max(steps, 1),
        "prefill_tokens_per_s": pre_toks / max(t_prefill, 1e-9),
        "wire_bytes_per_slot": wire / max(inserts, 1),
        "raw_bf16_bytes_per_slot": eng.raw_slot_bytes(),
        "wire_vs_raw": (wire / max(inserts, 1)) / eng.raw_slot_bytes(),
    }


def _bench_stream(cfg, params, *, seq, prompt, stages):
    """Streaming-migration row: prefill on rank 0 of a 2-device mesh with
    per-page sends overlapping the ongoing prefill (DESIGN.md §10)."""
    mesh = jax.make_mesh((2,), ("wire",))
    # warmup compile
    E.stream_prefill(cfg, params, prompt[:S.PAGE + 1], seq=seq, mesh=mesh,
                     axis="wire", stages=stages)
    t0 = time.perf_counter()
    sp = E.stream_prefill(cfg, params, prompt, seq=seq, mesh=mesh,
                          axis="wire", stages=stages)
    jax.block_until_ready(sp.cache)
    dt = time.perf_counter() - t0
    return {
        "pages_streamed": sp.stats["pages_streamed"],
        "prefill_tokens_per_s": sp.stats["prefill_tokens"] / dt,
        "wire_bytes": sp.stats["wire_bytes"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: tiny reduced model, seconds on CPU")
    ap.add_argument("--arch", default="deepseek-67b")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--stages", default="kv-page",
                    help="page-chain preset or fragment (registry "
                         "KV_PAGE_CHAINS)")
    ap.add_argument("--stream", action="store_true",
                    help="add the 2-device streaming-migration row")
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_decode.json"))
    args = ap.parse_args()
    use_compile_cache()

    if args.smoke:
        defaults = dict(slots=2, seq=256, requests=3, prompt_len=130,
                        new_tokens=8)
    else:
        defaults = dict(slots=4, seq=512, requests=8, prompt_len=200,
                        new_tokens=32)
    for k, v in defaults.items():
        if getattr(args, k if k != "prompt_len" else "prompt_len") is None:
            setattr(args, k, v)

    cfg = registry.get(args.arch).reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    stages = get_kv_chain(args.stages)
    rng = np.random.default_rng(zlib.crc32(b"engine-prompts"))
    prompts = [rng.integers(0, cfg.vocab, size=args.prompt_len)
               .astype(np.int32) for _ in range(args.requests)]

    row = {
        "bench": "engine_decode", "arch": args.arch, "reduced": True,
        "backend": jax.default_backend(), "page": S.PAGE,
        "n_slots": args.slots, "seq": args.seq,
        "requests": args.requests, "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens, "stages": args.stages,
        "smoke": bool(args.smoke),
    }
    row.update(_bench_engine(cfg, params, n_slots=args.slots, seq=args.seq,
                             prompts=prompts, new_tokens=args.new_tokens,
                             stages=stages))
    rows = [row]
    print(f"engine_decode[{args.arch} reduced, {args.slots} slots, "
          f"seq {args.seq}, {args.requests} reqs]: "
          f"{row['tokens_per_s']:.1f} tok/s end-to-end "
          f"({row['decode_tokens_per_s']:.1f} decode-only), "
          f"{row['ms_per_step']:.2f} ms/step, wire/slot "
          f"{row['wire_bytes_per_slot']/2**10:.1f} KiB vs raw "
          f"{row['raw_bf16_bytes_per_slot']/2**10:.1f} KiB "
          f"({1/row['wire_vs_raw']:.2f}x smaller)")

    if args.stream:
        assert jax.device_count() >= 2, "--stream needs 2 devices"
        srow = dict(row, bench="engine_stream")
        srow.update(_bench_stream(cfg, params, seq=args.seq,
                                  prompt=prompts[0], stages=stages))
        rows.append(srow)
        print(f"engine_stream: {srow['pages_streamed']} pages overlapped "
              f"with prefill at {srow['prefill_tokens_per_s']:.1f} tok/s, "
              f"{srow['wire_bytes']/2**10:.1f} KiB on the wire")

    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.relpath(args.out, ROOT)}")


if __name__ == "__main__":
    main()
