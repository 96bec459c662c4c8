"""Offline autotuner for the adaptive chain selector (DESIGN.md §11).

Sweeps every registered `SELECTOR_SETS` candidate over its
representative suites (the `exhaustive_sweep` discipline applied to the
chain space instead of the value space: measure EVERYTHING, then let the
cheap runtime statistics only have to rank, not predict), and produces:

  * per-suite rows — exact transmitted bits for every candidate, the
    statistics-chosen chain, the true best chain, and the auto-vs-best
    ratio — written to `BENCH_select.json` (consumed by
    `benchmarks.roofline --select-bench`);
  * bias calibration — the median measured-minus-estimated gap per
    candidate in bits per 1024 words; `--write` rewrites the `bias`
    tuples between the AUTOTUNED markers in `configs/registry.py` so the
    runtime scoring rule inherits the measurement.

Every dataset comes from the crc32-seeded `benchmarks.datasets`
registry, so tuning reproduces bit-for-bit across processes.

Usage: PYTHONPATH=src python -m benchmarks.autotune
           [--smoke] [--full] [--write] [--out BENCH_select.json]

--smoke shrinks the suites for CI (same flag grammar as run.py);
default size is 2^20 values per suite; --full uses the suites' native
~4M size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import select as SEL
from repro.launch.cache import use_compile_cache

from . import datasets

GRAD_EB_REL = 2.0 ** -8      # the gradient wire's runtime bound policy
_REPO_ROOT = Path(__file__).resolve().parent.parent


def _zero_bias(sel):
    """Measure with bias off so the calibration is absolute."""
    return dataclasses.replace(sel, bias=tuple(0.0 for _ in sel.chains))


def _cut(smoke: bool, full: bool) -> int | None:
    return 1 << 16 if smoke else (None if full else 1 << 20)


# ------------------------------------------------- full-pipeline sets ----

def _pipeline_suites(name: str, smoke: bool, full: bool):
    """(suite name -> array, eb policy) for a full-pipeline set."""
    cut = _cut(smoke, full)
    if name == "grad-wire":
        suites = dict(datasets.GRAD_SUITES, iid=datasets.iid)
        data = {k: jnp.asarray(gen()[:cut]) for k, gen in suites.items()}
        # the wire's runtime per-tensor bound, like compress_shard
        ebs = {k: jnp.float32(GRAD_EB_REL) * jnp.sqrt(jnp.mean(v * v))
               for k, v in data.items()}
        return data, ebs
    if name == "sci-plane":
        grid = 256 if smoke else (1024 if full else 512)
        data = {"nyxplane": jnp.asarray(datasets.nyx_plane(grid))}
        return data, {"nyxplane": None}       # the spec's own bound
    raise KeyError(name)


def tune_pipeline_set(name: str, smoke: bool, full: bool):
    sel = _zero_bias(SEL.get_selector(name))
    data, ebs = _pipeline_suites(name, smoke, full)
    rows, deltas = [], [[] for _ in sel.chains]
    for suite, x in data.items():
        eb = ebs[suite]
        n = x.size
        n_words = sel.n_words(n)
        est = np.asarray(sel.score(x, eb))
        actual = []
        for pipe in sel.chains:
            enc = pipe.encode(x, eb, kernels=False)
            actual.append(float(pipe.wire_bits(enc, n)))
        wire = sel.encode(x, eb)
        auto_bits = float(sel.wire_bits(wire, n))
        cid = int(wire.chain_id)
        best = int(np.argmin(actual))
        for i in range(len(sel.chains)):
            deltas[i].append((actual[i] - float(est[i]))
                             / (n_words / 1024.0))
        rows.append({
            "set": name, "suite": suite, "n": int(n),
            "chosen": sel.chains[cid].spec(),
            "best": sel.chains[best].spec(),
            "auto_ratio": round(n * 32 / auto_bits, 3),
            "best_ratio": round(n * 32 / actual[best], 3),
            "auto_vs_best": round(actual[best] / auto_bits, 4),
            "chains": {sel.chains[i].spec(): round(n * 32 / actual[i], 3)
                       for i in range(len(sel.chains))},
        })
    return rows, _relative_bias(deltas)


# ------------------------------------------------------- KV page set ----

def _kv_caches(smoke: bool, full: bool):
    """Representative serving caches (crc32-seeded): a mid-decode cache
    (unwritten tail pages) and a token-correlated one (kvdelta's case)."""
    s, d = (256, 64) if smoke else ((2048, 64) if full else (1024, 64))
    r = datasets._rng("kvtune")
    mid = r.standard_normal((2, 2, s, d)).astype(np.float32)
    mid[:, :, int(s * 0.6):, :] = 0.0
    steps = r.standard_normal((2, 2, s, d)).astype(np.float32)
    corr = np.cumsum(steps, axis=2).astype(np.float32) * 0.05
    return {"kv": mid, "kvcorr": corr}


def tune_kv_set(name: str, smoke: bool, full: bool):
    from repro.compression import kv as KVC

    sel = _zero_bias(SEL.get_kv_selector(name))
    from repro.configs.registry import SELECTOR_SETS
    frags = SELECTOR_SETS[name]["chains"]
    page = 128
    rows, deltas = [], [[] for _ in sel.chains]
    for suite, cache in _kv_caches(smoke, full).items():
        q = KVC.quantize_kv(jnp.asarray(cache), KVC.kv_quantizer_config(),
                            page=page)
        *lead, s, d = q.bins.shape
        n_pages_total = int(np.prod(lead)) * (s // page)
        per = page * d
        wpp = per // 4
        # statics identical across fragments: eb2/outlier/overflow
        # planes + the per-page chain-id byte (page_costs already counts
        # each fragment's header content and transmitted length)
        statics = (q.eb2.size * 32 + q.out_idx.size * 32
                   + q.out_val.size * 32 + q.overflow.size * 8
                   + n_pages_total * 8)
        flat = q.bins.reshape(-1, per).astype(jnp.int32)
        costs = np.asarray(jax.vmap(
            lambda b: sel.page_costs(b, (page, d), 8, wpp))(flat))
        est = costs.sum(axis=0) + statics               # [n_chains]
        actual = []
        for frag in frags:
            w = KVC.pack_kv(q, page=page, stages=frag)
            # +1 byte/page chain id so static wires compare to auto
            actual.append(float(w.wire_nbytes()) * 8 + n_pages_total * 8)
        auto = KVC.pack_kv(q, page=page, stages=sel)
        auto_bits = float(auto.wire_nbytes()) * 8
        best = int(np.argmin(actual))
        raw_bits = cache.size * 32
        total_words = n_pages_total * wpp
        for i in range(len(sel.chains)):
            deltas[i].append((actual[i] - est[i])
                             / (total_words / 1024.0))
        chosen_ids, counts = np.unique(np.asarray(auto.chain_id),
                                       return_counts=True)
        rows.append({
            "set": name, "suite": suite, "n": int(cache.size),
            "chosen": frags[int(chosen_ids[int(np.argmax(counts))])],
            "chosen_pages": {frags[int(c)]: int(k)
                             for c, k in zip(chosen_ids, counts)},
            "best": frags[best],
            "auto_ratio": round(raw_bits / auto_bits, 3),
            "best_ratio": round(raw_bits / actual[best], 3),
            "auto_vs_best": round(actual[best] / auto_bits, 4),
            "chains": {frags[i]: round(raw_bits / actual[i], 3)
                       for i in range(len(frags))},
        })
    return rows, _relative_bias(deltas)


def _relative_bias(deltas) -> tuple:
    """Per-chain median measured-minus-estimated gap, shifted so the
    smallest is 0 — a shared constant (e.g. the §4 outlier-table statics
    every candidate pays identically) cancels in the argmin, so only the
    RELATIVE offsets carry calibration signal."""
    med = [float(np.median(d)) for d in deltas]
    lo = min(med)
    return tuple(round(m - lo, 3) for m in med)


# ------------------------------------------------------ registry write ---

def rewrite_registry_bias(bias_by_set: dict, path: Path | None = None):
    """Rewrite each set's `bias` tuple between the AUTOTUNED markers in
    configs/registry.py — the only generated values; chain membership
    and comments stay hand-edited."""
    path = path or (_REPO_ROOT / "src" / "repro" / "configs"
                    / "registry.py")
    text = path.read_text()
    begin = text.index("# --- AUTOTUNED BEGIN")
    end = text.index("# --- AUTOTUNED END")
    block = text[begin:end]
    for name, bias in bias_by_set.items():
        lit = "(" + ", ".join(f"{b:g}" for b in bias) + ("," if len(bias) == 1 else "") + ")"
        block, nsub = re.subn(
            r'("%s":\s*\{[^}]*"bias":\s*)\([^)]*\)' % re.escape(name),
            lambda m: m.group(1) + lit, block, count=1)
        if nsub != 1:
            raise RuntimeError(f"could not locate bias tuple for {name!r}")
    path.write_text(text[:begin] + block + text[end:])


# ------------------------------------------------------------- driver ----

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.autotune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="small suites for CI")
    ap.add_argument("--full", action="store_true",
                    help="native ~4M-value suites")
    ap.add_argument("--write", action="store_true",
                    help="rewrite the bias tuples in configs/registry.py")
    ap.add_argument("--out", default=str(_REPO_ROOT / "BENCH_select.json"),
                    help="where to write the per-suite rows")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    use_compile_cache()

    from repro.configs.registry import SELECTOR_SETS

    all_rows, bias_by_set = [], {}
    for name, entry in SELECTOR_SETS.items():
        if entry["base"] is None:
            rows, bias = tune_kv_set(name, args.smoke, args.full)
        else:
            rows, bias = tune_pipeline_set(name, args.smoke, args.full)
        all_rows.extend(rows)
        bias_by_set[name] = bias
        for r in rows:
            print(f"{r['set']}.{r['suite']}: chosen={r['chosen']} "
                  f"best={r['best']} auto={r['auto_ratio']}x "
                  f"best={r['best_ratio']}x "
                  f"auto/best={r['auto_vs_best']}")
        print(f"{name}: bias={bias}")

    Path(args.out).write_text(json.dumps(all_rows, indent=1) + "\n")
    print(f"wrote {args.out}")
    if args.write:
        rewrite_registry_bias(bias_by_set)
        print("rewrote SELECTOR_SETS bias tuples in configs/registry.py")


if __name__ == "__main__":
    main()
