"""Exhaustive float32 validation — the paper's §6 claim ("we exhaustively
tested it on all roughly 4 billion possible 32-bit floating-point values").

Sweeps ALL 2^32 bit patterns in slabs through the ABS and REL roundtrip
and verifies, in float64, that every decoded value is within the bound or
bit-identical.  ~2^32 values x a few ebs is CPU-hours: `--slabs N` runs N
random-offset slabs (default 64 x 2^20 ~= 67M values, a superset of every
exponent class); `--full` runs the whole space; `--smoke` runs the CI
subset (the exponent-boundary slabs plus a few random ones).

Slab selection shares `benchmarks.datasets`' crc32-seeded registry
(seeds derive from zlib.crc32 of a name, never the salted built-in
hash), so the checked subset reproduces across processes without
pinning PYTHONHASHSEED — the same discipline as every suite generator.

    PYTHONPATH=src python -m benchmarks.exhaustive_sweep [--full|--smoke]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import QuantizerConfig, roundtrip_dense
from repro.launch.cache import use_compile_cache

from .datasets import _rng

SLAB = 1 << 20


def verify_slab(start: int, cfg: QuantizerConfig) -> int:
    bits = (np.arange(start, start + SLAB, dtype=np.int64)
            .astype(np.uint32))
    x = bits.view(np.float32)
    y = np.asarray(roundtrip_dense(jnp.asarray(x), cfg))
    fin = np.isfinite(x)
    if cfg.mode == "abs":
        bad = np.abs(x[fin].astype(np.float64)
                     - y[fin].astype(np.float64)) > cfg.error_bound
    else:
        m = fin & (x != 0)
        xv = x[m].astype(np.float64)
        bad = np.abs(xv - y[m].astype(np.float64)) / np.abs(xv) \
            > cfg.error_bound
        exact_rest = np.array_equal(x[fin & (x == 0)].view(np.uint32),
                                    y[fin & (x == 0)].view(np.uint32))
        if not exact_rest:
            return SLAB
    nf = ~fin
    if not np.array_equal(x[nf].view(np.uint32), y[nf].view(np.uint32)):
        return int(np.sum(nf))
    return int(np.sum(bad))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--slabs", type=int, default=64)
    ap.add_argument("--eb", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: the exponent-boundary slabs plus "
                         "4 random ones (same flag grammar as run.py)")
    args = ap.parse_args()
    use_compile_cache()

    total_slabs = (1 << 32) // SLAB
    if args.full:
        starts = [i * SLAB for i in range(total_slabs)]
    else:
        n_slabs = 4 if args.smoke else args.slabs
        # crc32-seeded like every datasets.py generator — the checked
        # subset is identical in every process
        starts = sorted(int(i) * SLAB for i in _rng("sweep").choice(
            total_slabs, size=n_slabs, replace=False))
        # always include the exponent-boundary slabs
        starts = sorted(set(starts) | {0, 0x7F000000, 0x7F800000,
                                       0x80000000, 0xFF000000})

    for mode in ("abs", "rel"):
        cfg = QuantizerConfig(mode=mode, error_bound=args.eb, bin_bits=32)
        viol = 0
        t0 = time.time()
        for i, s in enumerate(starts):
            viol += verify_slab(s, cfg)
            if i % 32 == 31:
                print(f"  {mode}: {i+1}/{len(starts)} slabs, "
                      f"violations={viol}, {time.time()-t0:.0f}s",
                      flush=True)
        n = len(starts) * SLAB
        print(f"{mode} eb={args.eb:g}: {n/2**30:.2f}G values checked, "
              f"violations={viol}")
        if viol:
            sys.exit(1)
    print("exhaustive sweep: GUARANTEE HOLDS on every checked value")


if __name__ == "__main__":
    main()
