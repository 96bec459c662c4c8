"""Guarantee-audit bench (DESIGN.md §12): detection coverage + overhead.

Two tables, both written to the committed BENCH_audit.json artifact:

  detection  the fault-injection matrix: every `runtime.guard` fault
             class against every registry pipeline preset, every `auto`
             selector set, and every KV page chain (static and
             selected).  Each applicable wire fault must flip the §12
             checksum verdict; `nan_input` must surface in the
             `verify=` audit report (`n_nonfinite > 0`); and the CLEAN
             wire must pass its own checksum (zero false positives).
             Any miss makes the process exit nonzero, so the CI smoke
             step doubles as a gate.

  overhead   `encode(verify=True)` vs plain encode on the lossless
             GRAD_SUITES rows (the `benchmarks.run lossless` chains at
             eb = 2^-8 * rms).  The audit fuses decode-and-check into
             planes the encoder already computed, so the target is
             <= 5% — the acceptance bound the artifact is committed
             under.

Usage: PYTHONPATH=src python -m benchmarks.audit_bench
           [--smoke] [--out PATH]

--smoke shrinks datasets/repeats for CI; --out defaults to the repo
root's BENCH_audit.json.  Render the artifact as markdown via
`benchmarks.roofline --audit-bench`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.registry import (KV_PAGE_CHAINS, PIPELINES,
                                    SELECTOR_SETS, get_pipeline)
from repro.core.pipeline import parse_pipeline
from repro.core.select import get_kv_selector, get_selector
from repro.compression.kv import kv_quantizer_config, pack_kv, quantize_kv
from repro.launch.cache import use_compile_cache
from repro.runtime import guard

from . import datasets

OUT_DEFAULT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_audit.json")
OVERHEAD_BOUND = 0.05          # the committed acceptance bound


def _time_pair(f0, f1, x, repeats=5):
    """Paired-difference ABBA timing: run the two variants back to back,
    alternating the order each pair, and estimate the overhead as the
    MEDIAN of per-pair deltas over the fastest plain run.  Adjacent runs
    share the machine state, so the delta distribution centers on the
    true audit cost (~ms) even when absolute run times drift 10-20% over
    the sweep, and the ABBA order flip cancels within-pair drift (a
    slowdown ramping through a pair penalizes whichever member runs
    second — fixed-order pairs turned that into a +10% phantom overhead
    on whole rows).  Separate min/median estimates were even worse,
    swinging -14%..+31% on a cost the isolated audit pass puts at <1%."""
    for _ in range(3):             # compile + shake off first-window drift
        jax.block_until_ready(f0(x))
        jax.block_until_ready(f1(x))
    t0s, diffs = [], []
    for i in range(repeats):
        first, second = (f0, f1) if i % 2 == 0 else (f1, f0)
        t = time.perf_counter()
        jax.block_until_ready(first(x))
        ta = time.perf_counter() - t
        t = time.perf_counter()
        jax.block_until_ready(second(x))
        tb = time.perf_counter() - t
        t0, t1 = (ta, tb) if i % 2 == 0 else (tb, ta)
        t0s.append(t0)
        diffs.append(t1 - t0)
    base = float(min(t0s))
    return base, base + float(np.median(diffs))


def _grad(n):
    return jnp.asarray(datasets.GRAD_SUITES["gradsmooth"]()[:n])


def _detection_row(kind, name, matrix, clean_ok):
    ok = clean_ok and all(matrix.values())
    print(f"detection.{kind}.{name}: "
          + " ".join(f"{k}={'ok' if v else 'MISS'}"
                     for k, v in matrix.items())
          + ("" if clean_ok else " CLEAN-FALSE-POSITIVE"))
    return dict(kind=kind, name=name, matrix=matrix, clean_ok=clean_ok,
                all_detected=ok)


def detection(smoke: bool) -> list:
    """The coverage matrix: corrupt, then ask the checksum."""
    n = 1 << 16 if smoke else 1 << 20
    rows = []

    # every registry pipeline preset -> an Encoded wire.  Data matches
    # the quantizer: REL chains get the mixed-sign REL suite (gradient
    # noise at rel:0.001|pack:8 is all-outlier — empty payloads would
    # make length faults vacuous no-ops); ABS chains get the gradient
    # suite with the lossless rows' rms-scaled bound for placeholder
    # (eb=1.0) presets.
    g = _grad(n)
    relmix = jnp.asarray(datasets.rel_mixed()[:n])
    rms = float(jnp.sqrt(jnp.mean(g * g)))
    for preset in sorted(PIPELINES):
        pipe = parse_pipeline(get_pipeline(preset))
        x = relmix if pipe.quant.mode == "rel" else g
        eb = rms * 2.0 ** -8 if pipe.quant.eb == 1.0 else None
        enc = pipe.encode(x, eb=eb, integrity=True)
        matrix = guard.detection_matrix(enc, suite=preset)
        plan = guard.FaultPlan(preset, "nan_input")
        _, rep = pipe.encode(plan.corrupt_input(x), eb=eb, verify=True,
                             integrity=True)
        matrix["nan_input"] = int(rep.n_nonfinite) > 0
        rows.append(_detection_row("pipeline", preset, matrix, True))

    # every auto selector set -> a SelectedWire (suite data the set was
    # autotuned for: gradients for grad-wire, the NYX field for
    # sci-plane's abs:64.0 bound)
    nyx = jnp.asarray(datasets.SUITES["NYX"]()[:n])
    for set_name, entry in SELECTOR_SETS.items():
        if entry["base"] is None:        # kv-page: fragments, covered below
            continue
        sel = get_selector(set_name)
        x = nyx if set_name == "sci-plane" else g
        eb = rms * 2.0 ** -8 if sel.qcfg().error_bound == 1.0 else None
        wire = sel.encode(x, eb=eb, integrity=True)
        matrix = guard.detection_matrix(wire, suite=set_name,
                                        n_chains=len(entry["chains"]))
        plan = guard.FaultPlan(set_name, "nan_input")
        _, rep = sel.encode(plan.corrupt_input(x), eb=eb, verify=True,
                            integrity=True)
        matrix["nan_input"] = int(rep.n_nonfinite) > 0
        rows.append(_detection_row("selector", f"auto:{set_name}", matrix,
                                   True))

    # KV page chains: static presets + the per-page auto selector
    r = datasets._rng("audit-kv-cache")
    s = 256 if smoke else 1024
    cache = r.standard_normal((2, 2, s, 64)).astype(np.float32)
    cache[:, :, int(s * 0.6):, :] = 0.0
    q = quantize_kv(jnp.asarray(cache), kv_quantizer_config())
    for preset, frag in KV_PAGE_CHAINS.items():
        p = pack_kv(q, stages=frag, integrity=True)
        rows.append(_detection_row(
            "kv", preset, guard.detection_matrix(p, suite=preset), True))
    ksel = get_kv_selector("kv-page")
    p = pack_kv(q, stages=ksel, integrity=True)
    rows.append(_detection_row(
        "kv", "auto:kv-page",
        guard.detection_matrix(p, suite="kv-page", n_chains=3), True))
    rows.append(ring_detection())
    return rows


# in-flight §12 coverage: the per-hop plane checksums of the verified
# ring reduce (Transport.reduce_mean(integrity='drop')) against a
# `hop_bitflip` fault hook, on this process's own devices.
def ring_detection() -> dict:
    """`hop_bitflip` row: clean ring keeps every contribution (no false
    positives); a corrupted hop is dropped on every receiving rank.
    Needs two devices: on CPU, start the process with
    XLA_FLAGS=--xla_force_host_platform_device_count=2."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compression.grads import GradCompressionConfig, compress_shard
    from repro.core.transport import TRANSPORT, Transport

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the ring detection row needs 2 devices, this process has "
            f"{len(jax.devices())}; on CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=2 before JAX starts")
    mesh = jax.make_mesh((2,), ("pod",), devices=jax.devices()[:2],
                         axis_types=(jax.sharding.AxisType.Auto,))
    # bin_bits=16 keeps the shards outlier-free so the §8 ring fires
    # (outliers would route the reduce to the gather fallback instead)
    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=16,
                                outlier_cap_frac=1 / 16)
    pipe, n = cfg.pipe(), 4096

    def run(tp, g):
        def f(v):
            shard, _ = compress_shard(v, cfg, integrity=True)
            mean, nv = tp.reduce_mean(shard.enc, pipe, n, "pod",
                                      integrity="drop", return_valid=True)
            return mean, nv[None]
        smap = jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                             out_specs=(P("pod"), P("pod")),
                             axis_names={"pod"}, check_vma=False)
        gd = jax.device_put(jnp.asarray(g),
                            NamedSharding(mesh, P("pod", None)))
        mean, nv = jax.jit(smap)(gd)
        return np.asarray(mean), np.asarray(nv).tolist()

    r = np.random.default_rng(__import__("zlib").crc32(b"ring-hop"))
    g = np.broadcast_to((r.standard_normal(n) * 1e-2).astype(np.float32),
                        (2, n)).copy()
    _, clean = run(TRANSPORT, g)
    plan = guard.FaultPlan("ring", "hop_bitflip")
    mean_f, fault = run(Transport(fault=plan.corrupt_hop), g)
    if not np.all(np.isfinite(mean_f)):
        raise AssertionError("degraded ring mean is not finite")
    clean_ok = clean == [2, 2]
    detected = bool(fault) and all(v < 2 for v in fault)
    return _detection_row("transport", "ring:reduce_mean",
                          {"hop_bitflip": detected}, clean_ok)


def overhead(smoke: bool) -> list:
    """verify= cost on the lossless GRAD_SUITES rows (run.py's chains)."""
    cut = 1 << 18 if smoke else None
    reps = 1 if smoke else 9
    chains = ("zero", "narrow", "narrow|ent", "delta|narrow|ent")
    rows = []
    for suite, gen in datasets.GRAD_SUITES.items():
        g = jnp.asarray(gen()[:cut])
        eb = float(jnp.sqrt(jnp.mean(g * g))) * 2.0 ** -8
        for chain in chains:
            pred = "delta|" if chain.startswith("delta|") else ""
            word = chain.removeprefix("delta|")
            pipe = parse_pipeline(
                f"{pred}abs:{eb!r}:cap=0.015625|pack:16|{word}")
            f_plain = jax.jit(lambda v, p=pipe: p.encode(v, kernels=False))
            f_verify = jax.jit(
                lambda v, p=pipe: p.encode(v, verify=True))
            t0, t1 = _time_pair(f_plain, f_verify, g, repeats=reps)
            frac = t1 / t0 - 1.0
            _, rep = f_verify(g)
            print(f"overhead.{suite}.{chain.replace('|', '+')}: "
                  f"plain={t0 * 1e6:.0f}us verify={t1 * 1e6:.0f}us "
                  f"overhead={frac * 100:+.1f}% "
                  f"violations={int(rep.violations)}")
            rows.append(dict(
                suite=suite, chain=chain, t_plain_us=t0 * 1e6,
                t_verify_us=t1 * 1e6, overhead_frac=frac,
                violations=int(rep.violations),
                max_err=float(rep.max_err), eb=eb))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.audit_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="small datasets / single repeats (CI)")
    ap.add_argument("--out", default=OUT_DEFAULT,
                    help="artifact path (default: repo BENCH_audit.json)")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    use_compile_cache()

    det = detection(args.smoke)
    ovh = overhead(args.smoke)
    doc = dict(smoke=bool(args.smoke), detection=det, overhead=ovh)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")

    failures = [r for r in det if not r["all_detected"]]
    if failures:
        print(f"DETECTION FAILURES: {[r['name'] for r in failures]}")
        return 1
    bad = [r for r in ovh if r["violations"] != 0]
    if bad:
        print(f"AUDIT VIOLATIONS ON CLEAN ENCODES: "
              f"{[(r['suite'], r['chain']) for r in bad]}")
        return 1
    worst = max(ovh, key=lambda r: r["overhead_frac"])
    print(f"worst verify overhead: {worst['overhead_frac'] * 100:+.1f}% "
          f"({worst['suite']}.{worst['chain']}) bound "
          f"{OVERHEAD_BOUND * 100:.0f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
