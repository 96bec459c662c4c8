"""Benchmark harness — one function per paper table/figure, plus
beyond-paper system benchmarks.  Prints ``name,us_per_call,derived`` CSV
(derived = the table's metric: ratio, GB/s, %, ...).

  table3   special-value handling matrix (paper Table 3)
  table4   REL ratio: library log/pow vs parity-safe approximations (Fig 1)
  table56  REL codec throughput: original vs replaced fns (Fig 2, T5/T6)
  table7   ABS throughput: protected vs unprotected (Fig 3)
  table8   ABS ratio: protected vs unprotected (Fig 4)
  table9   % values hitting the rounding-error fallback
  ckpt     checkpoint codec ratio (beyond paper)
  kv       KV-cache compression footprint + error (beyond paper)
  gradwire cross-pod gradient wire bytes (beyond paper)
  packedwire packed vs unpacked wire + codec throughput (beyond paper)
  lossless device-side lossless stages: end-to-end ratio vs packed/f32
           on gradient-shaped + scientific data, KV pages, Pallas
           parity, the shuffle stage on mixed-sign REL bins, the
           `ent` entropy stage over surviving chunk payloads, and the
           closed-loop predictor rows (`delta` on the correlated
           gradient walk, 2-D `lorenzo` on the NYX-like plane, §9)
  transfer prefill->decode KV transfer (DESIGN.md §8): PackedCache wire
           bytes per stage chain (incl. the §9 `kvdelta` page chain)
           vs raw pages, pack/unpack throughput, and simulated link
           occupancy under load

Usage: PYTHONPATH=src python -m benchmarks.run [names...]
           [--pipeline SPEC|PRESET] [--smoke]

--pipeline benches an arbitrary pipeline chain (DESIGN.md §7 spec string
like "rel:1e-3|pack:8|zero|narrow", a configs.registry preset name, or
"auto" / "auto:SET" for the §11 adaptive selector — the chosen chain is
reported per suite) in the `lossless` table; --smoke shrinks the
lossless and transfer tables' datasets/repeats for CI.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (QuantizerConfig, compression_ratio, decode_dense,
                        encode_dense, roundtrip_dense, serialize)
from repro.core.quantizer import (quantize_abs, quantize_abs_unprotected,
                                  quantize_rel, quantize_rel_library)
from repro.launch.cache import use_compile_cache

from . import datasets

EB = 1e-3      # the paper's evaluation bound for Figs 1-4


def _time(f, *args, repeats=5):
    f(*args)                                    # compile/warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = f(*args)
        jax.block_until_ready(r)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------- tables --

def table3():
    """Paper Table 3: which value classes are handled with the bound
    guaranteed.  For LC(ours) every cell must be 'ok'."""
    x = datasets.special_values()
    for mode in ("abs", "rel"):
        cfg = QuantizerConfig(mode=mode, error_bound=EB, bin_bits=32)
        t0 = time.perf_counter()
        y = np.asarray(roundtrip_dense(jnp.asarray(x), cfg))
        us = (time.perf_counter() - t0) * 1e6
        fin = np.isfinite(x)
        if mode == "abs":
            viol = np.sum(np.abs(x[fin].astype(np.float64) - y[fin]) > EB)
        else:
            m = fin & (x != 0)
            viol = np.sum(np.abs((x[m].astype(np.float64) - y[m])
                                 / x[m].astype(np.float64)) > EB)
        exact = np.array_equal(x[~fin].view(np.uint32),
                               y[~fin].view(np.uint32))
        status = "ok" if viol == 0 and exact else f"VIOLATIONS={viol}"
        _emit(f"table3.{mode}.normal+inf+nan+denormal", us, status)


def _rel_est_ratio(x, outlier):
    # bins+payload+sign cost model (matches the serializer layout)
    n_out = float(jnp.sum(outlier))
    bits = x.size * 16 + n_out * 32 + x.size
    return x.size * 32 / bits


def table4():
    """Fig 1 / Table 4: REL compression ratio, parity-safe bit-trick
    log2/pow2 vs the library functions.

    Two comparisons are reported:
      * freestep — the paper's setting (w = log2(1+eb) exactly): the
        bit-trick's octave-slope error pushes border values to the
        lossless fallback, reproducing the paper's ~5% loss;
      * pow2step — OUR production codec: the pow2-floored step absorbs
        that slope error entirely, so parity costs NO ratio vs the
        library (a beyond-paper improvement; the <=1-bit finer step is
        already included in both sides).
    """
    from .ablation import quantize_rel_freestep

    cfg = QuantizerConfig(mode="rel", error_bound=EB, bin_bits=32)
    fs_ratios, ps_ratios = [], []
    for name, gen in datasets.SUITES.items():
        x = gen()
        xj = jnp.asarray(x)
        t0 = time.perf_counter()
        q_ours = quantize_rel(xj, cfg)
        jax.block_until_ready(q_ours.bins)
        us = (time.perf_counter() - t0) * 1e6
        q_lib = quantize_rel_library(xj, cfg)
        _, out_fs_trick = quantize_rel_freestep(xj, cfg, library=False)
        _, out_fs_lib = quantize_rel_freestep(xj, cfg, library=True)

        fs = (_rel_est_ratio(x, out_fs_trick)
              / _rel_est_ratio(x, out_fs_lib))
        ps = (_rel_est_ratio(x, q_ours.outlier)
              / _rel_est_ratio(x, q_lib.outlier))
        fs_ratios.append(fs)
        ps_ratios.append(ps)
        _emit(f"table4.{name}", us,
              f"freestep_norm={fs:.4f} pow2step_norm={ps:.4f}")
    _emit("table4.geomean.freestep", 0.0,
          f"{np.exp(np.mean(np.log(fs_ratios))):.4f} (paper: ~0.948)")
    _emit("table4.geomean.pow2step", 0.0,
          f"{np.exp(np.mean(np.log(ps_ratios))):.4f} (ours: parity is free)")


def table56():
    """Fig 2 / Tables 5-6: REL throughput with replaced vs library fns
    (paper: within +-1%).  GB/s of the jitted quantize on this CPU."""
    cfg = QuantizerConfig(mode="rel", error_bound=EB, bin_bits=32)
    f_ours = jax.jit(lambda v: quantize_rel(v, cfg).bins)
    f_lib = jax.jit(lambda v: quantize_rel_library(v, cfg).bins)
    for name in ("CESM", "HACC", "QMCPACK"):
        x = jnp.asarray(datasets.SUITES[name]())
        t_ours = _time(f_ours, x)
        t_lib = _time(f_lib, x)
        gbs = x.size * 4 / t_ours / 1e9
        _emit(f"table56.compress.{name}", t_ours * 1e6,
              f"{gbs:.2f}GB/s rel_to_lib={t_lib / t_ours:.3f}")


def table7():
    """Fig 3 / Table 7: ABS compression throughput, double-check protected
    vs unprotected (paper: no significant change on memory-bound GPU; this
    CPU is compute-bound so the checks cost ~10-15% — the TPU VPU roofline
    argument is in EXPERIMENTS.md)."""
    cfg = QuantizerConfig(mode="abs", error_bound=EB, bin_bits=32)
    f_p = jax.jit(lambda v: quantize_abs(v, cfg).bins)
    f_u = jax.jit(lambda v: quantize_abs_unprotected(v, cfg).bins)
    for name in ("CESM", "EXAALT", "SCALE"):
        x = jnp.asarray(datasets.SUITES[name]())
        t_p, t_u = _time(f_p, x), _time(f_u, x)
        _emit(f"table7.{name}", t_p * 1e6,
              f"{x.size*4/t_p/1e9:.2f}GB/s protected/unprotected="
              f"{t_u / t_p:.3f}")


def table8():
    """Fig 4 / Table 8: ABS ratio protected vs unprotected (paper: ~5%
    lower with protection, EXAALT worst)."""
    import zlib

    # bin_bits=32: the suites span O(100) magnitudes, so eb=1e-3 needs
    # ~18-bit bins — int16 would make everything a range outlier
    cfg = QuantizerConfig(mode="abs", error_bound=EB, bin_bits=32)
    rels = []
    for name, gen in datasets.SUITES.items():
        x = gen()
        r_p = compression_ratio(x, cfg)
        q = quantize_abs_unprotected(jnp.asarray(x), cfg)
        n_out = float(jnp.sum(q.outlier))
        bins32 = np.asarray(q.bins, np.int64).astype(np.int32).tobytes()
        stream = zlib.compress(bins32, 6)
        r_u = x.nbytes / (len(stream) + n_out * 4 + 24)
        rels.append(r_p / r_u)
        _emit(f"table8.{name}", 0.0,
              f"protected={r_p:.2f}x unprotected={r_u:.2f}x "
              f"norm={r_p / r_u:.4f}")
    _emit("table8.geomean", 0.0,
          f"{np.exp(np.mean(np.log(rels))):.4f} (paper: ~0.95)")


def table9():
    """Table 9: % of values whose rounding error forces the lossless
    fallback (paper avg 0.00-3.41%, max 11.16%).

    Production codec column is ~0% BY CONSTRUCTION: pow2 steps make the
    quantization arithmetic exact, eliminating the paper's rounding-error
    class entirely (the cost moved into <=1-bit-finer bins).  The REL
    freestep column reproduces the paper's effect."""
    from .ablation import quantize_rel_freestep

    cfg = QuantizerConfig(mode="abs", error_bound=EB, bin_bits=32)
    cfg_r = QuantizerConfig(mode="rel", error_bound=EB, bin_bits=32)
    for name, gen in datasets.SUITES.items():
        x = gen()
        q = quantize_abs(jnp.asarray(x), cfg)
        qu = quantize_abs_unprotected(jnp.asarray(x), cfg)
        extra = float(jnp.sum(q.outlier)) - float(jnp.sum(qu.outlier))
        _, fs_trick = quantize_rel_freestep(jnp.asarray(x), cfg_r, False)
        _, fs_lib = quantize_rel_freestep(jnp.asarray(x), cfg_r, True)
        fs = (float(jnp.sum(fs_trick)) - float(jnp.sum(fs_lib))) / x.size
        _emit(f"table9.{name}", 0.0,
              f"pow2step={100 * extra / x.size:.3f}% "
              f"freestep_rel={100 * fs:.3f}%")


# ------------------------------------------------------- beyond paper ----

def ckpt():
    """Checkpoint codec: LC-serialized f32 master weights vs raw."""
    r = datasets._rng("ckpt-weights")
    w = (r.standard_normal(1 << 21) * 0.02).astype(np.float32)
    for eb in (1e-5, 1e-6, 1e-7):
        cfg = QuantizerConfig(mode="abs", error_bound=eb)
        t0 = time.perf_counter()
        stream = serialize(w, cfg)
        us = (time.perf_counter() - t0) * 1e6
        _emit(f"ckpt.eb{eb:g}", us, f"{w.nbytes / len(stream):.2f}x")


def kv():
    """KV-cache quantization: footprint + worst-page error vs bound, plus
    the packed wire form a cache migration would ship."""
    from repro.compression.kv import (dequantize_kv, kv_quantizer_config,
                                      kv_wire_bytes, pack_kv, quantize_kv)
    r = datasets._rng("kv-cache")
    k = jnp.asarray(r.standard_normal((2, 4, 1024, 128)).astype(np.float32))
    cfg = kv_quantizer_config()
    t0 = time.perf_counter()
    q = quantize_kv(k, cfg)
    jax.block_until_ready(q.bins)
    us = (time.perf_counter() - t0) * 1e6
    comp = (q.bins.size + q.eb2.size * 4 + q.out_idx.size * 4
            + q.out_val.size * 4 + q.overflow.size)
    y = dequantize_kv(q)
    err = float(jnp.max(jnp.abs(k - y)))
    _emit("kv.int8+outliers", us,
          f"{k.size * 4 / comp:.2f}x max_err={err:.4f}")
    p = pack_kv(q)
    assert p.nbytes() == kv_wire_bytes(k.shape)
    _emit("kv.packed_wire", 0.0,
          f"{k.size * 4 / p.nbytes():.2f}x vs f32 on the wire")


def gradwire():
    """Cross-pod gradient wire bytes: packed-words wire vs f32 psum.
    wire_bytes is the MEASURED footprint of CompressedShard (what the
    all-gather moves), not an estimate."""
    from repro.compression.grads import (CompressedShard,  # noqa: F401
                                         GradCompressionConfig, compress_shard,
                                         wire_bytes)
    cfg = GradCompressionConfig()
    n = 1 << 24
    shard, _ = compress_shard(jnp.zeros((n,), jnp.float32), cfg)
    assert shard.nbytes() == wire_bytes(n, cfg)
    _emit("gradwire.packed+outliers", 0.0,
          f"{n * 4 / wire_bytes(n, cfg):.2f}x less traffic")


def packedwire():
    """Packed vs unpacked codec pipeline and wire.

    Honest accounting: encode_compact already narrows bins to bin_bits
    DEVICE-side, so at bin_bits in {8, 16} the packed uint32 words are
    byte-parity with the narrowed bins on the wire (reported below as a
    check, ~1.0x).  What the fused pipeline buys instead:
      * pipeline HBM: the seed quantize kernel emitted int32 bins + bool
        outlier + f32 recon planes (9 B/elem) and narrowing was a separate
        XLA pass; fused quantize+pack emits words + bool (bb/8 + 1 B/elem)
        in ONE pass.
      * wire vs f32 psum: the headline gradient-compression ratio.
      * REL sign plane: 1 bit/value packed vs XLA's byte-wide bool (8x).
    Also times the jitted encode paths — the pack must ride under the same
    memory stream (pack/nopack ~ 1.0).
    """
    from repro.core import (decode_packed, encode_compact, encode_packed,
                            packed_word_count)
    r = datasets._rng("packed-wire")
    n = 1 << 22
    x = jnp.asarray((r.standard_normal(n) * 0.02).astype(np.float32))
    for bb in (8, 16):
        cfg = QuantizerConfig(mode="abs", error_bound=1e-4, bin_bits=bb,
                              outlier_cap_frac=1 / 64)
        k = cfg.outlier_cap(n)
        f_un = jax.jit(lambda v, c=cfg: encode_compact(v, c))
        f_pk = jax.jit(lambda v, c=cfg: encode_packed(v, c))
        f_rt = jax.jit(lambda v, c=cfg: decode_packed(encode_packed(v, c),
                                                      c, n=v.size))
        t_un = _time(f_un, x)
        t_pk = _time(f_pk, x)
        t_rt = _time(f_rt, x)
        seed_hbm = n * (4 + 1 + 4)                 # int32 + bool + f32 recon
        fused_hbm = n * bb // 8 + n                # packed words + bool
        compact_wire = n * bb // 8 + k * 8 + 4     # narrowed bins + table
        pk_bytes = packed_word_count(n, bb) * 4 + k * 8 + 8
        _emit(f"packedwire.abs.bb{bb}", t_pk * 1e6,
              f"pipeline_hbm {seed_hbm / fused_hbm:.2f}x less "
              f"wire {n * 4 / pk_bytes:.2f}x vs f32 "
              f"(parity vs narrowed-compact {compact_wire / pk_bytes:.2f}x) "
              f"enc={x.size * 4 / t_pk / 1e9:.2f}GB/s "
              f"pack/nopack={t_pk / t_un:.3f} roundtrip={t_rt * 1e6:.0f}us")
    cfg = QuantizerConfig(mode="rel", error_bound=1e-3, bin_bits=16,
                          outlier_cap_frac=1 / 8)
    k = cfg.outlier_cap(n)
    f_pk = jax.jit(lambda v: encode_packed(v, cfg))
    t_pk = _time(f_pk, x)
    pk_bytes = (packed_word_count(n, 16) * 4
                + packed_word_count(n, 1) * 4 + k * 8 + 8)
    unpacked_sign = n * 2 + n + k * 8 + 4          # int16 + byte-wide bool sign
    _emit("packedwire.rel.bb16", t_pk * 1e6,
          f"{n * 4 / pk_bytes:.2f}x vs f32, sign plane 8x (1bit vs bool: "
          f"wire {unpacked_sign / pk_bytes:.2f}x smaller) "
          f"enc={x.size * 4 / t_pk / 1e9:.2f}GB/s")


def _bench_pipeline_chain(spec: str, smoke: bool):
    """Bench one arbitrary pipeline chain (--pipeline): transmitted-wire
    ratio vs the packed-only prefix and vs f32, on the gradient suites
    plus the `iid` noise suite and the mixed-sign REL suite.  'auto' /
    'auto:SET' specs (DESIGN.md §11) run the adaptive selector — the
    per-suite chosen chain is emitted alongside the ratios."""
    from repro.core import select as SEL
    from repro.core.pipeline import Pipeline

    pipe = SEL.parse_chain(spec)
    pk_pipe = Pipeline(pipe.quant, pipe.pack)      # packed-only prefix
    cut = 1 << 18 if smoke else None
    suites = dict(datasets.GRAD_SUITES, iid=datasets.iid,
                  relmix=datasets.rel_mixed)
    for name, gen in suites.items():
        x = jnp.asarray(gen()[:cut])
        f = jax.jit(lambda v: pipe.encode(v))
        enc = f(x)
        t = _time(f, x, repeats=1 if smoke else 5)
        bits = float(pipe.wire_bits(enc, x.size))
        pk_bits = pk_pipe.wire_bits(pk_pipe.encode(x, kernels=False), x.size)
        chosen = ""
        if isinstance(pipe, SEL.Selector):
            chosen = f"chosen={pipe.chains[int(enc.chain_id)].spec()} "
        # honest accounting: overflow means the capped table could NOT
        # absorb the outliers — the bound is not met and a real caller
        # must take the lossless fallback; a ratio alone would hide that
        _emit(f"lossless.pipeline.{name}", t * 1e6,
              f"spec={pipe.spec()} {chosen}"
              f"vs_packed={pk_bits / bits:.2f}x "
              f"vs_f32={x.size * 32 / bits:.2f}x "
              f"overflow={bool(enc.overflow)} "
              f"outliers={float(enc.n_outliers) / x.size:.3f}")


def lossless(pipeline: str | None = None, smoke: bool = False):
    """Device-side lossless stages (DESIGN.md §6/§7): end-to-end
    transmitted-wire ratio of the pipeline's `Encoded` vs the packed-only
    wire and vs f32.

    Rows:
      * gradient wire (pack:16, eb = 2^-8 * rms): the realistic
        smooth/sparse gradients must beat the packed wire (zero chunks
        dominate dead rows); the adversarial dense gradient shows the ~1x
        floor — the stage never costs more than the small header plane.
      * scientific suites: NYX (non-negative, wide range) is where
        width-narrowing pays beyond zero suppression; CESM (dense smooth
        field) sits at the ~1x floor.
      * mixed-sign REL bins: narrow alone sits at its floor (sign
        extension sets the high bits of every word); the shuffle stage's
        zigzag fold + byte-plane shuffle is what unlocks the win.
      * closed-loop predictors (DESIGN.md §9): `delta` residual bins on
        the correlated gradient walk (gradwalk) and 2-D `lorenzo` on the
        NYX-like plane — each must beat its plain narrow|ent twin where
        neighbour correlation exists (iid data pays a few % vs ent:
        folded residuals of white noise are a touch wider than raw bins).
      * KV pages: a cache whose tail pages are unwritten (zeros).
      * Pallas parity: the pipeline's fused-kernel dispatch must be
        bit-identical to its jit reference in interpret mode.

    --pipeline SPEC replaces the fixed rows with the given chain.
    """
    from repro.compression.grads import (GradCompressionConfig,
                                         compress_shard, wire_bytes)
    from repro.compression.kv import kv_quantizer_config, pack_kv, quantize_kv
    from repro.core import parse_pipeline

    if pipeline is not None:
        _bench_pipeline_chain(pipeline, smoke)
        return

    cut = 1 << 18 if smoke else None      # --smoke: small data, 1 repeat
    reps = 1 if smoke else 5

    # the pred row (§9): closed-loop `delta` residuals on the bin plane
    # ahead of narrow|ent — must beat plain narrow|ent on the correlated
    # walk (gradwalk) and must not cost anything on the iid suites
    grad_chains = ("zero", "narrow", "narrow|ent", "delta|narrow|ent")
    for name, gen in datasets.GRAD_SUITES.items():
        g = jnp.asarray(gen()[:cut])
        n = g.size
        for stage in grad_chains:
            pred = "delta|" if stage.startswith("delta|") else ""
            word = stage.removeprefix("delta|")
            cfg = GradCompressionConfig(
                bin_bits=16,
                pipeline=f"{pred}abs:1.0:cap=0.015625|pack:16|{word}")
            f = jax.jit(lambda v, c=cfg: compress_shard(v, c)[0])
            shard = f(g)
            t = _time(f, g, repeats=reps)
            lc_b = float(shard.nbytes())
            pk_b = wire_bytes(n, cfg)
            _emit(f"lossless.{name}.{stage.replace('|', '+')}", t * 1e6,
                  f"vs_packed={pk_b / lc_b:.2f}x vs_f32={n * 4 / lc_b:.2f}x "
                  f"(packed_only {n * 4 / pk_b:.2f}x) "
                  f"enc={n * 4 / t / 1e9:.2f}GB/s")

    for name, eb, bb in (("NYX", 64.0, 32), ("CESM", 1e-3, 32)):
        x = jnp.asarray(datasets.SUITES[name]()[:cut])
        pk_pipe = parse_pipeline(f"abs:{eb!r}:cap=0.015625|pack:{bb}")
        pk_bits = pk_pipe.wire_bits(pk_pipe.encode(x, kernels=False), x.size)
        for chain in ("narrow", "narrow|ent"):
            pipe = parse_pipeline(
                f"abs:{eb!r}:cap=0.015625|pack:{bb}|{chain}")
            f = jax.jit(lambda v, p=pipe: p.encode(v))
            lc = f(x)
            t = _time(f, x, repeats=reps)
            lc_bits = float(pipe.wire_bits(lc, x.size))
            _emit(f"lossless.{name}.{chain.replace('|', '+')}", t * 1e6,
                  f"vs_packed={pk_bits / lc_bits:.2f}x "
                  f"vs_f32={x.size * 32 / lc_bits:.2f}x "
                  f"enc={x.size * 4 / t / 1e9:.2f}GB/s")

    # 2-D smooth plane (NYX-like slice): the `lorenzo` predictor's row
    # (§9) — the 2-D input's shape reaches the stage as pred_shape, so
    # residuals are second differences over the plane; must beat the
    # plain narrow|ent chain on the same data
    x2 = jnp.asarray(datasets.nyx_plane(512 if smoke else 1024))
    pk_pipe = parse_pipeline("abs:64.0:cap=0.015625|pack:32")
    pk_bits = pk_pipe.wire_bits(pk_pipe.encode(x2, kernels=False), x2.size)
    for chain in ("abs:64.0:cap=0.015625|pack:32|narrow|ent",
                  "lorenzo|abs:64.0:cap=0.015625|pack:32|narrow|ent"):
        pipe = parse_pipeline(chain)
        f = jax.jit(lambda v, p=pipe: p.encode(v))
        lc = f(x2)
        t = _time(f, x2, repeats=reps)
        lc_bits = float(pipe.wire_bits(lc, x2.size))
        label = "lorenzo+narrow+ent" if pipe.pred else "narrow+ent"
        _emit(f"lossless.nyxplane.{label}", t * 1e6,
              f"vs_packed={pk_bits / lc_bits:.2f}x "
              f"vs_f32={x2.size * 32 / lc_bits:.2f}x "
              f"enc={x2.size * 4 / t / 1e9:.2f}GB/s")

    # mixed-sign REL bins: the shuffle stage's reason to exist (§7), and
    # the entropy stage stacked on top of it
    x = jnp.asarray(datasets.rel_mixed()[:cut])
    pk_pipe = parse_pipeline("rel:0.001|pack:32")
    pk_bits = pk_pipe.wire_bits(pk_pipe.encode(x, kernels=False), x.size)
    for chain, label in (("narrow", "narrow"),
                         ("shuffle|narrow", "shuffle+narrow"),
                         ("shuffle|narrow|ent", "shuffle+narrow+ent")):
        pipe = parse_pipeline(f"rel:0.001|pack:32|{chain}")
        f = jax.jit(lambda v, p=pipe: p.encode(v))
        enc = f(x)
        t = _time(f, x, repeats=reps)
        bits = float(pipe.wire_bits(enc, x.size))
        _emit(f"lossless.relmix.{label}", t * 1e6,
              f"vs_packed={pk_bits / bits:.2f}x "
              f"vs_f32={x.size * 32 / bits:.2f}x "
              f"enc={x.size * 4 / t / 1e9:.2f}GB/s")

    # KV: tail pages unwritten (zeros) — the migration wire drops them,
    # and `ent` squeezes the written pages below narrow's byte floor
    r = datasets._rng("kv-tail-pages")
    cache = r.standard_normal((2, 4, 1024, 64)).astype(np.float32)
    cache[:, :, 600:, :] = 0.0
    q = quantize_kv(jnp.asarray(cache), kv_quantizer_config())
    pk = pack_kv(q)
    for stages in ("zero", "narrow|ent"):
        lc = pack_kv(q, stages=stages)
        _emit(f"lossless.kv.{stages.replace('|', '+')}", 0.0,
              f"vs_packed={pk.nbytes() / float(lc.wire_nbytes()):.2f}x "
              f"vs_f32={cache.nbytes / float(lc.wire_nbytes()):.2f}x")

    # Pallas fused dispatch vs jit reference: bit-identical (compiled on
    # a TPU, interpret mode elsewhere)
    x = jnp.asarray(datasets.GRAD_SUITES["gradsmooth"]()[:1 << 19])
    pipe = parse_pipeline("abs:1e-05:cap=0.015625|pack:16|narrow")
    ref = pipe.encode(x, kernels=False)
    ker = pipe.encode(x, kernels=True)
    same = all(
        (a is None and b is None) or (np.array_equal(np.asarray(a),
                                                     np.asarray(b))
                                      if not isinstance(a, tuple) else
                                      all(np.array_equal(np.asarray(p),
                                                         np.asarray(q_))
                                          for p, q_ in zip(a, b)))
        for a, b in zip(ref, ker))
    _emit("lossless.pallas_parity", 0.0,
          "bit-identical" if same else "MISMATCH")


def transfer(smoke: bool = False):
    """Prefill->decode KV transfer over the Transport layer (DESIGN.md
    §8): measured `PackedCache` wire bytes per stage chain — via the same
    `Transport.bytes_moved` accessor `models/serve.py` ships with — vs
    moving raw f32 pages, pack+unpack roundtrip time, and simulated
    transfer time / sustainable migration rate on a 100 Gb/s link.

    Two load points: a cache mid-decode (60% written — zero chunks drop
    the unwritten tail) and a fully written one (the stage floor).
    """
    from repro.compression.kv import kv_quantizer_config, quantize_kv
    from repro.core.transport import TRANSPORT
    from repro.models.serve import QuantCache, pack_cache, unpack_cache

    link_gbps = 100.0                       # simulated disaggregation link
    link_bps = link_gbps * 1e9 / 8
    # [L, B, G, S, hd] serving-cache shape (reduced-model scale on CPU)
    l_, b, g_, s, hd = (2, 2, 2, 512, 64) if smoke else (4, 4, 4, 2048, 64)
    reps = 1 if smoke else 3
    r = datasets._rng("serve-cache")
    kv_cfg = kv_quantizer_config()

    for load, written in (("midstream", 0.6), ("full", 1.0)):
        x = r.standard_normal((l_, b, g_, s, hd)).astype(np.float32)
        x[:, :, :, int(s * written):, :] = 0.0       # unwritten tail pages
        qk = quantize_kv(jnp.asarray(x), kv_cfg)
        qv = quantize_kv(jnp.asarray(x[..., ::-1]), kv_cfg)
        hot = jnp.zeros((l_, b, 128, g_, hd), jnp.float32)
        cache = QuantCache(qk, qv, hot, hot)
        raw_pages = 2 * qk.bins.size * 4 + 2 * hot.size * hot.dtype.itemsize

        for stages in ("", "zero", "narrow", "shuffle|narrow",
                       "narrow|ent", "kvdelta|narrow|ent"):
            f_pack = jax.jit(lambda c, st=stages: pack_cache(c, stages=st))
            f_rt = jax.jit(
                lambda c, st=stages: unpack_cache(pack_cache(c, stages=st)))
            wire = f_pack(cache)
            t = _time(f_rt, cache, repeats=reps)
            moved = float(TRANSPORT.bytes_moved(wire, op="send_pages"))
            ms = moved / link_bps * 1e3
            label = stages.replace("|", "+") if stages else "packed"
            _emit(f"transfer.{load}.{label}", t * 1e6,
                  f"wire={moved/2**20:.2f}MiB vs_raw_f32="
                  f"{raw_pages/moved:.2f}x link{link_gbps:g}Gbps="
                  f"{ms:.2f}ms sustainable={link_bps/moved:.1f}migr/s "
                  f"roundtrip={t*1e6:.0f}us")

    # transfer is exact: the unpacked cache must be bit-identical — both
    # for a word-only chain and for the §9 kvdelta page-predictor chain
    for st in ("shuffle|narrow", "kvdelta|zero|narrow"):
        back = unpack_cache(pack_cache(cache, stages=st))
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(cache),
                                   jax.tree.leaves(back)))
        _emit(f"transfer.roundtrip.{st.replace('|', '+')}", 0.0,
              "bit-identical" if same else "MISMATCH")


TABLES = {
    "table3": table3, "table4": table4, "table56": table56,
    "table7": table7, "table8": table8, "table9": table9,
    "ckpt": ckpt, "kv": kv, "gradwire": gradwire, "packedwire": packedwire,
    "lossless": lossless, "transfer": transfer,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("names", nargs="*", default=[],
                    help=f"tables to run (default: all of {list(TABLES)})")
    ap.add_argument("--pipeline", default=None, metavar="SPEC",
                    help="bench this pipeline chain in the `lossless` "
                         "table: a DESIGN.md §7 spec string or a "
                         "configs.registry preset name")
    ap.add_argument("--smoke", action="store_true",
                    help="small datasets / single repeats for the "
                         "`lossless` table (CI)")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    use_compile_cache()
    names = args.names or list(TABLES)
    unknown = [n for n in names if n not in TABLES]
    if unknown:
        ap.error(f"unknown table(s) {unknown}; have {list(TABLES)}")
    pipeline = args.pipeline
    if pipeline is not None:
        from repro.configs.registry import get_pipeline
        if pipeline == "auto" or pipeline.startswith("auto:"):
            pass              # §11 selector spec — resolved by the bench
        else:
            try:
                pipeline = get_pipeline(pipeline)
            except KeyError as e:
                ap.error(str(e))
        if args.names and args.names != ["lossless"]:
            ap.error("--pipeline applies to the `lossless` table only; "
                     f"drop {[n for n in args.names if n != 'lossless']} "
                     "or run them separately")
        names = ["lossless"]
    print("name,us_per_call,derived")
    for n in names:
        if n == "lossless":
            TABLES[n](pipeline=pipeline, smoke=args.smoke)
        elif n == "transfer":
            TABLES[n](smoke=args.smoke)
        else:
            TABLES[n]()


if __name__ == "__main__":
    main()
