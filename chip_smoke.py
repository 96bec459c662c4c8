#!/usr/bin/env python3
"""Chip smoke: drive the codec and the quantized-KV decode engine once on
a TPU, through the entry points a user calls, and check every result
against what it is compared with.

    python3 chip_smoke.py [--seed N]               one chip
    python3 chip_smoke.py --four-chip [--seed N]   four chips

One chip runs two phases:

  codec   one 512^3 f32 field (the size of one SDRBench NYX field,
          512 MiB), generated on the device from --seed with a few
          hundred NaN, +-Inf and denormal values in it, encoded and
          decoded through three registry chains.  The default dispatch
          (fused Pallas kernels where the chain has one) must be
          bit-identical to the jit reference plane by plane, every value
          must come back within the plain bound or bit-exact, no wire may
          overflow, and a 2^22-value slice must match the host numpy
          oracle bin for bin.
  engine  models.engine.DecodeEngine serving deepseek-67b at its
          published widths (depth cut to 4 whole layers): 4 slots, 4
          requests, 16 new tokens each.  Every request is answered, and
          every prefill and decode logit agrees with transformer.forward
          run in f32 over the same tokens.

--four-chip runs only the two cross-chip paths and their references:
the compressed gradient reduce over a ('pod',) mesh of 4 chips on one
full-width deepseek-67b layer's gradients (ring against gather, both
against lax.pmean), and streaming KV page migration from chip 0 to
chip 1 (bit-identical to the source cache).

This is a smoke run, not a benchmark: the times it prints include
compilation.  Any failed check raises.  The last line of stdout is the
JSON result, printed only when every check passed; without a TPU the
script exits nonzero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FIELD_SHAPE = (512, 512, 512)
ORACLE_N = 1 << 22               # host-oracle slice (values)
N_SPECIAL = 384                  # far under every chain's outlier cap
CODEC_CHAINS = ("sci-abs-narrow", "sci-rel-narrow", "sci-lorenzo-ent")
# NaN, +Inf, -Inf, the smallest and largest-magnitude denormals, and a
# NaN with a payload: what the paper's FTZ lesson is about
SPECIAL_BITS = (0x7FC00000, 0x7F800000, 0xFF800000, 0x00000001,
                0x807FFFFF, 0x7FC00123)
DENORMAL_BITS = (0x00000001, 0x807FFFFF)

SERVE_ARCH = "deepseek-67b"
SERVE_LAYERS = 4                 # depth cut; every width is published
N_SLOTS, SEQ, NEW_TOKENS = 4, 512, 16
PROMPT_LENS = (100, 300)
GRAD_BUCKET = 1 << 22            # 16 MiB of f32 gradient per reduce call
# Logit tolerance: the rms of (engine - reference) over every compared
# logit, relative to the rms of the reference.  The reference is
# transformer.forward in f32 on the same bf16 weights with unquantized
# K/V.  The engine attends over KV pages quantized to eb = 2^-6 of each
# page's max |value| (compression.kv.kv_quantizer_config): a uniform
# error of rms ~eb/sqrt(3), ~2.6% of the K/V rms for pages whose max is
# ~4.5 rms; its bf16 compute adds ~2^-8.  Logits inherit errors of that
# relative size, and 2^-4 leaves about twice that.  A K/V element off by
# more than its page's bound (an unrestored outlier, a page decoded at the
# wrong step) pushes the error past it.
LOGIT_TOL_REL = 2.0 ** -4


def log(*a):
    print(*a, flush=True)


def _bits(a):
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(a, jnp.uint32)


# ---------------------------------------------------------------- codec ---

def make_field(seed: int, shape=FIELD_SHAPE):
    """A smooth log-normal density field with specials, built on the
    device.  Returns (field f32[shape], special flat indices, their
    intended bit patterns)."""
    import jax
    import jax.numpy as jnp

    n = int(np.prod(shape))
    half = N_SPECIAL // 2

    @jax.jit
    def build(key):
        ka, kp, kn, ko = jax.random.split(key, 4)
        amp = jax.random.normal(ka, (3, 4))
        ph = jax.random.uniform(kp, (3, 4)) * 2 * jnp.pi
        modes = jnp.arange(1, 5, dtype=jnp.float32)
        log_rho = 0.1 * jax.random.normal(kn, shape)
        for d, s in enumerate(shape):
            t = jnp.linspace(0, 2 * jnp.pi, s, dtype=jnp.float32)
            wave = jnp.sum(amp[d][:, None] * jnp.sin(
                modes[:, None] * t[None, :] + ph[d][:, None]), axis=0)
            log_rho = log_rho + 0.5 * wave.reshape(
                [s if i == d else 1 for i in range(len(shape))])
        x = jnp.exp(log_rho).reshape(-1)
        # half the specials inside the oracle slice, half beyond it
        off = jax.random.randint(ko, (2,), 0, 1 << 10)
        idx = jnp.concatenate([
            jnp.arange(half) * (ORACLE_N // half) + off[0],
            ORACLE_N + jnp.arange(half) * ((n - ORACLE_N) // half) + off[1]])
        pat = jnp.asarray(SPECIAL_BITS, jnp.uint32)[
            jnp.arange(N_SPECIAL) % len(SPECIAL_BITS)]
        x = x.at[idx].set(jax.lax.bitcast_convert_type(pat, jnp.float32))
        return x.reshape(shape), idx, pat

    return build(jax.random.PRNGKey(seed))


def _planes_equal(a, b) -> dict:
    """Plane-by-plane bit identity of two wires -> {plane path: equal}."""
    import jax
    import jax.numpy as jnp

    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    if [p for p, _ in la] != [p for p, _ in lb]:
        raise AssertionError("fused and reference wires differ in structure")
    return {jax.tree_util.keystr(p): bool(jnp.array_equal(x, y))
            for (p, x), (_, y) in zip(la, lb)}


def codec_chain(name: str, x, spec_idx, spec_bits) -> dict:
    import jax.numpy as jnp

    from repro.configs.registry import get_pipeline
    from repro.core import oracle_np
    from repro.core.pipeline import parse_pipeline

    pipe = parse_pipeline(get_pipeline(name))
    qc = pipe.qcfg()
    n = x.size
    fused = pipe.kernel_dispatch()

    t0 = time.perf_counter()
    enc = pipe.encode(x)                  # kernels=None: fused on TPU
    y = pipe.decode(enc, shape=x.shape)
    y.block_until_ready()
    first_s = time.perf_counter() - t0
    enc_ref = pipe.encode(x, kernels=False)
    y_ref = pipe.decode(enc_ref, shape=x.shape, kernels=False)

    planes = _planes_equal(enc, enc_ref)
    enc_same = all(planes.values())
    dec_same = bool(jnp.array_equal(_bits(y), _bits(y_ref)))
    del enc_ref, y_ref

    eb = jnp.float32(qc.error_bound)
    err = jnp.abs(x - y)
    within = err <= (eb * jnp.abs(x) if qc.mode == "rel" else eb)
    ok = within | (_bits(x) == _bits(y))
    violations = int(jnp.sum(~ok))
    xs, ys = x.reshape(-1)[spec_idx], y.reshape(-1)[spec_idx]
    denorm = jnp.isin(spec_bits, jnp.asarray(DENORMAL_BITS, jnp.uint32))
    spec_stored = bool(jnp.all(_bits(xs) == spec_bits))
    spec_exact = _bits(xs) == _bits(ys)
    nonfinite_exact = bool(jnp.all(spec_exact | denorm))
    denorm_exact = int(jnp.sum(spec_exact & denorm))
    overflow = bool(enc.overflow)
    bits = int(pipe.wire_bits(enc, n))

    # the chip's quantizer against the host oracle on a slice
    sl = x.reshape(-1)[:ORACLE_N]
    _, qt = pipe.encode(sl, return_quantized=True)
    xs_np = np.asarray(sl)
    if qc.mode == "rel":
        o_bins, o_out, _, _ = oracle_np.quantize_rel(xs_np, qc)
    else:
        o_bins, o_out, _ = oracle_np.quantize_abs(xs_np, qc)
    bins_match = int(np.sum(np.asarray(qt.bins) != o_bins))
    out_match = int(np.sum(np.asarray(qt.outlier) != o_out))

    res = dict(chain=name, dispatch=fused or "jit reference",
               ratio=32.0 * n / bits, first_call_s=first_s,
               enc_bit_identical=enc_same, dec_bit_identical=dec_same,
               violations=violations, overflow=overflow,
               n_outliers=int(enc.n_outliers),
               specials_stored=spec_stored,
               nonfinite_bit_exact=nonfinite_exact,
               denormals_bit_exact=f"{denorm_exact}/{int(jnp.sum(denorm))}",
               oracle_bin_mismatches=bins_match,
               oracle_outlier_mismatches=out_match,
               max_err=float(jnp.max(jnp.where(jnp.isfinite(err), err, 0))))
    log("codec", json.dumps(res))
    bad = [p for p, same in planes.items() if not same]
    if bad:
        raise AssertionError(f"{name}: fused encode differs from the jit "
                             f"reference in planes {bad}")
    checks = {"decode bit-identical to the reference": dec_same,
              "zero bound violations": violations == 0,
              "overflow false": not overflow,
              "specials stored as seeded": spec_stored,
              "NaN/Inf bit-exact": nonfinite_exact,
              "oracle bins match": bins_match == 0,
              "oracle outliers match": out_match == 0}
    failed = [k for k, v in checks.items() if not v]
    if failed:
        raise AssertionError(f"{name}: {failed}")
    return res


def codec_phase(seed: int, shape=FIELD_SHAPE) -> list:
    x, idx, pat = make_field(seed, shape)
    x.block_until_ready()
    log(f"codec field {shape} f32 ({x.size * 4 / 2**20:.0f} MiB), "
        f"{N_SPECIAL} specials, seed {seed}")
    return [codec_chain(c, x, idx, pat) for c in CODEC_CHAINS]


# --------------------------------------------------------------- engine ---

def serve_config(n_layers=SERVE_LAYERS):
    from repro.configs import registry
    return dataclasses.replace(registry.get(SERVE_ARCH), n_layers=n_layers)


def engine_phase(seed: int, cfg=None, *, n_slots=N_SLOTS, seq=SEQ,
                 new_tokens=NEW_TOKENS, prompt_lens=PROMPT_LENS) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_kv_chain
    from repro.models import build
    from repro.models import engine as E
    from repro.models.transformer import forward

    cfg = serve_config() if cfg is None else cfg
    t0 = time.perf_counter()
    params = build(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"engine {cfg.name} d_model {cfg.d_model} heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim {cfg.head_dim} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab} layers {cfg.n_layers}: {n_bytes / 1e9:.2f} GB weights,"
        f" init {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_slots)
    prompts = [rng.integers(0, cfg.vocab, size=int(m)).astype(np.int32)
               for m in lens]
    eng = E.DecodeEngine(cfg, params, n_slots=n_slots, seq=seq,
                         stages=get_kv_chain("kv-page"))
    t0 = time.perf_counter()
    slots, toks, logs = {}, {}, {}
    for rid, p in enumerate(prompts):
        slot = eng.allocate()
        pre = eng.prefill(p)
        if not eng.insert(slot, pre, request=rid):
            raise AssertionError(f"request {rid}: insert refused")
        slots[rid] = slot
        toks[rid] = [int(pre.next_token.reshape(()))]
        logs[rid] = [np.asarray(pre.logits[0])]
    for _ in range(new_tokens - 1):
        logits, t = eng.generate_step()
        logits, t = np.asarray(logits), np.asarray(t)
        for rid, slot in slots.items():
            toks[rid].append(int(t[slot]))
            logs[rid].append(logits[slot])
    serve_s = time.perf_counter() - t0

    # DESIGN §10: the vmapped slot step against the batch-1 step on the
    # same state — recorded, not asserted (it does not hold on every
    # backend; see DESIGN §10)
    live = jnp.ones((n_slots,), bool)
    v_logits = eng._vstep(eng.params, eng._cache, eng._tok, eng._pos,
                          live)[0]
    vmap_diff = []
    for slot in range(n_slots):
        one = jax.tree.map(lambda a: a[slot], eng._cache)
        l1, _ = eng._step1(eng.params, one, eng._tok[slot], eng._pos[slot])
        vmap_diff.append(float(jnp.max(jnp.abs(v_logits[slot] - l1[0]))))
    kv_outliers = int(sum(jnp.sum(c.out_idx >= 0)
                          for c in (eng._cache.k, eng._cache.v)))

    # reference: transformer.forward in f32 over prompt + generated tokens
    # (teacher-forced on the engine's own tokens), padded to one length —
    # causal, so the padding never reaches the compared positions
    length = max(len(p) for p in prompts) + new_tokens - 1
    batch = np.zeros((n_slots, length), np.int32)
    for rid, p in enumerate(prompts):
        full = np.concatenate([p, np.asarray(toks[rid][:-1], np.int32)])
        batch[rid, :len(full)] = full

    @jax.jit
    def reference(params, tokens):
        p32 = {**params, "emb": params["emb"].astype(jnp.float32)}
        return forward(cfg, p32, tokens, remat=False)[0]

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference(params, jnp.asarray(batch)))
    worst = []
    for rid, p in enumerate(prompts):
        m = len(p)
        r = ref[rid, m - 1:m - 1 + new_tokens].astype(np.float64)
        d = np.stack(logs[rid]) - r
        rel = float(np.sqrt(np.mean(d ** 2) / np.mean(r ** 2)))
        worst.append(rel)
        log(f"engine request {rid}: prompt {m}, {len(toks[rid])} tokens; "
            f"logits vs f32 forward: rms err {rel:.6g} x rms(ref) (tol "
            f"{LOGIT_TOL_REL:.6g}), max |err| {np.max(np.abs(d)):.6g}, "
            f"rms(ref) {np.sqrt(np.mean(r ** 2)):.6g}")
    res = dict(requests=len(prompts),
               answered=sum(len(t) == new_tokens for t in toks.values()),
               serve_s_incl_compile=serve_s,
               worst_rms_logit_err_rel=max(worst), tol_rel=LOGIT_TOL_REL,
               kv_outliers=kv_outliers,
               vmap_vs_batch1_max_abs_diff=vmap_diff)
    log("engine", json.dumps(res))
    if res["answered"] != len(prompts):
        raise AssertionError(f"answered {res['answered']}/{len(prompts)}")
    if not max(worst) <= LOGIT_TOL_REL:
        raise AssertionError(f"logits off the f32 reference by rms "
                             f"{max(worst):.6g} x rms(ref) > {LOGIT_TOL_REL}")
    return res


# ------------------------------------------------------------ four chips ---

def reduce_check(mesh, gcfg, pinned: bool):
    """Jitted per-pod check of compressed_mean_tree over one flat
    gradient bucket: [|auto reduce - pmean|, bound] and, with `pinned`,
    [auto bit-identical to Transport(reduce='gather'), |gather - pmean|,
    the §8 ring taken]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compression.grads import compress_shard, compressed_mean_tree
    from repro.core.transport import TRANSPORT, Transport

    def body(g):
        g = g[0]
        zero = (jnp.zeros_like(g),)
        (auto,), _ = compressed_mean_tree((g,), zero, gcfg, "pod")
        ref = jax.lax.pmean(g, "pod")
        # per-pod bound eb = eb_rel * rms(g) (compress_shard's); the mean
        # of p decodes is off pmean by at most the mean bound, plus the
        # f32 rounding of the two p-term sums (a few ulps of |g|)
        eb = jnp.asarray(gcfg.eb_rel, jnp.float32) * jnp.sqrt(
            jnp.mean(g * g))
        mag = jax.lax.pmax(jnp.max(jnp.abs(g)), "pod")
        p = jax.lax.axis_size("pod")
        bound = (jax.lax.pmean(eb, "pod")
                 + 4 * p * jnp.finfo(jnp.float32).eps * mag)
        row = [jnp.max(jnp.abs(auto - ref)), bound]
        if pinned:
            (gat,), _ = compressed_mean_tree(
                (g,), zero, gcfg, "pod", transport=Transport(reduce="gather"))
            shard, _ = compress_shard(g, gcfg)
            row += [jnp.all(_bits(auto) == _bits(gat)).astype(jnp.float32),
                    jnp.max(jnp.abs(gat - ref)),
                    TRANSPORT._ring_compat(shard.enc, "pod").astype(
                        jnp.float32)]
        return jnp.stack(row)[None]

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                 out_specs=P("pod"), axis_names={"pod"},
                                 check_vma=False))


def gradient_reduce_phase(seed: int, devices, cfg=None) -> dict:
    """compressed_mean_tree over a ('pod',) mesh of the given devices on
    one full-width layer's gradient tree, every leaf flattened into
    GRAD_BUCKET-value buckets as a bucketed data-parallel all-reduce
    sends them (one bucket per call, so every bucket shares a compile).
    Two chains: a ring-eligible one (ABS, stage-free; identical shards,
    so the §8 ring fires) against the same chain pinned to the gather
    reduce, and grad-wire-16-narrow (per-pod shards, gather).  Both
    against lax.pmean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compression.grads import GradCompressionConfig
    from repro.configs.registry import get_pipeline
    from repro.models.transformer import param_specs

    cfg = serve_config(1) if cfg is None else cfg
    p = len(devices)
    mesh = jax.make_mesh((p,), ("pod",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    sizes = {k: int(np.prod(s.shape[1:]))
             for k, s in sorted(param_specs(cfg)["layers"].items())}
    chains = {"ring-eligible": ("abs:1.0:cap=0.015625|pack:16", True),
              "grad-wire-16-narrow": (get_pipeline("grad-wire-16-narrow"),
                                      False)}
    out = {}
    for label, (spec, same) in chains.items():
        check = reduce_check(mesh, GradCompressionConfig(pipeline=spec),
                             pinned=same)
        t0 = time.perf_counter()
        rows = {}
        for i, (name, size) in enumerate(sizes.items()):
            bucket = GRAD_BUCKET if size % GRAD_BUCKET == 0 else size

            def gen(key, n=bucket):
                if same:       # every pod holds the same gradient
                    return jnp.broadcast_to(jax.random.normal(key, (n,)),
                                            (p, n))
                return jax.random.normal(key, (p, n))

            gen = jax.jit(gen, out_shardings=NamedSharding(mesh, P("pod")))
            stats = []
            for j in range(size // bucket):
                g = gen(jax.random.fold_in(jax.random.PRNGKey(seed),
                                           i * 4096 + j))
                if {s.device for s in g.addressable_shards} != set(devices):
                    raise AssertionError("gradient shards are not one per "
                                         "chip")
                stats.append(np.asarray(check(g)))    # one row per pod
            s = np.stack(stats)                       # [buckets, pods, k]
            err = s[..., 0].max(1)
            if same:
                err = np.maximum(err, s[..., 3].max(1))
            bound = s[..., 1].min(1)
            r = dict(values=size, buckets=len(stats),
                     worst_err_over_bound=float((err / bound).max()))
            if same:
                r.update(ring_bit_identical_to_gather=bool(s[..., 2].min()),
                         ring_buckets=int(s[..., 4].min(1).sum()))
            rows[name] = r
        res = dict(chain=spec, identical_shards=same,
                   seconds_incl_compile=time.perf_counter() - t0,
                   leaves=rows)
        log("grad", label, json.dumps(res))
        for name, r in rows.items():
            if not r["worst_err_over_bound"] <= 1.0:
                raise AssertionError(f"{label}/{name}: off pmean by "
                                     f"{r['worst_err_over_bound']} x bound")
            if same and not r["ring_bit_identical_to_gather"]:
                raise AssertionError(f"{label}/{name}: ring reduce differs "
                                     f"from Transport(reduce='gather')")
        # the ring needs outlier-free wires (§8): a bucket whose bound
        # sits just above a power of two can carry rounding outliers and
        # take the gather path, so the ring must fire, not always
        if same and not sum(r["ring_buckets"] for r in rows.values()):
            raise AssertionError(f"{label}: the ring never fired")
        out[label] = res
    return out


def migration_phase(seed: int, devices, cfg=None, *, seq=SEQ,
                    prompt_len=200) -> dict:
    """engine.stream_prefill from devices[0] to devices[1]: the cache
    assembled on the destination must be bit-identical to the source's
    sequential prefill."""
    import jax
    import jax.numpy as jnp

    from repro.compression import kv as KVC
    from repro.models import build
    from repro.models import engine as E
    from repro.models import serve as S

    cfg = serve_config() if cfg is None else cfg
    params = jax.device_put(build(cfg).init(jax.random.PRNGKey(seed)),
                            devices[0])
    prompt = np.random.default_rng(seed).integers(
        0, cfg.vocab, size=prompt_len).astype(np.int32)
    mesh = jax.make_mesh((2,), ("wire",), devices=devices[:2],
                         axis_types=(jax.sharding.AxisType.Auto,))
    t0 = time.perf_counter()
    sp = E.stream_prefill(cfg, params, prompt, seq=seq, mesh=mesh,
                          axis="wire", src=0, dst=1)
    jax.block_until_ready(sp.cache)
    secs = time.perf_counter() - t0

    kv_cfg = KVC.kv_quantizer_config()
    step = jax.jit(lambda p, c, t, i: S.serve_step(cfg, p, c, t, i, None,
                                                   kv_cfg))
    cache = jax.device_put(S.make_quant_cache(cfg, 1, seq), devices[0])
    for i, t in enumerate(prompt):
        logits, cache = step(params, cache, jnp.asarray(t).reshape(1, 1),
                             jnp.int32(i))
    where = {d for a in jax.tree.leaves(sp.cache) for d in a.devices()}
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(sp.cache),
                               jax.tree.leaves(cache)))
    same_logits = bool(np.array_equal(np.asarray(sp.logits),
                                      np.asarray(logits)))
    res = dict(prompt=prompt_len, pages_streamed=sp.stats["pages_streamed"],
               wire_bytes=sp.stats["wire_bytes"], seconds=secs,
               cache_on=sorted(str(d) for d in where),
               cache_bit_identical=same, logits_bit_identical=same_logits)
    log("migration", json.dumps(res))
    if where != {devices[1]}:
        raise AssertionError(f"assembled cache lives on {where}, "
                             f"not {devices[1]}")
    if not (same and same_logits):
        raise AssertionError("migrated cache differs from the source")
    return res


# ----------------------------------------------------------------- main ---

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every field, weight and prompt")
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the gradient reduce and page migration "
                         "on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    from repro.launch.cache import use_compile_cache

    log(f"chip smoke (not a benchmark; times include compilation): "
        f"{len(devices)} x {devices[0].device_kind}, jax {jax.__version__},"
        f" compile cache {use_compile_cache()}")
    if args.four_chip:
        if len(devices) != 4:
            raise SystemExit(f"chip_smoke: --four-chip needs 4 chips, "
                             f"JAX finds {len(devices)}")
        gradient_reduce_phase(args.seed, devices)
        migration_phase(args.seed, devices)
    else:
        codec_phase(args.seed)
        engine_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
