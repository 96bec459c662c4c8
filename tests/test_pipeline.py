"""Pipeline API (DESIGN.md §7): spec parse/print roundtrip, pipeline-vs-
legacy bit-identity on every chain the pre-pipeline surfaces could
express, fused-kernel vs jit-fallback dispatch parity, the shuffle stage,
and shard_map transparency of the unified CompressedShard.

Everything wire-shaped here is a bit-equality test: the pipeline replaced
the forked *_lc surfaces, so ANY discrepancy against them — one word, one
header code, one accounted byte — is a regression, not a quality delta."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import QuantizerConfig, codec
from repro.core.pipeline import (Encoded, PackStage, Pipeline,
                                 QuantStage, ShuffleStage, STAGES,
                                 parse_pipeline)

RNG = np.random.default_rng(71)


def _mix(n):
    x = (RNG.standard_normal(n) * 3e-3).astype(np.float32)
    x[RNG.random(n) < 0.6] = 0.0
    if n >= 8:
        x[:8] = [np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-42,
                 np.finfo(np.float32).max, 5e-4]
    return x


def _mixed_sign_rel(n):
    """|x| straddles 1 with both signs -> mixed-sign log-domain bins."""
    mag = np.exp(RNG.standard_normal(n) * 1.5)
    sgn = np.where(RNG.random(n) < 0.5, -1.0, 1.0)
    return (mag * sgn).astype(np.float32)


# ------------------------------------------------------- spec roundtrip ---

@pytest.mark.parametrize("spec", [
    "abs:0.001|pack:16",
    "rel:0.001|pack:8|zero|narrow",
    "noa:0.0001|pack:32|narrow",
    "abs:0.0001:cap=0.015625|pack:16|narrow",
    "rel:0.001|pack:32|shuffle:32|narrow",
    "abs:0.001:cap=0.25:dtype=float64|pack:16|zero",
    "abs:0.001|pack:8|zero|narrow|ent",
    "delta|abs:0.001|pack:16|narrow",
    "lorenzo|abs:0.001|pack:32|narrow|ent",
    "kvdelta|abs:0.001|pack:8|zero|narrow",
    "delta|kvdelta|abs:0.001|pack:16",
])
def test_spec_parse_print_roundtrip(spec):
    pipe = parse_pipeline(spec)
    assert parse_pipeline(pipe.spec()) == pipe
    # idempotent canonical form
    assert parse_pipeline(pipe.spec()).spec() == pipe.spec()


def test_bare_shuffle_inherits_pack_width():
    assert parse_pipeline("rel:0.001|pack:32|shuffle|narrow").stages[0] \
        == ShuffleStage(32)
    assert parse_pipeline("abs:0.001|pack:8|shuffle|zero").stages[0] \
        == ShuffleStage(8)


@pytest.mark.parametrize("bad", [
    "", "abs:0.001", "pack:8|abs:0.001", "abs:0.001|pack:12",
    "abs:0.001|pack:8|wavelet", "abs|pack:8", "abs:0.001:k=2|pack:8",
    "zero|abs:0.001|pack:8", "abs:0.001|pack:8|shuffle:9",
    "abs:0.001|pack:8|zero:5", "abs:0.001|pack:8|ent:5",
    "abs:0.001|pack:8|ent:k=2",
    "abs:0.001|delta|pack:8", "delta:3|abs:0.001|pack:8",
    "delta|lorenzo",
])
def test_spec_parse_rejects_malformed(bad):
    with pytest.raises((ValueError, KeyError)):
        parse_pipeline(bad)


def test_spec_roundtrip_property():
    pytest.importorskip("hypothesis")   # optional dev dep
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=80, deadline=None)
    @given(st.data())
    def run(data):
        mode = data.draw(st.sampled_from(["abs", "rel", "noa"]))
        eb = data.draw(st.floats(1e-30, 1e3, allow_nan=False,
                                 allow_infinity=False))
        cap = data.draw(st.sampled_from([0.125, 0.25, 1 / 64, 0.5]))
        bits = data.draw(st.sampled_from([8, 16, 32]))
        names = data.draw(st.lists(
            st.sampled_from(sorted(STAGES)), max_size=3))
        stages = tuple(STAGES[n](n, [], bits) for n in names)
        pipe = Pipeline(QuantStage(mode, float(eb), cap),
                        PackStage(bits), stages)
        assert parse_pipeline(pipe.spec()) == pipe

    run()


# ---------------------------------------- two-domain grammar fuzzer -------
#
# Random LEGAL chains from the full grammar {pred}|quant|pack|{word-stage}
# must (1) parse<->print roundtrip, (2) decode bit-transparent vs the
# stage-free quant|pack reference — every stage in BOTH domains is an
# exact inverse — and (3) hold the §1 bound.  Word stages are drawn as
# subsequences of the canonical order; every subset is legal (verified
# exhaustively by the deterministic twin's superset sweep).

PRED_NAMES = ["delta", "lorenzo", "kvdelta"]
WORD_ORDER = ["shuffle", "zero", "narrow", "ent"]


def _grammar_chain_is_transparent(preds, mode, eb, bits, words, x):
    """One fuzzer case, shared with the deterministic twin."""
    n = x.size
    base = f"{mode}:{eb!r}|pack:{bits}"
    spec = "".join(p + "|" for p in preds) + base \
        + "".join("|" + w for w in words)
    pipe = parse_pipeline(spec)
    assert parse_pipeline(pipe.spec()) == pipe
    assert parse_pipeline(pipe.spec()).spec() == pipe.spec()
    ref = parse_pipeline(base)
    xj = jnp.asarray(x)
    y0 = np.asarray(ref.decode(ref.encode(xj, kernels=False), n=n,
                               kernels=False))
    y = np.asarray(pipe.decode(pipe.encode(xj, kernels=False), n=n,
                               kernels=False))
    np.testing.assert_array_equal(y.view(np.uint32), y0.view(np.uint32),
                                  err_msg=spec)
    fin = np.isfinite(x)
    np.testing.assert_array_equal(x[~fin].view(np.uint32),
                                  y[~fin].view(np.uint32), err_msg=spec)
    if mode == "abs":
        assert np.abs(x[fin].astype(np.float64) - y[fin]).max() <= eb, spec
    else:
        m = fin & (x != 0)
        assert np.abs((x[m].astype(np.float64) - y[m])
                      / x[m].astype(np.float64)).max() <= eb, spec


def test_two_domain_grammar_fuzzer():
    pytest.importorskip("hypothesis")   # optional dev dep
    from hypothesis import given, settings, strategies as st

    n = 6000
    x = _mix(n)

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def run(data):
        preds = data.draw(st.lists(st.sampled_from(PRED_NAMES),
                                   max_size=2, unique=True))
        mode = data.draw(st.sampled_from(["abs", "rel"]))
        eb = data.draw(st.sampled_from([1e-3, 1e-2]))
        bits = data.draw(st.sampled_from([8, 16, 32]))
        words = [w for w in WORD_ORDER if data.draw(st.booleans())]
        _grammar_chain_is_transparent(preds, mode, eb, bits, words, x)

    run()


@pytest.mark.parametrize("preds,words", [
    ([], ["zero", "narrow"]),
    ([], ["shuffle", "zero", "narrow", "ent"]),
    (["delta"], []),
    (["delta"], ["narrow", "ent"]),
    (["lorenzo"], ["shuffle", "narrow"]),
    (["kvdelta"], ["zero", "narrow", "ent"]),
    (["delta", "kvdelta"], ["zero"]),
    (["kvdelta", "lorenzo"], ["shuffle", "zero", "narrow", "ent"]),
])
def test_two_domain_grammar_deterministic_sweep(preds, words):
    """Deterministic twin of the fuzzer (hypothesis is an optional dev
    dep): representative chains over both domains, every check shared."""
    x = _mix(6000)
    for mode, bits in [("abs", 8), ("rel", 16)]:
        _grammar_chain_is_transparent(preds, mode, 1e-3, bits, words, x)

LEGACY_CHAINS = [(m, bb, st) for m in ("abs", "rel") for bb in (8, 16)
                 for st in (None, "zero", "narrow")]


@pytest.mark.parametrize("mode,bin_bits,stage", LEGACY_CHAINS)
def test_pipeline_matches_legacy_chain(mode, bin_bits, stage):
    """Every chain expressible before the pipeline API must produce the
    bit-identical wire arrays, accounting, and decode."""
    n = 70_000
    x = jnp.asarray(_mix(n))
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    spec = f"{mode}:0.01|pack:{bin_bits}" + (f"|{stage}" if stage else "")
    pipe = parse_pipeline(spec)
    assert pipe.qcfg() == cfg
    enc = pipe.encode(x, kernels=False)

    ep = codec.encode_packed(x, cfg)
    if stage is None:
        legacy, hdr = ep, None
        np.testing.assert_array_equal(np.asarray(enc.payload),
                                      np.asarray(ep.words))
        assert pipe.wire_bits(enc, n) == ep.wire_bits()
    else:
        lc = codec.encode_lossless(ep, stage)
        np.testing.assert_array_equal(np.asarray(enc.payload),
                                      np.asarray(lc.payload))
        np.testing.assert_array_equal(np.asarray(enc.headers[0]),
                                      np.asarray(lc.header_words))
        assert int(enc.payload_len) == int(lc.payload_len)
        assert float(pipe.wire_bits(enc, n)) == float(lc.wire_bits())
    for field in ("out_idx", "out_payload", "n_outliers", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(enc, field)),
                                      np.asarray(getattr(ep, field)),
                                      err_msg=field)
    if mode == "rel":
        np.testing.assert_array_equal(np.asarray(enc.sign_words),
                                      np.asarray(ep.sign_words))

    y_pipe = np.asarray(pipe.decode(enc, n=n, kernels=False))
    y_legacy = np.asarray(codec.decode_packed(ep, cfg, n=n))
    np.testing.assert_array_equal(y_pipe.view(np.uint32),
                                  y_legacy.view(np.uint32))


@pytest.mark.parametrize("spec", [
    "abs:0.01|pack:16", "abs:0.01|pack:8|narrow", "rel:0.01|pack:16|zero",
    "noa:0.001|pack:16|narrow",
])
def test_kernel_dispatch_matches_reference(spec):
    """The fused Pallas dispatch (interpret mode) must be bit-identical,
    field for field, to the jit reference fallback."""
    x = jnp.asarray(_mix(60_000))
    pipe = parse_pipeline(spec)
    a = pipe.encode(x, kernels=False)
    b = pipe.encode(x, kernels=True, interpret=True)
    for fa, fb, name in zip(a, b, Encoded._fields):
        if name == "headers":
            for ha, hb in zip(fa, fb):
                np.testing.assert_array_equal(np.asarray(ha),
                                              np.asarray(hb))
        elif fa is None:
            assert fb is None, name
        else:
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                          err_msg=name)
    ya = pipe.decode(a, n=x.size, kernels=False)
    yb = pipe.decode(b, n=x.size, kernels=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ya).view(np.uint32),
                                  np.asarray(yb).view(np.uint32))


def test_unknown_chain_falls_back_to_reference():
    pipe = parse_pipeline("rel:0.01|pack:16|shuffle|narrow")
    assert pipe.kernel_dispatch() is None
    x = jnp.asarray(_mix(30_000))
    a = pipe.encode(x, kernels=False)
    b = pipe.encode(x, kernels=True, interpret=True)   # falls back
    np.testing.assert_array_equal(np.asarray(a.payload),
                                  np.asarray(b.payload))


@pytest.mark.parametrize("spec", [
    "abs:0.01|pack:8|zero|narrow",           # stacked chunk stages
    "rel:0.01|pack:16|shuffle|narrow",
    "rel:0.01|pack:32|shuffle|zero|narrow",
    "noa:0.0001|pack:32|shuffle:32",
    "abs:0.01|pack:8|narrow|ent",            # entropy stage on top
    "rel:0.01|pack:16|shuffle|narrow|ent",
    "noa:0.0001|pack:32|ent",                # ent straight after pack
])
def test_novel_chain_roundtrip_holds_guarantee(spec):
    """Chains the forked surfaces could NOT express: decode must still be
    the exact inverse and the §1 bound must hold (specials bit-exact)."""
    n = 50_000
    x = _mix(n)
    pipe = parse_pipeline(spec)
    y = np.asarray(pipe.roundtrip(jnp.asarray(x), kernels=False))
    fin = np.isfinite(x)
    np.testing.assert_array_equal(x[~fin].view(np.uint32),
                                  y[~fin].view(np.uint32))
    eb = pipe.quant.eb
    if pipe.quant.mode == "abs":
        assert np.abs(x[fin].astype(np.float64) - y[fin]).max() <= eb
    elif pipe.quant.mode == "rel":
        m = fin & (x != 0)
        rel = np.abs((x[m].astype(np.float64) - y[m])
                     / x[m].astype(np.float64))
        assert rel.max() <= eb


# ----------------------------------------------------------- ent stage ----

def test_every_registry_preset_extended_with_ent_is_bit_transparent():
    """Appending `|ent` to ANY registry preset must leave the decoded
    stream bit-identical (the stage is an exact inverse) while the
    encode/decode dispatch still works end to end."""
    from repro.configs.registry import PIPELINES, get_pipeline

    n = 20_000
    x = jnp.asarray(_mix(n))
    for name in sorted(PIPELINES):
        spec = get_pipeline(name)
        if spec.endswith("|ent"):
            continue                      # already entropy-terminated
        base = parse_pipeline(spec)
        ext = parse_pipeline(spec + "|ent")
        eb = 1e-2 if base.quant.eb == 1.0 else None   # placeholder bounds
        y0 = np.asarray(base.decode(base.encode(x, eb=eb, kernels=False),
                                    n=n, kernels=False))
        y1 = np.asarray(ext.decode(ext.encode(x, eb=eb, kernels=False),
                                   n=n, kernels=False))
        np.testing.assert_array_equal(y0.view(np.uint32),
                                      y1.view(np.uint32), err_msg=name)


def test_ent_chain_falls_back_to_reference_dispatch():
    pipe = parse_pipeline("abs:0.01|pack:16|narrow|ent")
    assert pipe.kernel_dispatch() is None
    x = jnp.asarray(_mix(30_000))
    a = pipe.encode(x, kernels=False)
    b = pipe.encode(x, kernels=True, interpret=True)   # falls back
    np.testing.assert_array_equal(np.asarray(a.payload),
                                  np.asarray(b.payload))
    np.testing.assert_array_equal(np.asarray(a.headers[1]),
                                  np.asarray(b.headers[1]))


def test_ent_wire_accounting_counts_transmitted_prefix_only():
    """wire_bits must count payload_len words + header content + the
    length field — never the capacity padding — and stage_report's last
    row must mirror it exactly."""
    n = 1 << 17
    x = np.zeros(n, np.float32)
    x[: n // 16] = RNG.standard_normal(n // 16).astype(np.float32) * 3e-3
    pipe = parse_pipeline("abs:0.001|pack:16|narrow|ent")
    enc = pipe.encode(jnp.asarray(x), kernels=False)
    sizes = pipe.stage_sizes(n)
    hdr = sum(st.header_content_bits(sz)
              for st, sz in zip(pipe.stages, sizes[:-1]))
    base = 64 + enc.out_idx.shape[0] * 64      # header + outlier table
    want = 32 * int(enc.payload_len) + hdr + 32 + base
    assert float(pipe.wire_bits(enc, n)) == want
    assert float(pipe.wire_bits(enc)) == want      # capacity-idempotent
    rows = pipe.stage_report(jnp.asarray(x))
    assert float(rows[-1][1]) == want


# ------------------------------------------------------- shuffle stage ----

@pytest.mark.parametrize("width", [8, 16, 32])
@pytest.mark.parametrize("n", [1, 37, 128, codec.LC_CHUNK + 1, 5000])
def test_shuffle_words_roundtrip(width, n):
    w = jnp.asarray(RNG.integers(0, 1 << 32, n, dtype=np.uint32))
    s = codec.shuffle_words(w, width)
    assert s.shape[0] == codec.shuffle_word_count(n)
    back = codec.unshuffle_words(s, n, width)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w))


def test_shuffle_preserves_zero_streams():
    w = jnp.zeros(4 * codec.LC_CHUNK, jnp.uint32)
    assert not np.asarray(codec.shuffle_words(w, 16)).any()


def test_shuffle_makes_narrow_fire_on_mixed_sign_bins():
    """The stage's reason to exist: on mixed-sign REL bins, narrow alone
    sits at its ~1x floor (sign extension sets the high bits of every
    word); shuffle's zigzag fold unlocks the width codes."""
    x = jnp.asarray(_mixed_sign_rel(1 << 18))
    plain = parse_pipeline("rel:0.001|pack:32|narrow")
    shuf = parse_pipeline("rel:0.001|pack:32|shuffle|narrow")
    b_plain = float(plain.wire_bits(plain.encode(x, kernels=False), x.size))
    b_shuf = float(shuf.wire_bits(shuf.encode(x, kernels=False), x.size))
    assert b_shuf < 0.75 * b_plain, (b_plain, b_shuf)
    # and the decoded streams are still bit-identical to each other
    ya = plain.decode(plain.encode(x, kernels=False), n=x.size,
                      kernels=False)
    yb = shuf.decode(shuf.encode(x, kernels=False), n=x.size, kernels=False)
    np.testing.assert_array_equal(np.asarray(ya).view(np.uint32),
                                  np.asarray(yb).view(np.uint32))


def test_stage_report_decomposes_the_ratio():
    x = jnp.asarray(_mix(1 << 17))
    pipe = parse_pipeline("abs:0.01|pack:16|shuffle|narrow")
    rows = pipe.stage_report(x)
    labels = [r[0] for r in rows]
    assert labels == ["raw", "abs:0.01|pack:16", "shuffle:16", "narrow"]
    enc = pipe.encode(x, kernels=False)
    assert float(rows[-1][1]) == float(pipe.wire_bits(enc, x.size))


def test_stage_report_matches_wire_bits_on_every_prefix():
    """Each stage_report row must equal the prefix pipeline's wire_bits —
    the accessor compression_ratio(per_stage=True) reports from must not
    drift from the one the collectives are measured with, including
    static (non-length-transmitting) prefixes."""
    x = jnp.asarray(_mix(1 << 16))
    pipe = parse_pipeline("abs:0.01|pack:16|shuffle|narrow")
    rows = pipe.stage_report(x)
    for i in range(len(pipe.stages) + 1):
        prefix = Pipeline(pipe.quant, pipe.pack, pipe.stages[:i])
        enc = prefix.encode(x, kernels=False)
        assert float(rows[1 + i][1]) == float(prefix.wire_bits(enc, x.size))


def test_compression_ratio_per_stage():
    from repro.core import compression_ratio
    x = _mix(1 << 16)
    cfg = QuantizerConfig(mode="abs", error_bound=1e-2, bin_bits=16)
    dev = compression_ratio(x, cfg, wire="device",
                            pipeline="abs:0.01|pack:16|narrow")
    rows = compression_ratio(x, cfg, wire="device",
                             pipeline="abs:0.01|pack:16|narrow",
                             per_stage=True)
    assert rows[-1][0] == "narrow"
    assert rows[-1][1] == pytest.approx(dev)


# --------------------------------------------------- unified grad shard ---

def test_compressed_shard_unifies_the_fork():
    """One CompressedShard for every chain: legacy field views, measured
    accounting equal to the pre-pipeline formulas."""
    from repro.compression.grads import (GradCompressionConfig,
                                         compress_shard, wire_bytes)
    n = 1 << 16
    g = jnp.asarray(_mix(n))
    plain = GradCompressionConfig(bin_bits=16)
    shard, _ = compress_shard(g, plain)
    assert shard.nbytes() == wire_bytes(n, plain)
    np.testing.assert_array_equal(np.asarray(shard.words),
                                  np.asarray(shard.enc.payload))

    staged = GradCompressionConfig(
        bin_bits=16, pipeline="abs:1.0:cap=0.015625|pack:16|narrow")
    shard_lc, _ = compress_shard(g, staged)
    # legacy CompressedShardLC.nbytes formula, reproduced exactly
    n_chunks = shard_lc.payload.size // codec.LC_CHUNK
    want = (4.0 * float(shard_lc.payload_len)
            + codec.lc_header_content_words(n_chunks) * 4 + 4
            + shard_lc.out_idx.size * 4 + shard_lc.out_payload.size * 4
            + 4 + 4)
    assert float(shard_lc.nbytes()) == want
    assert float(shard_lc.nbytes()) <= shard_lc.capacity_nbytes()
    # .words view decodes the stage chain back to the §4 plane
    np.testing.assert_array_equal(np.asarray(shard_lc.words),
                                  np.asarray(shard.words))


@pytest.mark.parametrize("spec", ["abs:1.0:cap=0.015625|pack:8|narrow",
                                  "abs:1.0:cap=0.015625|pack:8|shuffle|zero",
                                  "abs:1.0:cap=0.015625|pack:8|narrow|ent",
                                  "delta|abs:1.0:cap=0.015625|pack:8|narrow"])
def test_compressed_mean_pipeline_transparent_under_shard_map(spec):
    """compressed_mean through ANY pipeline must produce the same mean
    and residual bits as the stage-free wire (stages are exact), under
    the same shard_map collective — the unified CompressedShard is
    shard_map-transparent."""
    from jax.sharding import PartitionSpec as P

    from repro.compression.grads import GradCompressionConfig, compressed_mean

    n = 8192
    g = np.zeros(n, np.float32)
    g[:256] = 0.01
    g[-1] = 50.0                                   # exact-outlier path too
    mesh = jax.make_mesh((1,), ("pod",))

    def run(cfg):
        mapped = jax.shard_map(lambda x: compressed_mean(x, cfg, "pod"),
                               mesh=mesh, in_specs=P(), out_specs=(P(), P()),
                               axis_names={"pod"}, check_vma=False)
        return jax.jit(mapped)(jnp.asarray(g))

    base = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=8,
                                 outlier_cap_frac=1 / 64)
    mean0, resid0 = run(base)
    mean1, resid1 = run(base._replace(pipeline=spec))
    np.testing.assert_array_equal(np.asarray(mean0).view(np.uint32),
                                  np.asarray(mean1).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(resid0).view(np.uint32),
                                  np.asarray(resid1).view(np.uint32))
    assert np.asarray(mean1)[-1] == g[-1]          # outlier still exact


# ------------------------------------------------------ unified PackedKV --

def test_pack_kv_stage_chains_roundtrip():
    from repro.compression.kv import (kv_quantizer_config, pack_kv,
                                      quantize_kv, unpack_kv)
    x = RNG.standard_normal((2, 3, 256, 64)).astype(np.float32)
    x[:, :, 160:, :] = 0.0
    q = quantize_kv(jnp.asarray(x), kv_quantizer_config())
    pk = pack_kv(q)
    for stages in ("zero", "narrow", "shuffle|narrow", "narrow|ent",
                   "kvdelta|zero|narrow", "kvdelta|narrow|ent"):
        p = pack_kv(q, stages=stages)
        back = unpack_kv(p)
        for a, b in zip(q, back):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(p.wire_nbytes()) < pk.nbytes(), stages


# -------------------------------------------------------- config guards ---

def test_grad_config_rejects_non_abs_pipelines():
    """compressed_mean's gather/dequant is ABS-only (per-tensor rms
    bound, no sign plane) — a REL/NOA spec must fail loudly, not corrupt
    the mean silently."""
    from repro.compression.grads import GradCompressionConfig
    for spec in ("rel:0.001|pack:8|narrow", "noa:0.0001|pack:8"):
        with pytest.raises(ValueError, match="abs"):
            GradCompressionConfig(pipeline=spec).pipe()


def test_header_words_view_semantics():
    """The legacy header_words view is the chunk coder's width-code
    plane: stage-free shards have none (AttributeError, not IndexError),
    and a headerless shuffle stage ahead of the chunk stage is skipped."""
    from repro.compression.grads import GradCompressionConfig, compress_shard
    g = jnp.asarray(_mix(1 << 14))
    plain, _ = compress_shard(g, GradCompressionConfig(bin_bits=16))
    with pytest.raises(AttributeError, match="header"):
        plain.header_words
    cfg = GradCompressionConfig(
        bin_bits=16, pipeline="abs:1.0:cap=0.015625|pack:16|shuffle|narrow")
    shard, _ = compress_shard(g, cfg)
    assert shard.header_words.size > 0
    np.testing.assert_array_equal(np.asarray(shard.header_words),
                                  np.asarray(shard.enc.headers[1]))


def test_grad_config_default_fields_build_stage_free_chain():
    """The eb_rel/bin_bits/outlier_cap_frac fields (no spec) must build
    the same stage-free pipeline the equivalent spec does."""
    from repro.compression.grads import GradCompressionConfig
    pipe = GradCompressionConfig(bin_bits=8, outlier_cap_frac=1 / 64).pipe()
    assert pipe.stages == ()
    spec_pipe = GradCompressionConfig(
        pipeline="abs:1.0:cap=0.015625|pack:8").pipe()
    assert pipe == spec_pipe
