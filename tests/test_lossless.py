"""Device-side lossless stage (DESIGN.md §6): bit-exact roundtrips over
arbitrary word streams, Pallas-interpret vs jit-reference parity, and
honest wire accounting through the gradient and KV wires.

Everything here is a bit-equality test: the lossless stage sits between
quantize+pack and the collective, so ANY discrepancy — one word, one chunk
code — is a guarantee violation, not a quality regression."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compression.grads import (GradCompressionConfig, compress_shard,
                                     wire_bytes)
from repro.compression.kv import (kv_quantizer_config, pack_kv,
                                  quantize_kv, unpack_kv)
from repro.core import (ENT_MAX_LEN, LC_CHUNK, LC_STAGES, QuantizerConfig,
                        decode_lossless, decode_packed, decode_words_ent,
                        decode_words_lc, encode_lossless, encode_packed,
                        encode_words_ent, encode_words_lc, ent_header_words,
                        lc_header_words, packed_word_count)
from repro.core.codec import ent_code_lengths, ent_header_content_words, lc_chunk_count
from repro.kernels import lossless as klc

RNG = np.random.default_rng(61)

# odd lengths, sub-chunk, exact-chunk, and multi-chunk word streams
WORD_SIZES = [1, 37, LC_CHUNK - 1, LC_CHUNK, LC_CHUNK + 1, 4 * LC_CHUNK,
              10 * LC_CHUNK + 13]


def _stream(n, pattern):
    if pattern == "allzero":
        return np.zeros(n, np.uint32)
    if pattern == "dense":
        return RNG.integers(0, 1 << 32, n, dtype=np.uint32)
    if pattern == "bytes":
        return RNG.integers(0, 1 << 8, n, dtype=np.uint32)
    if pattern == "halves":
        return RNG.integers(0, 1 << 16, n, dtype=np.uint32)
    if pattern == "outlier_chunk":
        # one hot chunk in an otherwise all-zero stream
        w = np.zeros(n, np.uint32)
        lo = (n // 2 // LC_CHUNK) * LC_CHUNK
        w[lo:lo + min(LC_CHUNK, n - lo)] = RNG.integers(
            0, 1 << 32, min(LC_CHUNK, n - lo), dtype=np.uint32)
        return w
    if pattern == "mixed":
        # per-chunk width classes drawn independently
        n_chunks = -(-n // LC_CHUNK)
        hi = np.array([0, 1 << 8, 1 << 16, 1 << 32],
                      np.uint64)[RNG.integers(0, 4, n_chunks)]
        w = (RNG.integers(0, 1 << 32, n_chunks * LC_CHUNK, dtype=np.uint64)
             % np.maximum(np.repeat(hi, LC_CHUNK), 1))
        return w[:n].astype(np.uint32)
    raise AssertionError(pattern)


PATTERNS = ("allzero", "dense", "bytes", "halves", "outlier_chunk", "mixed")


# ------------------------------------------------- word-stream roundtrip --

@pytest.mark.parametrize("stage", LC_STAGES)
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("n", WORD_SIZES)
def test_words_lc_roundtrip_bitexact(n, pattern, stage):
    w = _stream(n, pattern)
    hw, payload, plen = encode_words_lc(jnp.asarray(w), stage)
    assert hw.shape[0] == lc_header_words(n)
    assert int(plen) <= payload.shape[0]
    back = np.asarray(decode_words_lc(hw, payload, n))
    np.testing.assert_array_equal(back, w)


def test_words_lc_zero_stream_is_headers_only():
    w = jnp.zeros(8 * LC_CHUNK, jnp.uint32)
    for stage in LC_STAGES:
        _, _, plen = encode_words_lc(w, stage)
        assert int(plen) == 0


def test_words_lc_narrow_beats_zero_on_byte_stream():
    w = jnp.asarray(_stream(8 * LC_CHUNK, "bytes"))
    _, _, plen_zero = encode_words_lc(w, "zero")
    _, _, plen_narrow = encode_words_lc(w, "narrow")
    assert int(plen_narrow) == int(plen_zero) // 4 == 2 * LC_CHUNK


def test_words_lc_dense_stream_costs_only_headers():
    n = 4 * LC_CHUNK + 7
    w = jnp.asarray(_stream(n, "dense"))
    hw, payload, plen = encode_words_lc(w, "narrow")
    # no chunk compresses -> payload is the (chunk-padded) stream verbatim
    assert int(plen) == 5 * LC_CHUNK
    np.testing.assert_array_equal(np.asarray(payload[:n]), np.asarray(w))


@pytest.mark.parametrize("stage", LC_STAGES)
def test_words_lc_roundtrip_property(stage):
    pytest.importorskip("hypothesis")   # optional dev dep
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def run(data):
        n = data.draw(st.integers(1, 3 * LC_CHUNK), label="n")
        seed = data.draw(st.integers(0, 2 ** 32 - 1), label="seed")
        shift = data.draw(st.sampled_from([0, 8, 16, 24, 31]), label="shift")
        r = np.random.default_rng(seed)
        w = (r.integers(0, 1 << 32, n, dtype=np.uint32)
             >> np.uint32(shift)).astype(np.uint32)
        w[r.random(n) < 0.5] = 0           # mix in zero runs
        hw, payload, plen = encode_words_lc(jnp.asarray(w), stage)
        back = np.asarray(decode_words_lc(hw, payload, n))
        np.testing.assert_array_equal(back, w)

    run()


# ------------------------------------------ row-granular LC payload mover --

def _coded_stream(codes, n=None):
    """Words whose chunks take exactly `codes` under the narrow stage."""
    codes = np.asarray(codes)
    top = np.array([0, (1 << 8) - 1, (1 << 16) - 1, (1 << 32) - 1],
                   np.uint64)[codes]
    w = RNG.integers(0, 1 << 32, (codes.size, LC_CHUNK), dtype=np.uint64)
    w = w % (top[:, None] + 1)
    w[:, 0] = top                          # each chunk's widest word
    w = w.reshape(-1).astype(np.uint32)
    return w if n is None else w[:n]


ROW_CASES = {
    # name: (words, payload rows handed to the gather; None = all)
    "allzero": lambda: (_coded_stream([0] * 4), None),
    "allfull": lambda: (_coded_stream([3] * 4), None),
    "alternating": lambda: (_coded_stream([1, 2, 3] * 3), None),
    "last_only": lambda: (_coded_stream([0] * 5 + [3]), None),
    "ragged": lambda: (_coded_stream([2, 0, 3, 1, 3], 4 * LC_CHUNK + 77),
                       None),
    # a header that claims 16 rows over a payload cut short to 6
    "overlong": lambda: (_coded_stream([3] * 4), 6),
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_lc_row_mover_matches_word_mover(case):
    """lc_compact_payload / lc_gather_chunks move whole 128-word rows; on
    the same codes they give the word-granular compact_chunks /
    gather_chunks' payload, payload_len and chunks bit for bit, and
    lc_expand_payload the widened words.  Where the header claims more
    rows than the payload holds, the gathers read the payload's last row
    again and zero past each chunk's length: deterministic, and nothing
    outside the payload is read."""
    from repro.core import codec as C

    words, keep_rows = ROW_CASES[case]()
    n = words.size
    n_chunks = lc_chunk_count(n)
    chunks = jnp.pad(jnp.asarray(words),
                     (0, n_chunks * LC_CHUNK - n)).reshape(n_chunks, LC_CHUNK)
    codes = C.lc_chunk_codes(chunks, "narrow")
    sel = C.lc_narrow_chunks(chunks, codes)
    lens = C.lc_chunk_lens(codes)

    payload, plen = C.lc_compact_payload(sel, codes)
    ref_payload, ref_plen = C.compact_chunks(sel, lens)
    np.testing.assert_array_equal(np.asarray(payload),
                                  np.asarray(ref_payload))
    assert int(plen) == int(ref_plen) == int(np.sum(lens))

    given = payload if keep_rows is None else payload[:keep_rows * 128]
    got = np.asarray(C.lc_gather_chunks(given, codes))
    np.testing.assert_array_equal(
        got, np.asarray(jax.jit(C.lc_gather_chunks)(given, codes)))
    # the row model: chunk c's row j is payload row first_c + j, clamped
    # to the last row the payload holds, and zero from row lens_c/128 on
    prows = np.asarray(given).reshape(-1, 128)
    nrows = np.asarray(lens) // 128
    first = np.cumsum(nrows) - nrows
    model = np.zeros((n_chunks, 4, 128), np.uint32)
    for c in range(n_chunks):
        for j in range(nrows[c]):
            model[c, j] = prows[min(first[c] + j, prows.shape[0] - 1)]
    np.testing.assert_array_equal(got, model.reshape(n_chunks, LC_CHUNK))
    # the decode gathers and widens in one pass: a width-w row holds 32/w
    # word rows (pack_words' layout), row b at shift w * (b % (32/w))
    codes_np = np.asarray(codes)
    wide = np.zeros_like(model)
    for c in range(n_chunks):
        if codes_np[c]:
            w = (0, 8, 16, 32)[codes_np[c]]
            for b in range(4):
                row = model[c, b // (32 // w)].astype(np.uint64)
                wide[c, b] = (row >> (w * (b % (32 // w)))) & ((1 << w) - 1)
    np.testing.assert_array_equal(
        np.asarray(C.lc_expand_payload(given, codes)),
        wide.reshape(n_chunks, LC_CHUNK))
    if keep_rows is None:
        np.testing.assert_array_equal(
            got, np.asarray(C.gather_chunks(payload, lens)))
        hw = C.pack_words(codes, 2)
        np.testing.assert_array_equal(
            np.asarray(decode_words_lc(hw, payload, n)), words)


def _hlo_windows(fn, *args):
    """(op, words per window) of every gather and scatter in fn's HLO."""
    txt = jax.jit(fn).lower(*args).compiler_ir("hlo").as_hlo_text()
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]",
                             txt, re.M))
    dims = lambda spec: [int(d) for d in spec.split(",") if d]
    out = []
    for line in txt.splitlines():
        m = re.search(r" (gather|scatter)\(([^)]*)\)", line)
        if not m:
            continue
        if m.group(1) == "gather":
            win = dims(re.search(r"slice_sizes=\{([\d,]*)\}",
                                 line).group(1))
        else:
            upd = dims(shapes[m.group(2).split(",")[-1].strip()])
            win = [upd[i] for i in dims(re.search(
                r"update_window_dims=\{([\d,]*)\}", line).group(1))]
        out.append((m.group(1), int(np.prod(win))))
    return out


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("stage", LC_STAGES)
def test_lc_payload_moves_whole_rows(stage, impl):
    """No gather or scatter of the zero/narrow coder, encode or decode,
    moves less than one 128-word row per index (a per-word mover costs
    the chip ~26 ns a word)."""
    from repro.core import codec as C

    n = 10 * LC_CHUNK + 13
    w = jax.ShapeDtypeStruct((n,), jnp.uint32)
    # the reader flags a word-granular mover
    lens = jax.ShapeDtypeStruct((lc_chunk_count(n),), jnp.int32)
    cap = jax.ShapeDtypeStruct((lc_chunk_count(n) * LC_CHUNK,), jnp.uint32)
    assert ("gather", 1) in _hlo_windows(C.gather_chunks, cap, lens)
    if impl == "reference":
        enc = lambda x: encode_words_lc(x, stage)
        dec = lambda h, p: decode_words_lc(h, p, n)
    else:
        enc = lambda x: klc.encode_words_lc(x, stage, interpret=True)
        dec = lambda h, p: klc.decode_words_lc(h, p, n, interpret=True)
    hw, payload, _ = jax.eval_shape(enc, w)
    moves = _hlo_windows(enc, w) + _hlo_windows(dec, hw, payload)
    assert {op for op, _ in moves} == {"gather", "scatter"}, moves
    assert min(win for _, win in moves) >= 128, moves


# ----------------------------------------------- ent word-stream stage ----


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("n", [1, 37, LC_CHUNK, LC_CHUNK + 1,
                               4 * LC_CHUNK + 13])
def test_words_ent_roundtrip_bitexact(n, pattern):
    w = _stream(n, pattern)
    hw, payload, plen = encode_words_ent(jnp.asarray(w))
    assert hw.shape[0] == ent_header_words(n)
    assert int(plen) <= payload.shape[0]
    back = np.asarray(decode_words_ent(hw, payload, n))
    np.testing.assert_array_equal(back, w)


def test_words_ent_zero_stream_is_headers_only():
    w = jnp.zeros(8 * LC_CHUNK, jnp.uint32)
    _, _, plen = encode_words_ent(w)
    assert int(plen) == 0


def test_words_ent_chunks_never_cost_more_than_raw():
    """No chunk may exceed its raw 512 payload words: uniform bytes code
    at exactly 8 bits/byte (the cap boundary), and when a skewed global
    codebook would push a chunk's rare bytes past the cap the mode-2
    escape stores it verbatim instead."""
    # uniform random bytes: 8-bit codes -> full chunks cost exactly raw
    n = 4 * LC_CHUNK
    w = jnp.asarray(_stream(n, "dense"))
    hw, payload, plen = encode_words_ent(w)
    assert int(plen) == 4 * LC_CHUNK
    np.testing.assert_array_equal(np.asarray(decode_words_ent(hw, payload,
                                                              n)),
                                  np.asarray(w))
    # skewed codebook + one dense chunk: its rare bytes would code past
    # 32 * LC_CHUNK bits -> verbatim escape, still exactly raw cost
    w2 = np.ones(5 * LC_CHUNK, np.uint32)
    w2[:LC_CHUNK] = _stream(LC_CHUNK, "dense")
    hw2, payload2, plen2 = encode_words_ent(jnp.asarray(w2))
    np.testing.assert_array_equal(np.asarray(payload2[:LC_CHUNK]),
                                  w2[:LC_CHUNK])     # stored untouched
    assert int(plen2) <= 5 * LC_CHUNK
    np.testing.assert_array_equal(
        np.asarray(decode_words_ent(hw2, payload2, w2.size)), w2)


def test_words_ent_beats_narrow_on_skewed_bytes():
    """The stage's reason to exist: narrow stops at whole-byte widths —
    a skewed byte distribution across all four byte planes leaves its
    width codes nothing to do, while ent codes it near entropy.  The
    transmitted wire (payload + header content + length) must come in
    far under narrow's."""
    n = 16 * LC_CHUNK
    b = RNG.choice([0, 1, 2], (n, 4), p=[.7, .2, .1]).astype(np.uint32)
    w = jnp.asarray(b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)
                    | (b[:, 3] << 24))
    nc = lc_chunk_count(n)
    _, _, plen_n = encode_words_lc(w, "narrow")
    bits_n = 32 * int(plen_n) + 32 * -(-nc // 16) + 32
    _, _, plen_e = encode_words_ent(w)
    bits_e = 32 * int(plen_e) + 32 * ent_header_content_words(nc) + 32
    assert bits_e < 0.25 * bits_n, (bits_e, bits_n)


def test_words_ent_roundtrip_property():
    pytest.importorskip("hypothesis")   # optional dev dep
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.data())
    def run(data):
        n = data.draw(st.integers(1, 3 * LC_CHUNK), label="n")
        seed = data.draw(st.integers(0, 2 ** 32 - 1), label="seed")
        shift = data.draw(st.sampled_from([0, 8, 16, 24, 31]), label="shift")
        r = np.random.default_rng(seed)
        w = (r.integers(0, 1 << 32, n, dtype=np.uint32)
             >> np.uint32(shift)).astype(np.uint32)
        w[r.random(n) < 0.5] = 0           # mix in zero runs
        hw, payload, plen = encode_words_ent(jnp.asarray(w))
        back = np.asarray(decode_words_ent(hw, payload, n))
        np.testing.assert_array_equal(back, w)

    run()


def test_ent_code_lengths_kraft_feasible():
    """Every histogram — uniform, skewed, degenerate — must yield
    lengths in [1, ENT_MAX_LEN] with Kraft sum <= 1 (a canonical prefix
    code exists), including the empty histogram of an all-zero stream."""
    cases = [np.zeros(256, np.int64),
             np.ones(256, np.int64),
             np.eye(1, 256, 0, dtype=np.int64).ravel() * 1000,
             RNG.integers(0, 1000, 256).astype(np.int64),
             np.array([2 ** 20] + [1] * 255, np.int64)]
    for hist in cases:
        lens = np.asarray(ent_code_lengths(jnp.asarray(hist, jnp.int32)))
        assert lens.min() >= 1 and lens.max() <= ENT_MAX_LEN, lens
        assert np.sum(2.0 ** -lens) <= 1.0 + 1e-12, np.sum(2.0 ** -lens)


# ------------------------------------------------- EncodedLC end-to-end ---

def _mix(n):
    x = (RNG.standard_normal(n) * 3e-3).astype(np.float32)
    x[RNG.random(n) < 0.6] = 0.0
    if n >= 8:
        x[:8] = [np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-42,
                 np.finfo(np.float32).max, 5e-4]
    return x


@pytest.mark.parametrize("stage", LC_STAGES)
@pytest.mark.parametrize("bin_bits", [8, 16])
@pytest.mark.parametrize("mode", ["abs", "rel", "noa"])
def test_lossless_stage_is_transparent(mode, bin_bits, stage):
    """decode(decode_lossless(encode_lossless(encode_packed(x)))) must be
    bit-identical to decoding the packed form directly — the stage cannot
    touch the guarantee."""
    n = 70_000
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    x = jnp.asarray(_mix(n))
    enc = encode_packed(x, cfg)
    n_words = packed_word_count(n, cfg.bin_bits)
    dec = decode_lossless(encode_lossless(enc, stage), n_words)
    np.testing.assert_array_equal(np.asarray(dec.words),
                                  np.asarray(enc.words))
    y_ref = np.asarray(decode_packed(enc, cfg, n=n))
    y_lc = np.asarray(decode_packed(dec, cfg, n=n))
    np.testing.assert_array_equal(y_ref.view(np.uint32),
                                  y_lc.view(np.uint32))


def test_lossless_wire_bits_sparse_beats_packed():
    n = 1 << 20
    cfg = QuantizerConfig(mode="abs", error_bound=1e-4, bin_bits=16,
                          outlier_cap_frac=1 / 64)
    x = np.zeros(n, np.float32)
    x[: n // 64] = RNG.standard_normal(n // 64) * 3e-3   # 1/64 live prefix
    enc = encode_packed(jnp.asarray(x), cfg)
    lc = encode_lossless(enc, "zero")
    assert float(lc.wire_bits()) < 0.1 * enc.wire_bits()


def test_lossless_wire_bits_dense_floor_is_header_plane():
    """On incompressible words the stage may only cost the header plane
    and padding — never more."""
    n = 1 << 18
    cfg = QuantizerConfig(mode="abs", error_bound=1e-4, bin_bits=16)
    x = jnp.asarray((RNG.standard_normal(n) * 3e-3).astype(np.float32))
    enc = encode_packed(x, cfg)
    lc = encode_lossless(enc, "narrow")
    n_words = packed_word_count(n, 16)
    n_chunks = -(-n_words // LC_CHUNK)
    overhead = (32 * -(-n_chunks // 16)                # header content
                + 32 * (LC_CHUNK - 1)                  # chunk padding
                + 32)                                  # transmitted length
    assert float(lc.wire_bits()) <= enc.wire_bits() + overhead


# ------------------------------------------------- Pallas kernel parity ---

@pytest.mark.parametrize("stage", LC_STAGES)
@pytest.mark.parametrize("pattern", ["allzero", "mixed", "dense"])
@pytest.mark.parametrize("n", [1, LC_CHUNK + 1, 10 * LC_CHUNK + 13])
def test_kernel_words_lc_matches_reference(n, pattern, stage):
    w = jnp.asarray(_stream(n, pattern))
    ref = encode_words_lc(w, stage)
    ker = klc.encode_words_lc(w, stage, interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    back = np.asarray(klc.decode_words_lc(ref[0], ref[1], n,
                                          interpret=True))
    np.testing.assert_array_equal(back, np.asarray(w))


@pytest.mark.parametrize("stage", LC_STAGES)
@pytest.mark.parametrize("bin_bits", [8, 16, 32])
@pytest.mark.parametrize("mode", ["abs", "rel"])
def test_fused_kernel_matches_reference(mode, bin_bits, stage):
    """encode_packed_lc (ONE fused quantize+pack+narrow HBM pass) must be
    bit-identical to the staged jit reference, field for field."""
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    x = jnp.asarray(_mix(100_000))
    ref = encode_lossless(encode_packed(x, cfg), stage)
    ker = klc.encode_packed_lc(x, cfg, stage=stage, interpret=True)
    for a, b, name in zip(ref, ker, ref._fields):
        if a is None:
            assert b is None, name
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_fused_kernel_tiling_invariance():
    cfg = QuantizerConfig(mode="abs", error_bound=1e-3, bin_bits=16)
    x = jnp.asarray(_mix(200_000))
    ref = encode_lossless(encode_packed(x, cfg), "narrow")
    for rows in (64, 256, 512):
        ker = klc.encode_packed_lc(x, cfg, stage="narrow", rows=rows,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(ref.payload),
                                      np.asarray(ker.payload))
        np.testing.assert_array_equal(np.asarray(ref.header_words),
                                      np.asarray(ker.header_words))


# ------------------------------------------------------- gradient wire ----

def test_grad_shard_lc_roundtrip_and_accounting():
    n = (1 << 18) + 349
    cfg = GradCompressionConfig(
        bin_bits=16, pipeline="abs:1.0:cap=0.015625|pack:16|zero")
    g = np.zeros(n, np.float32)
    g[: n // 32] = RNG.standard_normal(n // 32) * 3e-3
    shard_lc, _ = compress_shard(jnp.asarray(g), cfg)
    # independent stage-free reference: the coded wire must decode back
    # to exactly the §4 plane a stage-free pipeline ships
    shard, _ = compress_shard(
        jnp.asarray(g),
        cfg._replace(pipeline="abs:1.0:cap=0.015625|pack:16"))
    n_words = packed_word_count(n, 16)
    back = decode_words_lc(shard_lc.header_words, shard_lc.payload, n_words)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(shard.words))
    # measured transmitted bytes: far under the packed wire for sparse g,
    # and bounded by capacity
    assert float(shard_lc.nbytes()) < 0.25 * wire_bytes(n, cfg)
    assert float(shard_lc.nbytes()) <= shard_lc.capacity_nbytes()


@pytest.mark.parametrize("stage", ["zero", "narrow"])
def test_compressed_mean_lossless_stage_transparent(stage):
    """compressed_mean with the lossless stage enabled must produce the
    SAME mean and residual bits as without it (the stage is exact), under
    the same shard_map collective."""
    from jax.sharding import PartitionSpec as P

    from repro.compression.grads import compressed_mean

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

    base_cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=8,
                                     outlier_cap_frac=1 / 64)
    mean0, resid0 = run(base_cfg)
    mean1, resid1 = run(base_cfg._replace(
        pipeline=f"abs:1.0:cap=0.015625|pack:8|{stage}"))
    np.testing.assert_array_equal(np.asarray(mean0).view(np.uint32),
                                  np.asarray(mean1).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(resid0).view(np.uint32),
                                  np.asarray(resid1).view(np.uint32))
    assert np.asarray(mean1)[-1] == g[-1]          # outlier still exact


# ------------------------------------------------------------- KV wire ----

@pytest.mark.parametrize("stage", LC_STAGES)
def test_kv_lc_roundtrip_bitexact(stage):
    cfg = kv_quantizer_config()
    x = RNG.standard_normal((2, 3, 256, 64)).astype(np.float32)
    x[:, :, 160:, :] = 0.0                         # unwritten tail pages
    q = quantize_kv(jnp.asarray(x), cfg)
    lc = pack_kv(q, stages=stage)
    back = unpack_kv(lc)
    for a, b in zip(q, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # zero tail pages shrink the measured wire below the packed one
    pk = pack_kv(q)
    assert float(lc.wire_nbytes()) < pk.nbytes()
