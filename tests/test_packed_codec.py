"""Packed wire codec: bit-exact roundtrips, kernel/reference parity, and
measured wire sizes.

The PACKED layout is the format the collectives actually move, so every
test here is a bit-equality test: pack/unpack must be lossless over the
full bin range, decode_packed must agree with decode_compact elementwise
(including outlier restoration of NaN payloads / inf / -0.0), and the
fused Pallas pipeline (interpret mode) must reproduce the jit reference
word-for-word."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.compression.grads import (GradCompressionConfig, compress_shard,
                                     wire_bytes)
from repro.compression.kv import (kv_quantizer_config, kv_wire_bytes, pack_kv,
                                  quantize_kv, unpack_kv)
from repro.core import (QuantizerConfig, decode_compact, decode_packed,
                        encode_compact, encode_packed, pack_flags, pack_words,
                        packed_word_count, unpack_flags, unpack_words)
from repro.kernels import pack as kpack

RNG = np.random.default_rng(23)

# non-multiples of the 128-lane tile and of values-per-word, plus exact
# tile multiples and a single element
SIZES = [1, 12, 511, 4096, 32768, 65537]


def _mix(n):
    x = (RNG.standard_normal(n) * 10).astype(np.float32)
    if n >= 8:
        x[:8] = [np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-42,
                 np.finfo(np.float32).max, 5e-4]
    return x


# ------------------------------------------------------- pack primitives --

@pytest.mark.parametrize("bin_bits", [8, 16, 32])
@pytest.mark.parametrize("n", SIZES)
def test_pack_unpack_words_lossless(bin_bits, n):
    mx = (1 << (bin_bits - 1)) - 1
    bins = RNG.integers(-mx + 1, mx, size=n).astype(np.int32)
    words = pack_words(jnp.asarray(bins), bin_bits)
    assert words.dtype == jnp.uint32
    assert words.shape[0] == packed_word_count(n, bin_bits)
    back = np.asarray(unpack_words(words, n, bin_bits))
    np.testing.assert_array_equal(back, bins)


@pytest.mark.parametrize("n", SIZES)
def test_pack_unpack_flags_lossless(n):
    flags = RNG.integers(0, 2, size=n).astype(bool)
    words = pack_flags(jnp.asarray(flags))
    assert words.shape[0] == packed_word_count(n, 1)
    np.testing.assert_array_equal(np.asarray(unpack_flags(words, n)), flags)


# ------------------------------------------------ codec-level roundtrips --

@pytest.mark.parametrize("bin_bits", [8, 16])
@pytest.mark.parametrize("mode", ["abs", "rel", "noa"])
@pytest.mark.parametrize("n", SIZES)
def test_packed_matches_compact_bitexact(bin_bits, mode, n):
    """Acceptance: unpack(pack(x)) == decode_compact(encode_compact(x))
    elementwise at the bit level, outlier restoration included."""
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    x = jnp.asarray(_mix(n))
    via_compact = decode_compact(encode_compact(x, cfg), cfg)
    via_packed = decode_packed(encode_packed(x, cfg), cfg, n=n)
    np.testing.assert_array_equal(np.asarray(via_compact).view(np.uint32),
                                  np.asarray(via_packed).view(np.uint32))


def test_packed_all_outlier_tensor():
    """Every value an outlier (NaN/inf mix): bins are all zero on the wire
    and the table alone reconstructs the tensor bit-for-bit."""
    n = 300
    x = np.where(RNG.integers(0, 2, size=n).astype(bool),
                 np.float32(np.nan), np.float32(np.inf)).astype(np.float32)
    x[::3] = np.uint32(0x7FC00001).view(np.float32)   # NaN with payload
    cfg = QuantizerConfig(mode="abs", error_bound=1e-3, bin_bits=8,
                          outlier_cap_frac=1.0)
    enc = encode_packed(jnp.asarray(x), cfg)
    assert int(enc.n_outliers) == n
    assert not bool(enc.overflow)
    assert int(jnp.sum(enc.words)) == 0               # nothing but zeros
    y = np.asarray(decode_packed(enc, cfg, n=n))
    np.testing.assert_array_equal(x.view(np.uint32), y.view(np.uint32))


def test_packed_overflow_flag():
    cfg = QuantizerConfig(mode="abs", error_bound=1e-3, bin_bits=8,
                          outlier_cap_frac=1 / 256)
    x = jnp.asarray(np.full(1024, np.inf, np.float32))
    enc = encode_packed(x, cfg)
    assert bool(enc.overflow)


def test_packed_wire_bits_smaller_than_compact():
    n = 1 << 16
    cfg = QuantizerConfig(mode="abs", error_bound=1e-2, bin_bits=8,
                          outlier_cap_frac=1 / 64)
    x = jnp.asarray((RNG.standard_normal(n) * 0.1).astype(np.float32))
    c = encode_compact(x, cfg)
    p = encode_packed(x, cfg)
    # compact's wire_bits already assumes host narrowing; packed must not
    # exceed it by more than tile padding, and both are ~4x under f32
    assert p.wire_bits() <= c.wire_bits(cfg) + 32 * 128
    assert p.wire_bits() < n * 32 / 3


# ------------------------------------------------- fused kernel parity ----

@pytest.mark.parametrize("bin_bits", [8, 16])
@pytest.mark.parametrize("mode", ["abs", "rel"])
@pytest.mark.parametrize("n", SIZES)
def test_kernel_encode_matches_reference(bin_bits, mode, n):
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    x = jnp.asarray(_mix(n))
    ref = encode_packed(x, cfg)
    ker = kpack.encode_packed(x, cfg, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref.words), np.asarray(ker.words))
    np.testing.assert_array_equal(np.asarray(ref.out_idx),
                                  np.asarray(ker.out_idx))
    np.testing.assert_array_equal(np.asarray(ref.out_payload),
                                  np.asarray(ker.out_payload))
    assert int(ref.n_outliers) == int(ker.n_outliers)
    if mode == "rel":
        np.testing.assert_array_equal(np.asarray(ref.sign_words),
                                      np.asarray(ker.sign_words))


@pytest.mark.parametrize("bin_bits", [8, 16])
@pytest.mark.parametrize("mode", ["abs", "rel"])
@pytest.mark.parametrize("n", [511, 4096, 65537])
def test_kernel_decode_matches_reference(bin_bits, mode, n):
    cfg = QuantizerConfig(mode=mode, error_bound=1e-2, bin_bits=bin_bits)
    x = jnp.asarray(_mix(n))
    enc = encode_packed(x, cfg)
    ref = decode_packed(enc, cfg, n=n)
    ker = kpack.decode_packed(enc, cfg, n=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref).view(np.uint32),
                                  np.asarray(ker).view(np.uint32))


def test_kernel_traced_eb_and_tiling_invariance():
    cfg = QuantizerConfig(mode="abs", error_bound=1.0, bin_bits=8)
    x = jnp.asarray(_mix(100_000))
    eb = jnp.float32(3.7e-3)
    ref = encode_packed(x, cfg, eb=eb)
    base = None
    for rows in (32, 256, 512):
        ker = kpack.encode_packed(x, cfg, eb=eb, rows=rows, interpret=True)
        np.testing.assert_array_equal(np.asarray(ref.words),
                                      np.asarray(ker.words))
        if base is None:
            base = np.asarray(ker.words)
        else:
            np.testing.assert_array_equal(base, np.asarray(ker.words))


# ----------------------------------------------------- wire accounting ----

@pytest.mark.parametrize("n", [1000, 1 << 16, (1 << 20) + 17])
def test_grad_shard_wire_matches_wire_bytes(n):
    """Acceptance: what compressed_mean all-gathers is packed uint32 words
    and the measured size equals wire_bytes exactly."""
    cfg = GradCompressionConfig()
    g = jnp.asarray((RNG.standard_normal(n) * 0.01).astype(np.float32))
    shard, _ = compress_shard(g, cfg)
    assert shard.words.dtype == jnp.uint32
    assert shard.out_payload.dtype == jnp.uint32
    assert shard.nbytes() == wire_bytes(n, cfg)
    # packed words alone are 4x under f32; full wire under the cap's bound
    assert shard.words.size * 4 <= n + 4 * 128 * 4
    assert wire_bytes(n, cfg) < n * 4 / 3


def test_grad_shard_roundtrip_bound():
    """Decoding the shard's own wire arrays honors the per-tensor bound."""
    from repro.core import codec as C
    from repro.core.quantizer import dequantize_abs
    n = 8192
    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, outlier_cap_frac=1 / 4)
    g = np.asarray((RNG.standard_normal(n) * 0.01).astype(np.float32))
    shard, q = compress_shard(jnp.asarray(g), cfg)
    bins = C.unpack_words(shard.words, n, cfg.bin_bits)
    recon = dequantize_abs(bins, cfg.qcfg(), eb=shard.eb, dtype=jnp.float32)
    vals = jnp.asarray(shard.out_payload.astype(jnp.int32)).view(jnp.float32)
    recon = np.asarray(recon.at[shard.out_idx].set(vals, mode="drop"))
    eb = float(shard.eb)
    out_mask = np.asarray(q.outlier)
    assert np.all(np.abs(g[~out_mask] - recon[~out_mask]) <= eb)
    np.testing.assert_array_equal(g[out_mask], recon[out_mask])


def test_compressed_mean_outlier_at_last_index():
    """Regression: an outlier at flat index n-1 with spare table slots must
    ship exactly.  The empty slots' fill index is n; a clamped duplicate
    scatter (min(ii, n-1)) would overwrite the exact payload with the
    zeroed-bin reconstruction and decode 0 — silently violating the
    bound."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.compression.grads import compressed_mean

    n = 4096
    g = np.zeros(n, np.float32)
    g[:64] = 0.01
    g[-1] = 50.0                 # far outside the int8 bin range -> outlier
    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=8,
                                outlier_cap_frac=1 / 64)   # cap 64 >> 1
    mesh = jax.make_mesh((1,), ("pod",))
    mapped = jax.shard_map(lambda x: compressed_mean(x, cfg, "pod"),
                           mesh=mesh, in_specs=P(), out_specs=(P(), P()),
                           axis_names={"pod"}, check_vma=False)
    mean, resid = jax.jit(mapped)(jnp.asarray(g))
    mean = np.asarray(mean)
    assert mean[-1] == g[-1], (mean[-1], "outlier at last index not exact")
    eb = cfg.eb_rel * float(np.sqrt(np.mean(g ** 2)))
    assert np.abs(mean - g).max() <= eb * 1.01


def test_kv_pack_roundtrip_bitexact():
    cfg = kv_quantizer_config()
    x = jnp.asarray(RNG.standard_normal((2, 3, 256, 64)).astype(np.float32))
    q = quantize_kv(x, cfg)
    p = pack_kv(q)
    assert p.words.dtype == jnp.uint32
    assert p.nbytes() == kv_wire_bytes(x.shape)
    back = unpack_kv(p)
    np.testing.assert_array_equal(np.asarray(q.bins), np.asarray(back.bins))
    for a, b in zip(q[1:], back[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
