"""jit-safe fixed-shape codec over the quantizers.

XLA needs static shapes, so the in-flight representation differs from the
host byte stream (serializer.py) while preserving the paper's semantics:
outliers live WITH the bins (same index space — LC's inline placement, not
SZ3's side list), stored bit-exactly so NaN payloads / -0.0 / INF survive.

Three layouts:

  * DENSE  — bins + outlier payload at every index (payload 0 where not
    outlier).  Reference layout; wire-size = bins + full payload, used where
    simplicity beats size (activation offload, tests).
  * COMPACT — bins + (idx, payload) arrays capped at K = ceil(frac * n).
    If the outlier count exceeds K the tensor CANNOT be represented within
    the bound — encode reports `overflow` and callers must take the
    lossless path (compression/grads.py does this with a psum-agreed
    lax.cond).  The guarantee is never silently dropped.
  * PACKED — COMPACT with the bins bit-packed into uint32 lanes (and the
    REL sign plane packed at 1 bit/value).  This is the wire format the
    collectives actually move (compression/grads.py); pack/unpack here are
    the jit-safe lax shift/or reference paths, bit-exact oracles for the
    fused Pallas kernels in kernels/pack.py.  Layout documented in
    DESIGN.md §4 and under pack_words below.
  * LC — PACKED followed by the device-side lossless coding stage
    (DESIGN.md §6): the uint32 word stream is chunked, all-zero chunks are
    dropped, and the remaining chunks are stored at the minimal word width
    they need.  encode_lossless/decode_lossless are exact inverses, so the
    end-to-end bound guarantee is untouched; the Pallas twin lives in
    kernels/lossless.py.

Bin storage width is cfg.bin_bits; bins are produced as int32 and narrowed
here (safe: the quantizer's range check already confined them to
(-maxbin, maxbin)).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import quantizer as q
from .bitops import bits_to_float, float_to_bits
from .config import QuantizerConfig

_BIN_DTYPE = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}


class EncodedDense(NamedTuple):
    bins: jnp.ndarray        # int{8,16,32}[n]
    outlier: jnp.ndarray     # bool[n]
    payload: jnp.ndarray     # uint-bits[n], original bits where outlier
    sign: jnp.ndarray | None  # bool[n] (REL only)
    eb: jnp.ndarray | None   # traced scalar bound (NOA / per-tensor eb)


class EncodedCompact(NamedTuple):
    bins: jnp.ndarray        # int{8,16,32}[n]
    out_idx: jnp.ndarray     # int32[K], n = "empty slot"
    out_payload: jnp.ndarray  # uint-bits[K]
    n_outliers: jnp.ndarray  # int32 scalar
    overflow: jnp.ndarray    # bool scalar: n_outliers > K (bound NOT met)
    sign: jnp.ndarray | None
    eb: jnp.ndarray | None

    def wire_bits(self, cfg: QuantizerConfig) -> int:
        """Static wire size in bits (what the collective actually moves)."""
        n = self.bins.shape[0]
        k = self.out_idx.shape[0]
        elem = np.dtype(str(self.out_payload.dtype)).itemsize * 8
        sign_bits = n if self.sign is not None else 0
        return n * cfg.bin_bits + k * (32 + elem) + sign_bits + 64


def _narrow(bins: jnp.ndarray, cfg: QuantizerConfig) -> jnp.ndarray:
    return bins.astype(_BIN_DTYPE[cfg.bin_bits])


def encode_dense(x: jnp.ndarray, cfg: QuantizerConfig, eb=None) -> EncodedDense:
    flat = x.reshape(-1)
    if cfg.mode == "abs":
        qt = q.quantize_abs(flat, cfg, eb=eb)
    elif cfg.mode == "rel":
        qt = q.quantize_rel(flat, cfg)
    else:  # noa
        qt, eb = q.quantize_noa(flat, cfg)
    payload = jnp.where(qt.outlier, float_to_bits(flat), 0)
    return EncodedDense(_narrow(qt.bins, cfg), qt.outlier, payload, qt.sign,
                        None if eb is None else jnp.asarray(eb, flat.dtype))


def decode_dense(enc: EncodedDense, cfg: QuantizerConfig, shape=None):
    bins = enc.bins.astype(jnp.int32)
    if cfg.mode == "rel":
        recon = q.dequantize_rel(bins, enc.sign, cfg)
    else:
        recon = q.dequantize_abs(bins, cfg, eb=enc.eb)
    vals = jnp.where(enc.outlier, bits_to_float(enc.payload, recon.dtype), recon)
    return vals.reshape(shape) if shape is not None else vals


def encode_compact(x: jnp.ndarray, cfg: QuantizerConfig, eb=None) -> EncodedCompact:
    flat = x.reshape(-1)
    n = flat.shape[0]
    k = cfg.outlier_cap(n)
    if cfg.mode == "abs":
        qt = q.quantize_abs(flat, cfg, eb=eb)
    elif cfg.mode == "rel":
        qt = q.quantize_rel(flat, cfg)
    else:
        qt, eb = q.quantize_noa(flat, cfg)
    n_out = jnp.sum(qt.outlier).astype(jnp.int32)
    # Static-size gather of outlier positions; fill value n marks empties.
    (idx,) = jnp.nonzero(qt.outlier, size=k, fill_value=n)
    safe_idx = jnp.minimum(idx, n - 1)
    payload = jnp.where(idx < n, float_to_bits(flat)[safe_idx], 0)
    return EncodedCompact(_narrow(qt.bins, cfg), idx.astype(jnp.int32), payload,
                          n_out, n_out > k, qt.sign,
                          None if eb is None else jnp.asarray(eb, flat.dtype))


def decode_compact(enc: EncodedCompact, cfg: QuantizerConfig, shape=None,
                   dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    bins = enc.bins.astype(jnp.int32)
    if cfg.mode == "rel":
        recon = q.dequantize_rel(bins, enc.sign, cfg, dtype=dt)
    else:
        recon = q.dequantize_abs(bins, cfg, eb=enc.eb, dtype=dt)
    n = recon.shape[0]
    vals = bits_to_float(enc.out_payload, dt)
    # Scatter exact outliers back over their reconstructions; empty slots
    # (idx == n) drop out of bounds and are discarded by mode='drop'.
    recon = recon.at[enc.out_idx].set(vals, mode="drop")
    return recon.reshape(shape) if shape is not None else recon


def roundtrip_dense(x: jnp.ndarray, cfg: QuantizerConfig):
    """Encode+decode; the decoded result carries the full guarantee."""
    return decode_dense(encode_dense(x, cfg), cfg, shape=x.shape)


# ---------------------------------------------------------------------------
# PACKED layout — bins bit-packed into uint32 lanes (the device wire format)
# ---------------------------------------------------------------------------
#
# Word layout (little-endian within a word, lane-tiled across words): the
# flat stream is padded with zeros to a whole number of TILES of
# vpw * PACK_LANES elements (vpw = 32 // bin_bits values per word), viewed
# row-major as [R, PACK_LANES], and word row w packs element rows
# w*vpw .. w*vpw+vpw-1: element [w*vpw + i, lane] occupies bits
# [i*bin_bits, (i+1)*bin_bits) of word [w, lane].  Bins are stored as
# bin_bits-wide two's complement (lossless: the quantizer confined them to
# (-maxbin, maxbin)).  Grouping rows instead of adjacent lanes keeps the
# pack a pure sublane shift/or on the TPU VPU, and makes the layout
# identical for any kernel block height that is a multiple of vpw — the
# Pallas kernels and this reference produce bit-identical words.

PACK_LANES = 128          # lane width of the packed tile (VPU native)
_PACK_WIDTHS = (1, 2, 4, 8, 16, 32)


def packed_word_count(n: int, bin_bits: int) -> int:
    """Number of uint32 words `pack_words` emits for n elements."""
    vpw = 32 // bin_bits
    tile = vpw * PACK_LANES
    return -(-n // tile) * PACK_LANES


def pack_words(values: jnp.ndarray, bin_bits: int) -> jnp.ndarray:
    """Pack flat int values into uint32 words (layout in the module note).

    values: int32/uint32[n] with each value representable in bin_bits
    (two's complement).  Returns uint32[packed_word_count(n, bin_bits)].
    jit-safe: pure reshape + shift/or reduction, no gathers.
    """
    if bin_bits not in _PACK_WIDTHS:
        raise ValueError(f"bin_bits must be one of {_PACK_WIDTHS}")
    vpw = 32 // bin_bits
    n = values.shape[0]
    n_words = packed_word_count(n, bin_bits)
    u = values.astype(jnp.uint32)
    if bin_bits != 32:
        u = u & jnp.uint32((1 << bin_bits) - 1)
    u = jnp.pad(u, (0, n_words * vpw - n))
    grp = u.reshape(-1, vpw, PACK_LANES)
    word = grp[:, 0, :]
    for i in range(1, vpw):
        word = word | (grp[:, i, :] << jnp.uint32(i * bin_bits))
    return word.reshape(-1)


def unpack_words(words: jnp.ndarray, n: int, bin_bits: int,
                 signed: bool = True) -> jnp.ndarray:
    """Inverse of pack_words.  Returns int32[n] (sign-extended) or
    uint32[n] when signed=False."""
    vpw = 32 // bin_bits
    w = words.reshape(-1, PACK_LANES)
    if vpw == 1:
        flat = w.reshape(-1)[:n]
    else:
        mask = jnp.uint32((1 << bin_bits) - 1)
        cols = [(w >> jnp.uint32(i * bin_bits)) & mask for i in range(vpw)]
        flat = jnp.stack(cols, axis=1).reshape(-1)[:n]
    if not signed:
        return flat
    if bin_bits == 32:
        return flat.astype(jnp.int32)
    sh = jnp.int32(32 - bin_bits)
    return (flat.astype(jnp.int32) << sh) >> sh     # arithmetic sign-extend


def pack_flags(flags: jnp.ndarray) -> jnp.ndarray:
    """bool[n] -> uint32[ceil-to-tile(n/32)] at 1 bit/value (sign plane)."""
    return pack_words(flags.astype(jnp.uint32), 1)


# ---------------------------------------------------------------------------
# SHUFFLE — byte-plane shuffle with zigzag sign-fold (a lossless word stage)
# ---------------------------------------------------------------------------
#
# Two's-complement small negatives (0xFF.. sign extension) set the high
# bits of every word they touch, so the §6 width codes never fire on
# mixed-sign bin streams.  The shuffle stage (DESIGN.md §7) fixes that in
# two exactly-reversible moves, the byte-level analogue of FZ-GPU's
# bitshuffle (arXiv 2304.12557):
#
#   1. ZIGZAG fold each `width`-bit lane: z = (v << 1) ^ (v >> width-1),
#      so small |v| of EITHER sign has small z (clear high bytes);
#   2. byte-plane TRANSPOSE (width < 32): byte j of every lane becomes a
#      contiguous plane, so the cleared high bytes form whole all-zero
#      chunks the §6 coder drops.  At width == 32 a lane IS a word and the
#      §6 width codes already select trailing zero byte planes, so the
#      transpose is the identity and only the fold is applied — which is
#      exactly what makes `narrow` chunks fire on mixed-sign bins.
#
# The stream is padded to whole PACK_LANES tiles (zeros fold to zeros, so
# truncation on decode is exact); output length = shuffle_word_count(n).


def _width_mask(width: int) -> jnp.ndarray:
    return jnp.uint32(0xFFFFFFFF if width == 32 else (1 << width) - 1)


def _zigzag(lanes: jnp.ndarray, width: int) -> jnp.ndarray:
    """uint32 lanes holding width-bit two's complement -> zigzag codes."""
    sh = jnp.int32(32 - width)
    v = (lanes.astype(jnp.int32) << sh) >> sh          # sign-extend
    z = (v << jnp.int32(1)) ^ (v >> jnp.int32(31))
    return z.astype(jnp.uint32) & _width_mask(width)


def _unzigzag(z: jnp.ndarray, width: int) -> jnp.ndarray:
    v = (z >> jnp.uint32(1)) ^ (jnp.uint32(0) - (z & jnp.uint32(1)))
    return v & _width_mask(width)


def shuffle_word_count(n_words: int) -> int:
    """Words `shuffle_words` emits for an n_words stream (tile-padded)."""
    return -(-n_words // PACK_LANES) * PACK_LANES


def shuffle_words(words: jnp.ndarray, width: int) -> jnp.ndarray:
    """Fold + byte-plane-shuffle a packed uint32 word stream whose lanes
    are `width`-bit values (width in {8, 16, 32}).  jit-safe, exact
    inverse is unshuffle_words."""
    if width not in (8, 16, 32):
        raise ValueError(f"shuffle width must be 8, 16 or 32, got {width}")
    n_words = words.shape[0]
    npad = shuffle_word_count(n_words)
    w = jnp.pad(words, (0, npad - n_words))
    if width == 32:
        return _zigzag(w, 32)
    lanes = unpack_words(w, npad * 32 // width, width, signed=False)
    z = _zigzag(lanes, width)
    planes = [(z >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
              for j in range(width // 8)]
    return pack_words(jnp.concatenate(planes), 8)


def unshuffle_words(shuffled: jnp.ndarray, n_words: int,
                    width: int) -> jnp.ndarray:
    """Exact inverse of shuffle_words; n_words is the pre-shuffle count."""
    npad = shuffle_word_count(n_words)
    if width == 32:
        return _unzigzag(shuffled[:npad], 32)[:n_words]
    n_lanes = npad * 32 // width
    stream = unpack_words(shuffled, 4 * npad, 8, signed=False)
    planes = stream.reshape(width // 8, n_lanes)
    z = planes[0]
    for j in range(1, width // 8):
        z = z | (planes[j] << jnp.uint32(8 * j))
    return pack_words(_unzigzag(z, width), width)[:n_words]


def unpack_flags(words: jnp.ndarray, n: int) -> jnp.ndarray:
    return unpack_words(words, n, 1, signed=False).astype(bool)


class EncodedPacked(NamedTuple):
    """COMPACT with device-side bit-packed bins — the actual wire format.

    Everything here is what crosses the collective: uint32 words, the
    capped exact-outlier table, and an 8-byte header (n_outliers/overflow +
    eb).  No full-width bins, no bool plane, no recon plane.
    """
    words: jnp.ndarray        # uint32[n_words] — bin_bits-wide packed bins
    out_idx: jnp.ndarray      # int32[K], n = "empty slot"
    out_payload: jnp.ndarray  # uint32[K] — original IEEE bits, bit-exact
    n_outliers: jnp.ndarray   # int32 scalar
    overflow: jnp.ndarray     # bool scalar: n_outliers > K (bound NOT met)
    sign_words: jnp.ndarray | None  # uint32[n_sign_words] (REL only)
    eb: jnp.ndarray | None    # traced scalar bound (NOA / per-tensor eb)

    def wire_bits(self, cfg: QuantizerConfig | None = None) -> int:
        """Static wire size in bits — exactly the bytes the collective
        moves, tile padding included.  vs EncodedCompact (whose bins are
        also bin_bits-wide): the sign plane is 1 bit/value instead of a
        byte-wide bool, and everything rides uint32 lanes."""
        bits = 32 * self.words.shape[0]
        bits += self.out_idx.shape[0] * (32 + 32)
        if self.sign_words is not None:
            bits += 32 * self.sign_words.shape[0]
        return bits + 64                     # n_outliers/overflow + eb header


def encode_packed(x: jnp.ndarray, cfg: QuantizerConfig, eb=None, *,
                  return_quantized: bool = False,
                  bin_transform=None) -> EncodedPacked:
    """Quantize + bit-pack in one jit-safe call (reference path; the fused
    Pallas pipeline in kernels/pack.py is its bit-exact device twin).
    With return_quantized, also returns the local Quantized (outlier/recon
    planes stay on-device for residual bookkeeping, never on the wire).
    `bin_transform` (optional) is an exact int32 bijection applied to the
    bin plane just before packing — the value-domain predictor hook
    (core.predict / DESIGN.md §9).  It must be inverted by the matching
    `bin_untransform` in decode_packed; the returned Quantized keeps the
    UNtransformed bins so residual bookkeeping stays in the value domain."""
    with obs.scope("codec.quantize_pack"):
        flat = x.reshape(-1)
        n = flat.shape[0]
        k = cfg.outlier_cap(n)
        if cfg.mode == "abs":
            qt = q.quantize_abs(flat, cfg, eb=eb)
        elif cfg.mode == "rel":
            qt = q.quantize_rel(flat, cfg)
        else:
            qt, eb = q.quantize_noa(flat, cfg)
        bins = qt.bins if bin_transform is None else bin_transform(qt.bins)
        words = pack_words(bins, cfg.bin_bits)
        sign_words = None if qt.sign is None else pack_flags(qt.sign)
    n_out, idx, payload = compact_outliers(flat, qt.outlier, k)
    enc = EncodedPacked(words, idx.astype(jnp.int32),
                        payload.astype(jnp.uint32), n_out, n_out > k,
                        sign_words,
                        None if eb is None else jnp.asarray(eb, flat.dtype))
    return (enc, qt) if return_quantized else enc


def compact_outliers(flat: jnp.ndarray, outlier: jnp.ndarray, k: int):
    """The capped exact-outlier table of one encode: (count, the first k
    outlier indices padded with n, their raw bits).  Shared by the
    reference and both fused encoders."""
    n = flat.shape[0]
    with obs.scope("codec.compact"), obs.scope("codec.compact.outliers"):
        n_out = jnp.sum(outlier).astype(jnp.int32)
        (idx,) = jnp.nonzero(outlier, size=k, fill_value=n)
        safe_idx = jnp.minimum(idx, n - 1)
        payload = jnp.where(idx < n, float_to_bits(flat)[safe_idx], 0)
    return n_out, idx, payload


def decode_packed(enc: EncodedPacked, cfg: QuantizerConfig, n: int | None = None,
                  shape=None, dtype=None, bin_untransform=None):
    """Unpack + dequantize + exact outlier restore.  `n` (or `shape`) gives
    the true element count — the packed stream carries pad words.
    `bin_untransform` inverts the encode-side `bin_transform` on the
    unpacked plane before dequantize (core.predict / DESIGN.md §9)."""
    if n is None:
        if shape is None:
            raise ValueError("decode_packed needs n or shape")
        n = int(np.prod(shape))
    with obs.scope("codec.dequantize"):
        return _decode_packed(enc, cfg, n, shape, dtype, bin_untransform)


def _decode_packed(enc, cfg, n, shape, dtype, bin_untransform):
    dt = jnp.dtype(dtype or cfg.dtype)
    bins = unpack_words(enc.words, n, cfg.bin_bits)
    if bin_untransform is not None:
        bins = bin_untransform(bins)
    if cfg.mode == "rel":
        sign = unpack_flags(enc.sign_words, n)
        recon = q.dequantize_rel(bins, sign, cfg, dtype=dt)
    else:
        recon = q.dequantize_abs(bins, cfg, eb=enc.eb, dtype=dt)
    vals = bits_to_float(enc.out_payload.astype(jnp.int32), dt)
    recon = recon.at[enc.out_idx].set(vals, mode="drop")
    return recon.reshape(shape) if shape is not None else recon


# ---------------------------------------------------------------------------
# LC layout — device-side lossless stage over the packed word stream
# ---------------------------------------------------------------------------
#
# The paper's LC pipeline follows quantize+pack with a lossless coder — the
# stage GPU compressors win their ratio in (cuSZ's Huffman over quantization
# codes, FZ-GPU's bitshuffle + zero-suppression).  This is the TPU-shaped
# equivalent (DESIGN.md §6): the packed uint32 word stream is split into
# chunks of LC_CHUNK = 512 words (4 sublane rows x 128 lanes), and each
# chunk is stored at the minimal word width it needs:
#
#   code 0 — all words zero: the chunk is dropped entirely (dominant for
#            smooth/sparse gradients where most bins hit the zero bin);
#   code 1 — every word < 2^8:  stored at  8 bits/word (4 words/uint32);
#   code 2 — every word < 2^16: stored at 16 bits/word (2 words/uint32);
#   code 3 — verbatim uint32 words.
#
# A chunk's narrowed image IS pack_words(chunk_words, width): LC_CHUNK was
# chosen so one chunk is a whole pack tile at width 8 (vpw 4 * 128 lanes)
# and two tiles at width 16 — the narrowing reuses the sublane shift/or
# machinery and therefore fuses into the same kernels (kernels/lossless.py).
# The 2-bit codes pack into a header plane via pack_words(codes, 2).
#
# XLA needs static shapes, so the variable-length payload is carried
# padded-to-capacity (n_chunks * LC_CHUNK words) with the used word count
# transmitted in `payload_len` — a real transport moves only payload_len
# words plus the header plane; wire_bits() accounts exactly that.
# Every code's payload is 0, 1, 2 or 4 whole 128-lane rows, so the
# payload is placed and read back by rows from one per-chunk row offset
# (lc_row_offsets), never word by word.
# encode 'stage' selects the mode: 'zero' restricts codes to {0, 3} (zero
# suppression only), 'narrow' uses the full set.

LC_CHUNK = 512                 # words per chunk (4 x PACK_LANES)
LC_CHUNK_ROWS = LC_CHUNK // PACK_LANES
LC_STAGES = ("zero", "narrow")
_LC_WIDTHS = (0, 8, 16, 32)    # stored word width per header code
_LC_LENS = tuple(LC_CHUNK * w // 32 for w in _LC_WIDTHS)   # payload words
# every payload is whole 128-lane rows: code c < 3 holds c rows
assert _LC_LENS == (0, PACK_LANES, 2 * PACK_LANES, LC_CHUNK)


def transmitted_bits(payload_len, static_bits: int):
    """THE traced transmitted-size accounting every accessor shares
    (`Pipeline.wire_bits`, `EncodedLC.wire_bits`, `stage_report`,
    `transport._kv_wire_bytes`): `static_bits` (a python int — headers,
    tables, length fields) plus 32 bits per transmitted payload word.
    The static part is folded into the WORD count as exact int32 and
    converted to f32 ONCE: exact through 2^24 total words, one final
    rounding (never accumulated drift) beyond, and well-defined up to
    2^31 words (8 GiB of payload — beyond any single wire this repo can
    hold in device memory, since the padded capacity buffer is at least
    as large; int32 would wrap past that, f32-per-term would drift far
    sooner).  This JAX has no int64, hence the envelope."""
    static_words, rem = divmod(static_bits, 32)
    words = payload_len + jnp.int32(static_words)
    return 32.0 * words.astype(jnp.float32) + rem


def lc_chunk_count(n_words: int) -> int:
    return -(-n_words // LC_CHUNK)


def lc_header_words(n_words: int) -> int:
    """uint32 words in the STORED 2-bit header plane for an n_words stream
    (tile-padded per the §4 layout, pad words zero)."""
    return packed_word_count(lc_chunk_count(n_words), 2)


def lc_header_content_words(n_chunks: int) -> int:
    """uint32 words of real header content — 16 two-bit codes per word.
    This is what a transport moves; the stored plane is tile-padded to
    lc_header_words(...) with zeros the receiver re-pads, exactly like the
    payload's capacity padding."""
    return -(-n_chunks // 16)


def lc_chunk_codes(chunks: jnp.ndarray, stage: str) -> jnp.ndarray:
    """Per-chunk width code.  chunks: uint32[n_chunks, LC_CHUNK]."""
    if stage not in LC_STAGES:
        raise ValueError(f"lossless stage must be one of {LC_STAGES}")
    mx = jnp.max(chunks, axis=1)
    zero = mx == 0
    if stage == "zero":
        return jnp.where(zero, 0, 3).astype(jnp.int32)
    return jnp.where(zero, 0,
                     jnp.where(mx < (1 << 8), 1,
                               jnp.where(mx < (1 << 16), 2, 3))
                     ).astype(jnp.int32)


def lc_chunk_lens(codes: jnp.ndarray) -> jnp.ndarray:
    """Payload words each chunk occupies, from its header code."""
    return jnp.take(jnp.asarray(_LC_LENS, jnp.int32), codes)


def lc_narrow_chunks(chunks: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Narrow each chunk to its code's width, left-aligned and zero-padded
    to LC_CHUNK (the compaction scatter strips the padding)."""
    n_chunks = chunks.shape[0]
    flat = chunks.reshape(-1)
    # full-stream pack groups whole chunks (LC_CHUNK is a tile multiple for
    # both widths), so this equals a per-chunk pack_words — and equals the
    # kernels' sublane _pack_block on the same rows.
    cand1 = pack_words(flat, 8).reshape(n_chunks, LC_CHUNK // 4)
    cand2 = pack_words(flat, 16).reshape(n_chunks, LC_CHUNK // 2)
    pad1 = jnp.pad(cand1, ((0, 0), (0, LC_CHUNK - LC_CHUNK // 4)))
    pad2 = jnp.pad(cand2, ((0, 0), (0, LC_CHUNK - LC_CHUNK // 2)))
    c = codes[:, None]
    return jnp.where(c == 1, pad1,
                     jnp.where(c == 2, pad2,
                               jnp.where(c == 3, chunks, jnp.uint32(0))))


def compact_chunks(sel: jnp.ndarray, lens: jnp.ndarray):
    """Concatenate per-chunk word prefixes at their true lengths.  sel:
    uint32[n_chunks, LC_CHUNK] (each chunk's payload left-aligned), lens:
    int32[n_chunks] words used per chunk (<= LC_CHUNK).  Returns (payload
    uint32[n_chunks * LC_CHUNK] with the tail zero, payload_len int32
    scalar — the words a real transport moves).  Word-granular: the `ent`
    stage's lengths are arbitrary; the zero/narrow coder moves whole rows
    (lc_compact_payload)."""
    n_chunks = sel.shape[0]
    cap = n_chunks * LC_CHUNK
    with obs.scope("codec.compact"), obs.scope("codec.compact.chunks"):
        ends = jnp.cumsum(lens)
        offs = ends - lens
        slot = jnp.arange(LC_CHUNK, dtype=jnp.int32)[None, :]
        dest = jnp.where(slot < lens[:, None], offs[:, None] + slot, cap)
        payload = jnp.zeros((cap,), jnp.uint32).at[dest.reshape(-1)].set(
            sel.reshape(-1), mode="drop")
    return payload, ends[-1].astype(jnp.int32)


def gather_chunks(payload: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """Inverse of compact_chunks: re-pad each chunk's words to LC_CHUNK
    slots.  Returns uint32[n_chunks, LC_CHUNK].

    Corrupt (over-long) transmitted lengths would otherwise index past
    the padded plane; the clamp makes the gather deterministic on every
    backend — host-side length validation with a structured error lives
    at the decode entries (audit.check_payload_len, DESIGN.md §12)."""
    ends = jnp.cumsum(lens)
    offs = ends - lens
    slot = jnp.arange(LC_CHUNK, dtype=jnp.int32)[None, :]
    valid = slot < lens[:, None]
    src = jnp.where(valid, offs[:, None] + slot, 0)
    src = jnp.clip(src, 0, jnp.int32(payload.shape[0] - 1))
    return jnp.where(valid, payload[src], jnp.uint32(0))


def lc_row_offsets(codes: jnp.ndarray):
    """Where each chunk's payload lies, in 128-word rows: (rows int32
    [n_chunks] in {0, 1, 2, 4}, first row int32[n_chunks], total rows
    int32 scalar).  The one source of the offsets for both the
    compaction and its inverse gather."""
    # code c < 3 holds c rows (_LC_LENS): arithmetic, not a table lookup
    rows = jnp.where(codes == 3, LC_CHUNK_ROWS, codes).astype(jnp.int32)
    ends = jnp.cumsum(rows)
    return rows, ends - rows, ends[-1]


def lc_compact_payload(sel: jnp.ndarray, codes: jnp.ndarray):
    """compact_chunks with the §6 per-code chunk lengths, moving whole
    rows: chunk c's rows j < rows_c land at row first_c + j.  Each of the
    LC_CHUNK_ROWS scatters writes unique rows (rows of one chunk never
    overlap another's); a row past its chunk's length goes to a row of
    its own past the end, and is dropped.
    Bit-identical to compact_chunks(sel, lc_chunk_lens(codes))."""
    n_chunks = sel.shape[0]
    n_rows = n_chunks * LC_CHUNK_ROWS
    with obs.scope("codec.compact"), obs.scope("codec.compact.chunks"):
        rows, first, total = lc_row_offsets(codes)
        src = sel.reshape(n_chunks, LC_CHUNK_ROWS, PACK_LANES)
        past = n_rows + jnp.arange(n_chunks, dtype=jnp.int32)
        payload = jnp.zeros((n_rows, PACK_LANES), jnp.uint32)
        for j in range(LC_CHUNK_ROWS):
            dest = jnp.where(j < rows, first + j, past)
            payload = payload.at[dest].set(src[:, j], mode="drop",
                                           unique_indices=True)
    return payload.reshape(-1), total * PACK_LANES


def _lc_payload_rows(payload: jnp.ndarray, codes: jnp.ndarray,
                     j: jnp.ndarray) -> jnp.ndarray:
    """Row j[c, b] of chunk c's payload for each of its LC_CHUNK_ROWS
    slots b, one 128-word row per index: uint32[n_chunks, LC_CHUNK_ROWS,
    PACK_LANES].  A slot whose j is not below the chunk's row count reads
    zero.  A header that claims more rows than the payload holds (a
    payload cut short) reads the payload's last row again: deterministic,
    and never out of bounds."""
    prows = payload.reshape(-1, PACK_LANES)
    rows, first, _ = lc_row_offsets(codes)
    src = jnp.clip(first[:, None] + j, 0, prows.shape[0] - 1)
    return jnp.where((j < rows[:, None])[..., None], prows[src],
                     jnp.uint32(0))


def lc_gather_chunks(payload: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Inverse of lc_compact_payload: re-pad each chunk's narrowed rows to
    LC_CHUNK slots.  Returns uint32[n_chunks, LC_CHUNK]."""
    j = jnp.arange(LC_CHUNK_ROWS, dtype=jnp.int32)[None, :]
    return _lc_payload_rows(payload, codes, j).reshape(-1, LC_CHUNK)


def lc_expand_payload(payload: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """The chunks' words straight from the payload: lc_gather_chunks and
    the widening in one pass, one 128-word row per index.  At width w a
    payload row holds 32/w word rows (pack_words' layout), so word row b
    of a chunk is payload row b // (32/w) of it, shifted right by
    w * (b % (32/w)) and masked to w bits.  Returns uint32[n_chunks,
    LC_CHUNK]."""
    c = codes[:, None]
    b = jnp.arange(LC_CHUNK_ROWS, dtype=jnp.int32)[None, :]
    per = jnp.maximum(3 - c, 0)            # log2 of word rows a payload row
    width = jnp.left_shift(4, c)           # codes 1, 2, 3: 8, 16, 32 bits
    shift = ((b & ((1 << per) - 1)) * width).astype(jnp.uint32)
    mask = jnp.where(c == 3, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << width.astype(jnp.uint32)) - 1)
    rows = _lc_payload_rows(payload, codes, b >> per)
    out = (rows >> shift[..., None]) & mask[..., None]
    return out.reshape(-1, LC_CHUNK)


def encode_words_lc(words: jnp.ndarray, stage: str = "narrow"):
    """Lossless-code a packed uint32 word stream (layout in the module
    note).  Returns (header_words, payload, payload_len); jit-safe, exact.
    Reusable on any word plane (gradient shards, KV pages, sign planes)."""
    n_words = words.shape[0]
    n_chunks = lc_chunk_count(n_words)
    wpad = jnp.pad(words, (0, n_chunks * LC_CHUNK - n_words))
    chunks = wpad.reshape(n_chunks, LC_CHUNK)
    codes = lc_chunk_codes(chunks, stage)
    sel = lc_narrow_chunks(chunks, codes)
    payload, plen = lc_compact_payload(sel, codes)
    return pack_words(codes, 2), payload, plen


def decode_words_lc(header_words: jnp.ndarray, payload: jnp.ndarray,
                    n_words: int) -> jnp.ndarray:
    """Exact inverse of encode_words_lc.  n_words is the pre-coding word
    count (packed_word_count of the element count)."""
    n_chunks = lc_chunk_count(n_words)
    codes = unpack_words(header_words, n_chunks, 2,
                         signed=False).astype(jnp.int32)
    return lc_expand_payload(payload, codes).reshape(-1)[:n_words]


class EncodedLC(NamedTuple):
    """PACKED after the device-side lossless stage — the compressed wire.

    `payload` is padded to static capacity for XLA; only `payload_len`
    words of it (plus the header plane and the outlier table) are
    meaningful, and wire_bits() counts exactly those.  decode_lossless
    reproduces the EncodedPacked bit-for-bit, so every guarantee statement
    about PACKED carries over verbatim.  Layout: DESIGN.md §6.
    """
    header_words: jnp.ndarray   # uint32 — 2-bit per-chunk width codes
    payload: jnp.ndarray        # uint32[capacity] — compacted chunk data
    payload_len: jnp.ndarray    # int32 scalar — words actually used
    out_idx: jnp.ndarray        # int32[K], n = "empty slot"
    out_payload: jnp.ndarray    # uint32[K] — original IEEE bits
    n_outliers: jnp.ndarray     # int32 scalar
    overflow: jnp.ndarray       # bool scalar (bound NOT met when True)
    sign_words: jnp.ndarray | None  # uint32 (REL only, not lossless-coded)
    eb: jnp.ndarray | None      # traced scalar bound

    def wire_bits(self, cfg: QuantizerConfig | None = None):
        """Transmitted wire size in bits.  Traced (data-dependent) because
        the payload is variable-length; +32 for the transmitted length.
        Counts the header plane's content words only (its tile padding is
        zeros the receiver re-pads, like the payload's capacity padding).
        Routed through `transmitted_bits` — exact int32 word
        accumulation with one f32 conversion (see its docstring for the
        precision envelope)."""
        n_chunks = self.payload.shape[0] // LC_CHUNK
        static = 32 * lc_header_content_words(n_chunks)
        static += self.out_idx.shape[0] * (32 + 32)
        if self.sign_words is not None:
            static += 32 * self.sign_words.shape[0]
        static += 64 + 32           # packed header + payload_len field
        return transmitted_bits(self.payload_len, static)


# ---------------------------------------------------------------------------
# ENT — static canonical entropy coder over surviving chunk payloads (§7)
# ---------------------------------------------------------------------------
#
# The ratio the §6 width codes leave on the table is sub-byte: a surviving
# narrowed chunk still spends a full 8 bits on every byte even when the
# byte distribution is heavily skewed (small bins cluster around 0x00/0xFF).
# The `ent` word stage closes that gap cuSZ-style — a STATIC codebook built
# from the symbol histogram, transmitted in the stage's header plane — with
# FZ-GPU's lesson kept intact: the transform is an exact, reversible pass
# over the device word stream, so the §1 guarantee is untouched.
#
# Layout.  The input word stream is chunked exactly like §6 (LC_CHUNK = 512
# words).  Each chunk gets a 2-bit mode code:
#
#   mode 0 — all words zero: dropped entirely (0 payload words);
#   mode 1 — entropy-coded: the chunk's 2048 bytes (little-endian within
#            each word) encode as a variable-length bitstream, padded to a
#            whole word count, bit length transmitted per chunk;
#   mode 2 — verbatim escape: the coded stream would exceed the chunk's
#            raw 512 words (incompressible bytes), so the chunk is stored
#            untouched — `ent` never costs more than the header planes.
#
# The codebook is one canonical prefix code shared by every chunk of the
# stream, built from the byte histogram of the SURVIVING (non-zero) chunks:
# per-symbol Shannon lengths ceil(-log2 p) — read off the f32 exponent
# bits, no transcendentals, so the wire is deterministic integer work —
# clipped to ENT_MAX_LEN, then a Kraft-budget sweep over symbols in
# descending frequency guarantees sum 2^-l <= 1 (a canonical code always
# exists; frequent symbols keep their ideal lengths).  Only the 256 4-bit
# LENGTHS are transmitted — canonical codes and the 2^ENT_MAX_LEN decode
# LUT rebuild from lengths alone, the classic canonical-Huffman trick.
#
# Bit order: codes deposit first-bit-at-lowest-bit (LSB-first within
# uint32 words), so encode is a cumsum + disjoint-bit scatter-add and
# decode reads a 32-bit window per symbol.  Chunks encode independently —
# decode is a per-chunk scan (2048 symbols) vmapped across chunks, the
# same independence cuSZ uses to parallelize Huffman on GPUs.  The jit
# reference lives here; a fused Pallas kernel slot is documented in the
# §7 dispatch table.

ENT_MAX_LEN = 12               # max code length; decode LUT = 2^12 entries
ENT_SYMS = 256                 # byte alphabet
_ENT_CHUNK_SYMS = 4 * LC_CHUNK            # 2048 coded bytes per chunk
_ENT_CHUNK_CAP_BITS = 32 * LC_CHUNK       # verbatim-escape threshold
_ENT_BUF_WORDS = _ENT_CHUNK_SYMS * ENT_MAX_LEN // 32   # worst-case coded
# chunks per lax.map step of the ent coder: a step's byte-symbol planes
# stay at a few MiB however long the stream (a 512^3 field is 2^18 chunks)
_ENT_MAP_CHUNKS = 2048

# Static bit-reversal table for ENT_MAX_LEN-bit values (the canonical
# code is MSB-first; the stream is LSB-first — see the bit-order note).
_rev = np.zeros(1 << ENT_MAX_LEN, np.int32)
for _j in range(ENT_MAX_LEN):
    _rev = (_rev << 1) | ((np.arange(1 << ENT_MAX_LEN) >> _j) & 1)
_ENT_REV = _rev
del _rev, _j


def ent_header_words(n_words: int) -> int:
    """uint32 words in the STORED `ent` header plane: the 4-bit codebook
    lengths, the 2-bit per-chunk modes, and the 16-bit per-chunk bit
    lengths, each tile-padded per the §4 pack layout."""
    nc = lc_chunk_count(n_words)
    return (packed_word_count(ENT_SYMS, 4) + packed_word_count(nc, 2)
            + packed_word_count(nc, 16))


def ent_header_content_words(n_chunks: int) -> int:
    """uint32 words of real header content (what a transport moves; the
    stored plane's tile padding is zeros the receiver re-pads): 32 words
    of codebook lengths + 2 bits/chunk of modes + 16 bits/chunk of bit
    lengths."""
    return (ENT_SYMS * 4 // 32 + lc_header_content_words(n_chunks)
            + -(-n_chunks // 2))


def _floor_log2_f32(x: jnp.ndarray) -> jnp.ndarray:
    """floor(log2 x) for positive normal f32 — the unbiased exponent,
    pure integer work (deterministic on every backend)."""
    return ((float_to_bits(x) >> 23) & 0xFF) - 127


def ent_code_lengths(hist: jnp.ndarray) -> jnp.ndarray:
    """Length-limited code lengths (1..ENT_MAX_LEN) from a 256-bin symbol
    histogram (int32[256]).  Shannon ideal ceil(-log2 p) per symbol
    (= -floor_log2(p) exactly, read off the f32 exponent), clipped, then
    repaired to Kraft-feasibility by a budget scan in descending
    frequency order: each symbol takes the longest of its ideal length
    and the shortest length the remaining budget can afford while
    leaving one 2^-ENT_MAX_LEN slot per remaining symbol.  The budget
    invariant guarantees sum 2^-l <= 1, so canonical codes exist."""
    lmax = ENT_MAX_LEN
    total = jnp.maximum(jnp.sum(hist), 1).astype(jnp.float32)
    p = jnp.maximum(hist.astype(jnp.float32) / total, jnp.float32(2.0**-126))
    ideal = jnp.where(hist > 0, -_floor_log2_f32(p), lmax)
    ideal = jnp.clip(ideal, 1, lmax).astype(jnp.int32)
    order = jnp.argsort(-hist)                 # frequency descending
    remaining = jnp.arange(ENT_SYMS - 1, -1, -1, dtype=jnp.int32)

    def step(budget, inp):
        want, rem = inp
        lmin = lmax - _floor_log2_f32((budget - rem).astype(jnp.float32))
        lens = jnp.clip(jnp.maximum(want, lmin), 1, lmax)
        return budget - (jnp.int32(1) << (lmax - lens)), lens

    _, lens_sorted = jax.lax.scan(step, jnp.int32(1 << lmax),
                                  (ideal[order], remaining))
    return jnp.zeros(ENT_SYMS, jnp.int32).at[order].set(lens_sorted)


def _ent_canonical(lens: jnp.ndarray):
    """Canonical code assignment from lengths: symbols sorted by
    (length, symbol) take consecutive codes within their length class.
    Returns (order int32[256] = symbols in canonical order, codes
    MSB-first per canonical position, first-bit-aligned code starts)."""
    lmax = ENT_MAX_LEN
    count = jnp.zeros(lmax + 1, jnp.int32).at[lens].add(1)
    first, code = [jnp.int32(0)] * (lmax + 1), jnp.int32(0)
    for ln in range(1, lmax + 1):
        code = (code + count[ln - 1]) << 1
        first[ln] = code
    first = jnp.stack(first)
    order = jnp.argsort(lens)                  # stable: (length, symbol)
    sl = lens[order]
    rank = jnp.arange(ENT_SYMS, dtype=jnp.int32) - jnp.searchsorted(
        sl, sl, side="left").astype(jnp.int32)
    codes = first[sl] + rank
    return order, sl, codes


def ent_encode_table(lens: jnp.ndarray):
    """(length, LSB-first deposit value) per SYMBOL, from the code
    lengths: the deposit value is the canonical code bit-reversed within
    its length so its first (most-significant) bit lands first in the
    LSB-first stream."""
    order, sl, codes = _ent_canonical(lens)
    rev = jnp.asarray(_ENT_REV)[codes] >> (ENT_MAX_LEN - sl)
    return (jnp.zeros(ENT_SYMS, jnp.int32).at[order].set(sl),
            jnp.zeros(ENT_SYMS, jnp.uint32).at[order].set(
                rev.astype(jnp.uint32)))


def ent_decode_lut(lens: jnp.ndarray):
    """(symbol, length) decode LUT indexed by the next ENT_MAX_LEN raw
    stream bits (LSB-first window): canonical code starts are sorted, so
    the matching symbol is a searchsorted over the MSB-aligned window,
    composed with the static bit-reversal."""
    lmax = ENT_MAX_LEN
    order, sl, codes = _ent_canonical(lens)
    starts = codes << (lmax - sl)              # strictly increasing
    win = jnp.asarray(_ENT_REV)                # raw window -> MSB-aligned
    j = jnp.clip(jnp.searchsorted(starts, win, side="right") - 1,
                 0, ENT_SYMS - 1)
    return order[j].astype(jnp.int32), sl[j]


def _ent_chunk_bytes(chunks: jnp.ndarray) -> jnp.ndarray:
    """uint32[nc, LC_CHUNK] -> int32[nc, 4*LC_CHUNK] byte symbols in
    stream order (little-endian within each word).  Built lane-dense:
    a [..., 4] minor axis would pad 32x in TPU tiles."""
    k = np.arange(_ENT_CHUNK_SYMS)
    src = jnp.take(chunks, jnp.asarray(k // 4), axis=1)
    shift = jnp.asarray((k % 4) * 8, jnp.uint32)
    return ((src >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)


def encode_words_ent(words: jnp.ndarray):
    """Entropy-code a packed uint32 word stream (layout in the module
    note).  Returns (header_words, payload, payload_len); jit-safe,
    exact inverse is decode_words_ent.  Reusable on any word plane —
    gradient shards, KV pages — like every §7 word stage."""
    n_words = words.shape[0]
    nc = lc_chunk_count(n_words)
    wpad = jnp.pad(words, (0, nc * LC_CHUNK - n_words))
    chunks = wpad.reshape(nc, LC_CHUNK)
    alive = jnp.max(chunks, axis=1) > 0

    def chunk_hist(c):
        b = _ent_chunk_bytes(c[None])[0]
        return jnp.zeros(ENT_SYMS, jnp.int32).at[b].add(1)

    # codebook from the byte histogram of SURVIVING chunks only — zero
    # chunks are dropped whole and must not skew the code lengths
    hist = jnp.sum(jnp.where(alive[:, None], jax.lax.map(
        chunk_hist, chunks, batch_size=_ENT_MAP_CHUNKS), 0), axis=0)
    lens = ent_code_lengths(hist)
    sym_len, sym_code = ent_encode_table(lens)

    def code_chunk(c):
        # per-chunk bitstream: cumsum the code lengths, deposit each
        # code's <= 2 word fragments by scatter-ADD (bits are disjoint,
        # so add == or)
        b = _ent_chunk_bytes(c[None])[0]
        lns = sym_len[b]
        ends = jnp.cumsum(lns)
        offs = ends - lns
        code = sym_code[b]
        w_idx = offs >> 5
        boff = (offs & 31).astype(jnp.uint32)
        lo = code << boff
        hi = jnp.where(boff > 0,
                       code >> jnp.where(boff > 0, jnp.uint32(32) - boff,
                                         jnp.uint32(1)),
                       jnp.uint32(0))
        buf = jnp.zeros((_ENT_BUF_WORDS + 1,), jnp.uint32)
        buf = buf.at[w_idx].add(lo).at[w_idx + 1].add(hi)
        return buf[:LC_CHUNK], ends[-1]

    coded, bitlen = jax.lax.map(code_chunk, chunks,
                                batch_size=_ENT_MAP_CHUNKS)
    modes = jnp.where(~alive, 0,
                      jnp.where(bitlen <= _ENT_CHUNK_CAP_BITS, 1, 2)
                      ).astype(jnp.int32)
    m = modes[:, None]
    sel = jnp.where(m == 1, coded, jnp.where(m == 2, chunks, jnp.uint32(0)))
    lens_words = jnp.where(modes == 1, (bitlen + 31) >> 5,
                           jnp.where(modes == 2, LC_CHUNK, 0)
                           ).astype(jnp.int32)
    payload, plen = compact_chunks(sel, lens_words)
    header = jnp.concatenate([
        pack_words(lens, 4),
        pack_words(modes, 2),
        pack_words(jnp.where(modes == 1, bitlen, 0), 16)])
    return header, payload, plen


def decode_words_ent(header_words: jnp.ndarray, payload: jnp.ndarray,
                     n_words: int) -> jnp.ndarray:
    """Exact inverse of encode_words_ent.  n_words is the pre-coding word
    count; everything needed to decode (codebook lengths, per-chunk modes
    and bit lengths) rides in the header plane."""
    nc = lc_chunk_count(n_words)
    hw_len = packed_word_count(ENT_SYMS, 4)
    hw_mode = packed_word_count(nc, 2)
    lens = unpack_words(header_words[:hw_len], ENT_SYMS, 4,
                        signed=False).astype(jnp.int32)
    modes = unpack_words(header_words[hw_len:hw_len + hw_mode], nc, 2,
                         signed=False).astype(jnp.int32)
    bitlen = unpack_words(header_words[hw_len + hw_mode:], nc, 16,
                          signed=False).astype(jnp.int32)
    lens_words = jnp.where(modes == 1, (bitlen + 31) >> 5,
                           jnp.where(modes == 2, LC_CHUNK, 0)
                           ).astype(jnp.int32)
    padded = gather_chunks(payload, lens_words)
    lut_sym, lut_len = ent_decode_lut(lens)
    buf = jnp.pad(padded, ((0, 0), (0, 1)))    # window reads cross words

    def dec_chunk(cw):
        def step(pos, _):
            wi = pos >> 5
            bo = (pos & 31).astype(jnp.uint32)
            win = (cw[wi] >> bo) | jnp.where(
                bo > 0,
                cw[wi + 1] << jnp.where(bo > 0, jnp.uint32(32) - bo,
                                        jnp.uint32(1)),
                jnp.uint32(0))
            u = (win & jnp.uint32((1 << ENT_MAX_LEN) - 1)).astype(jnp.int32)
            # clamp: mode-0/2 lanes decode garbage that the mode mask
            # discards, but their positions must stay inside the padded
            # row (a fused-kernel port has no OOB-gather clamping); a
            # real mode-1 stream never exceeds the cap, so this is a
            # no-op for it
            nxt = jnp.minimum(pos + lut_len[u],
                              jnp.int32(_ENT_CHUNK_CAP_BITS))
            return nxt, lut_sym[u].astype(jnp.uint32)

        _, syms = jax.lax.scan(step, jnp.int32(0), None,
                               length=_ENT_CHUNK_SYMS)
        return (syms[0::4] | (syms[1::4] << jnp.uint32(8))
                | (syms[2::4] << jnp.uint32(16))
                | (syms[3::4] << jnp.uint32(24)))

    decoded = jax.lax.map(dec_chunk, buf, batch_size=_ENT_MAP_CHUNKS)
    m = modes[:, None]
    out = jnp.where(m == 1, decoded,
                    jnp.where(m == 2, padded, jnp.uint32(0)))
    return out.reshape(-1)[:n_words]


def encode_lossless(enc: EncodedPacked, stage: str = "narrow") -> EncodedLC:
    """Run the device-side lossless stage over an EncodedPacked (reference
    path; kernels/lossless.py is its bit-exact Pallas twin)."""
    header_words, payload, plen = encode_words_lc(enc.words, stage)
    return EncodedLC(header_words, payload, plen, enc.out_idx,
                     enc.out_payload, enc.n_outliers, enc.overflow,
                     enc.sign_words, enc.eb)


def decode_lossless(lc: EncodedLC, n_words: int) -> EncodedPacked:
    """Exact inverse of encode_lossless; n_words as in decode_words_lc."""
    words = decode_words_lc(lc.header_words, lc.payload, n_words)
    return EncodedPacked(words, lc.out_idx, lc.out_payload, lc.n_outliers,
                         lc.overflow, lc.sign_words, lc.eb)
