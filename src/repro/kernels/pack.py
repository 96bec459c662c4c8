"""Pallas TPU kernels: fused quantize + bit-pack (and unpack + dequantize).

The paper's LC pipeline wins on throughput because quantize -> pack ->
lossless runs GPU-resident; the seed quantize kernel wrote full-width
int32 bins plus bool-outlier and f32-recon planes to HBM (9 B/element)
and narrowing to bin_bits was a separate XLA pass — another full HBM
round trip.  These kernels close that gap on TPU: ONE HBM pass reads x
and writes bin_bits-wide bins already packed into uint32 lanes (plus the
outlier mask used to build the capped exact table) — the same fusion
FZ-GPU (arXiv 2304.12557) and cuSZ (arXiv 2007.09625) use on GPU,
adapted to the VPU:

  * packing is a SUBLANE shift/or: a (rows, 128) bin block is viewed as
    (rows/vpw, vpw, 128) and reduced over the middle axis, so no lane
    crossings are needed (lane shuffles are the expensive op on TPU).
  * the layout is block-height invariant (any rows % vpw == 0), so kernel
    words are bit-identical to the jit-safe reference in core.codec
    (pack_words) — which is the oracle the tests pin these kernels to.
  * quantize math is the bit-exact twin of core.quantizer (same as
    kernels/quantize_abs.py / quantize_rel.py); the pack rides for free
    under the same HBM stream (still ~1 flop/byte, memory-bound).

HBM accounting at bin_bits=8: fused output is words + bool = 2 B/element
vs the seed pipeline's 9 B/element kernel output, and no recon plane or
full-width bins are ever materialized (outliers ride the capped
(idx, payload) table; the REL sign plane packs at 1 bit/value vs a
byte-wide bool).

On a TPU these kernels are f32 only: Mosaic refuses 64-bit types, so
the f64 branches below run in interpret mode and nowhere else.

The device-side lossless stage (DESIGN.md §6) rides this same HBM pass:
kernels/lossless.py reuses _abs/_rel_quantize_block and _pack_block below
to fuse quantize + pack + per-chunk zero-detection/width-narrowing into
one kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro import obs
from repro.core import QuantizerConfig
from repro.core import codec as C
from repro.core.quantizer import Quantized

from .quantize_abs import DEFAULT_ROWS, LANES
from .quantize_rel import _log2approx, _pow2approx

assert LANES == C.PACK_LANES, "kernel tile width must match the wire layout"


# ------------------------------------------------------------- in-kernel --

def _pack_block(u32, vpw, bin_bits):
    """(rows, 128) uint32 -> (rows/vpw, 128) packed words (sublane or)."""
    if vpw == 1:
        return u32
    grp = u32.reshape(-1, vpw, LANES)
    word = grp[:, 0, :]
    for i in range(1, vpw):
        word = word | (grp[:, i, :] << jnp.uint32(i * bin_bits))
    return word


def _unpack_block(words, vpw, bin_bits, signed=True):
    """(rows/vpw, 128) words -> (rows, 128) int32 (sign-extended bins)."""
    if vpw == 1:
        return words.astype(jnp.int32) if signed else words
    mask = jnp.uint32((1 << bin_bits) - 1)
    cols = [(words >> jnp.uint32(i * bin_bits)) & mask for i in range(vpw)]
    flat = jnp.stack(cols, axis=1).reshape(-1, LANES)
    if not signed:
        return flat
    sh = jnp.int32(32 - bin_bits)
    return (flat.astype(jnp.int32) << sh) >> sh


def _narrow_mask(bin_bits):
    return jnp.uint32((1 << bin_bits) - 1) if bin_bits != 32 else jnp.uint32(
        0xFFFFFFFF)


# ---------------------------------------------------- fused quantize+pack --

def _eb_row(eb_ref):
    """The (1, 1) bound operand as a (1, LANES) vector.  Mosaic bitcasts
    vectors only, so the pow2 step is derived on a row that broadcasts
    over the block's sublanes."""
    return jnp.broadcast_to(eb_ref[...], (1, LANES))


def _abs_step(eb_in, dt, eb_floor):
    """(eb clamped to the FTZ floor, pow2-floored step 2*eb) — the
    bit-exact twin of core.quantizer's step; pow2 makes it FMA-immune."""
    eb = jnp.maximum(eb_in, jnp.asarray(eb_floor, dt))
    mant_mask = (1 << 23) - 1 if dt == jnp.float32 else (1 << 52) - 1
    int_t = jnp.int32 if dt == jnp.float32 else jnp.int64
    eb2 = lax.bitcast_convert_type(
        lax.bitcast_convert_type(jnp.asarray(2.0, dt) * eb, int_t) & ~mant_mask,
        dt)
    return eb, eb2


def _abs_quantize_block(x, eb_in, *, maxbin, tighten, eb_floor):
    """In-kernel ABS quantize math (bit-exact twin of core.quantizer).
    Returns (bins int32 with outliers zeroed, outlier bool).  Shared by the
    pack kernels here and the fused lossless kernels (kernels/lossless.py)."""
    dt = x.dtype
    degenerate = ~(eb_in >= eb_floor)            # FTZ guard (see core.config)
    eb, eb2 = _abs_step(eb_in, dt, eb_floor)
    inv_eb2 = jnp.asarray(1.0, dt) / eb2

    finite = jnp.isfinite(x)
    xs = jnp.where(finite, x, jnp.zeros((), dt))
    bin_f = jnp.rint(xs * inv_eb2)
    range_bad = jnp.abs(bin_f) >= jnp.asarray(float(maxbin), dt)
    bin_i = jnp.where(range_bad, jnp.zeros_like(bin_f), bin_f).astype(jnp.int32)
    range_bad_i = (bin_i >= maxbin) | (bin_i <= -maxbin)   # paper §3.3 form

    recon = bin_i.astype(dt) * eb2               # exact (pow2 step)
    fails = ~(jnp.abs(x - recon) <= eb * jnp.asarray(tighten, dt))
    fails |= ~jnp.isfinite(recon)    # recon-overflow guard (see quantizer.py)
    outlier = (~finite) | range_bad | range_bad_i | fails | degenerate
    return jnp.where(outlier, 0, bin_i), outlier


def _rel_quantize_block(x, *, maxbin, tighten, eb, log_step, inv_log_step,
                        screen, tiny, mb, emask, bias):
    """In-kernel REL quantize math.  Returns (bins, outlier, neg)."""
    dt = x.dtype
    int_t = jnp.int32 if dt == jnp.float32 else jnp.int64

    finite = jnp.isfinite(x)
    ax = jnp.abs(x)
    too_small = ~(ax >= jnp.asarray(screen, dt))           # FTZ screen
    safe = jnp.where(finite & ~too_small, ax, jnp.ones((), dt))
    lg = _log2approx(safe, mb, emask, bias)
    bin_f = jnp.rint(lg * jnp.asarray(inv_log_step, dt))
    range_bad = jnp.abs(bin_f) >= jnp.asarray(float(maxbin), dt)
    bin_i = jnp.where(range_bad, jnp.zeros_like(bin_f), bin_f).astype(jnp.int32)
    range_bad_i = (bin_i >= maxbin) | (bin_i <= -maxbin)   # paper §3.3 form

    neg = lax.bitcast_convert_type(x, int_t) < 0           # bit-pattern sign
    mag = _pow2approx(bin_i.astype(dt) * jnp.asarray(log_step, dt), mb, bias)
    recon = jnp.where(neg, -mag, mag)
    ebT = jnp.asarray(dt.type(eb) * dt.type(tighten), dt)
    ok = (jnp.abs(x - recon) <= ebT * ax) & jnp.isfinite(recon)
    ok &= mag >= jnp.asarray(tiny, dt)
    outlier = (~finite) | too_small | range_bad | range_bad_i | ~ok
    return jnp.where(outlier, 0, bin_i), outlier, neg


def _abs_pack_kernel(x_ref, eb_ref, words_ref, out_ref, *, maxbin, tighten,
                     eb_floor, bin_bits):
    bins, outlier = _abs_quantize_block(x_ref[...], _eb_row(eb_ref),
                                        maxbin=maxbin, tighten=tighten,
                                        eb_floor=eb_floor)
    words_ref[...] = _pack_block(
        bins.astype(jnp.uint32) & _narrow_mask(bin_bits),
        32 // bin_bits, bin_bits)
    out_ref[...] = outlier


def _rel_pack_kernel(x_ref, words_ref, out_ref, sign_words_ref, *, maxbin,
                     tighten, eb, log_step, inv_log_step, screen, tiny, mb,
                     emask, bias, bin_bits):
    bins, outlier, neg = _rel_quantize_block(
        x_ref[...], maxbin=maxbin, tighten=tighten, eb=eb, log_step=log_step,
        inv_log_step=inv_log_step, screen=screen, tiny=tiny, mb=mb,
        emask=emask, bias=bias)
    words_ref[...] = _pack_block(
        bins.astype(jnp.uint32) & _narrow_mask(bin_bits),
        32 // bin_bits, bin_bits)
    out_ref[...] = outlier
    sign_words_ref[...] = _pack_block(neg.astype(jnp.uint32), 32, 1)


# -------------------------------------------------- fused unpack+dequant --

def _abs_unpack_kernel(words_ref, eb_ref, y_ref, *, eb_floor, bin_bits):
    dt = y_ref.dtype
    _, eb2 = _abs_step(_eb_row(eb_ref), dt, eb_floor)   # matches the encoder
    bins = _unpack_block(words_ref[...], 32 // bin_bits, bin_bits)
    y_ref[...] = bins.astype(dt) * eb2           # exact


def _rel_unpack_kernel(words_ref, sign_words_ref, y_ref, *, log_step, mb,
                       bias, bin_bits):
    dt = y_ref.dtype
    bins = _unpack_block(words_ref[...], 32 // bin_bits, bin_bits)
    sign = _unpack_block(sign_words_ref[...], 32, 1, signed=False) != 0
    mag = _pow2approx(bins.astype(dt) * jnp.asarray(log_step, dt), mb, bias)
    y_ref[...] = jnp.where(sign, -mag, mag)


# -------------------------------------------------------------- wrappers --

def _use_interpret() -> bool:
    """The one backend switch for every kernel entry point: compiled by
    Mosaic on TPU, the Pallas interpreter elsewhere (CPU tests)."""
    return jax.default_backend() != "tpu"


def _check_rows(rows):
    # the sign plane packs 32 rows/word, so rows must cover whole words for
    # every output plane
    assert rows % 32 == 0, f"rows must be a multiple of 32, got {rows}"


def quantize_pack_abs_pallas(x2d, eb, *, maxbin, tighten, eb_floor, bin_bits,
                             rows=DEFAULT_ROWS, interpret):
    """x2d: [R_total, 128], R_total % rows == 0.  eb: [1, 1].
    Returns (words [R_total/vpw, 128] uint32, outlier [R_total, 128])."""
    r_total, lanes = x2d.shape
    _check_rows(rows)
    assert lanes == LANES and r_total % rows == 0
    vpw = 32 // bin_bits
    grid = (r_total // rows,)
    body = functools.partial(_abs_pack_kernel, maxbin=maxbin, tighten=tighten,
                             eb_floor=eb_floor, bin_bits=bin_bits)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),      # eb broadcast
        ],
        out_specs=[
            pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
        ],
        interpret=interpret,
        name="quantize_pack_abs",
    )(x2d, eb)


def quantize_pack_rel_pallas(x2d, *, cfg, rows=DEFAULT_ROWS, interpret):
    """Returns (words [R/vpw, 128], outlier [R, 128], sign_words [R/32, 128])."""
    import numpy as np

    r_total, lanes = x2d.shape
    _check_rows(rows)
    assert lanes == LANES and r_total % rows == 0
    dt = x2d.dtype
    vpw = 32 // cfg.bin_bits
    eb_, log_step, inv_log_step = cfg.rel_constants()
    mb, emask, bias = (23, 0xFF, 127) if dt == jnp.float32 else (52, 0x7FF, 1023)
    body = functools.partial(
        _rel_pack_kernel, maxbin=cfg.maxbin, tighten=cfg.tighten, eb=float(eb_),
        log_step=float(log_step), inv_log_step=float(inv_log_step),
        screen=float(cfg.rel_screen_threshold()), tiny=float(np.finfo(dt).tiny),
        mb=mb, emask=emask, bias=bias, bin_bits=cfg.bin_bits)
    return pl.pallas_call(
        body,
        grid=(r_total // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows // 32, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
            jax.ShapeDtypeStruct((r_total // 32, LANES), jnp.uint32),
        ],
        interpret=interpret,
        name="quantize_pack_rel",
    )(x2d)


def unpack_dequant_abs_pallas(words2d, eb, *, dtype, eb_floor, bin_bits,
                              rows=DEFAULT_ROWS, interpret):
    """words2d: [W_total, 128] with W_total % (rows/vpw) == 0.
    Returns recon [W_total*vpw, 128] (outliers NOT restored — the caller
    scatters the capped exact table afterwards)."""
    w_total, lanes = words2d.shape
    _check_rows(rows)
    vpw = 32 // bin_bits
    wrows = rows // vpw
    assert lanes == LANES and w_total % wrows == 0
    return pl.pallas_call(
        functools.partial(_abs_unpack_kernel, eb_floor=eb_floor,
                          bin_bits=bin_bits),
        grid=(w_total // wrows,),
        in_specs=[
            pl.BlockSpec((wrows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w_total * vpw, LANES), dtype),
        interpret=interpret,
        name="unpack_dequant_abs",
    )(words2d, eb)


def unpack_dequant_rel_pallas(words2d, sign_words2d, *, cfg, dtype,
                              rows=DEFAULT_ROWS, interpret):
    w_total, lanes = words2d.shape
    _check_rows(rows)
    vpw = 32 // cfg.bin_bits
    wrows = rows // vpw
    assert lanes == LANES and w_total % wrows == 0
    _, log_step, _ = cfg.rel_constants()
    mb, bias = (23, 127) if jnp.dtype(dtype) == jnp.float32 else (52, 1023)
    return pl.pallas_call(
        functools.partial(_rel_unpack_kernel, log_step=float(log_step),
                          mb=mb, bias=bias, bin_bits=cfg.bin_bits),
        grid=(w_total // wrows,),
        in_specs=[
            pl.BlockSpec((wrows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows // 32, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w_total * vpw, LANES), dtype),
        interpret=interpret,
        name="unpack_dequant_rel",
    )(words2d, sign_words2d)


# ------------------------------------------------------ jit'd public API --

def _tile_zero(x, rows):
    """Flatten + zero-pad to [R_total, 128].  Zero pad (not ops._tile's 1.0)
    so pad bins/signs are 0 for both ABS and REL — bit-matching the
    reference, which packs zero-padded bin streams."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    block = rows * LANES
    pad = (-n) % block
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), n


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "interpret"))
def encode_packed(x, cfg: QuantizerConfig, eb=None, *, rows=DEFAULT_ROWS,
                  interpret=None) -> C.EncodedPacked:
    """Fused-kernel twin of core.codec.encode_packed (bit-exact)."""
    interpret = _use_interpret() if interpret is None else interpret
    with obs.scope("codec.quantize_pack"):
        flat = x.reshape(-1)
        n = flat.shape[0]
        k = cfg.outlier_cap(n)
        if cfg.mode == "noa":
            # NOA = ABS with eb from the global value range (needs the full
            # tensor -> computed here, quantized by the ABS kernel)
            finite = jnp.isfinite(flat)
            import numpy as np
            big = jnp.asarray(np.finfo(flat.dtype).max, flat.dtype)
            hi = jnp.max(jnp.where(finite, flat, -big))
            lo = jnp.min(jnp.where(finite, flat, big))
            eb = jnp.asarray(cfg.error_bound, flat.dtype) * (hi - lo)

        x2d, _ = _tile_zero(flat, rows)
        sign_words = None
        if cfg.mode == "rel":
            words2d, out2d, sw2d = quantize_pack_rel_pallas(
                x2d, cfg=cfg, rows=rows, interpret=interpret)
            sign_words = sw2d.reshape(-1)[:C.packed_word_count(n, 1)]
        else:
            eb_arr = jnp.full((1, 1), cfg.error_bound if eb is None else eb,
                              x2d.dtype)
            words2d, out2d = quantize_pack_abs_pallas(
                x2d, eb_arr, maxbin=cfg.maxbin, tighten=cfg.tighten,
                eb_floor=cfg.eb_floor, bin_bits=cfg.bin_bits, rows=rows,
                interpret=interpret)
        # pad words beyond the reference tile count are all-zero (zero pad
        # in, zero bins out) — truncate to the canonical wire length
        words = words2d.reshape(-1)[:C.packed_word_count(n, cfg.bin_bits)]
        outlier = out2d.reshape(-1)[:n]

    n_out, idx, payload = C.compact_outliers(flat, outlier, k)
    return C.EncodedPacked(words, idx.astype(jnp.int32),
                           payload.astype(jnp.uint32), n_out, n_out > k,
                           sign_words,
                           None if eb is None else jnp.asarray(eb, flat.dtype))


def _tile_words(words, wrows):
    n_w = words.shape[0]
    pad = (-n_w) % (wrows * LANES)
    return jnp.pad(words, (0, pad)).reshape(-1, LANES)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n", "shape", "dtype", "rows",
                                    "interpret"))
def decode_packed(enc: C.EncodedPacked, cfg: QuantizerConfig, n=None,
                  shape=None, dtype=None, *, rows=DEFAULT_ROWS,
                  interpret=None):
    """Fused-kernel twin of core.codec.decode_packed (bit-exact)."""
    import numpy as np
    interpret = _use_interpret() if interpret is None else interpret
    if n is None:
        if shape is None:
            raise ValueError("decode_packed needs n or shape")
        n = int(np.prod(shape))
    with obs.scope("codec.dequantize"):
        return _decode_packed(enc, cfg, n, shape, dtype, rows, interpret)


def _decode_packed(enc, cfg, n, shape, dtype, rows, interpret):
    dt = jnp.dtype(dtype or cfg.dtype)
    vpw = 32 // cfg.bin_bits
    if cfg.mode == "rel":
        w2d = _tile_words(enc.words, rows // vpw)
        # the sign plane must cover exactly the element rows the bin words
        # cover (both planes' pad bits are zero, so pad/truncate is exact)
        blocks = w2d.shape[0] // (rows // vpw)
        s_need = blocks * (rows // 32) * LANES
        sw = enc.sign_words
        sw = jnp.pad(sw, (0, max(0, s_need - sw.shape[0])))[:s_need]
        y2d = unpack_dequant_rel_pallas(w2d, sw.reshape(-1, LANES), cfg=cfg,
                                        dtype=dt, rows=rows,
                                        interpret=interpret)
    else:
        w2d = _tile_words(enc.words, rows // vpw)
        eb_arr = jnp.full((1, 1),
                          cfg.error_bound if enc.eb is None else enc.eb, dt)
        y2d = unpack_dequant_abs_pallas(w2d, eb_arr, dtype=dt,
                                        eb_floor=cfg.eb_floor,
                                        bin_bits=cfg.bin_bits, rows=rows,
                                        interpret=interpret)
    recon = y2d.reshape(-1)[:n]
    vals = lax.bitcast_convert_type(enc.out_payload.astype(jnp.int32), dt)
    recon = recon.at[enc.out_idx].set(vals, mode="drop")
    return recon.reshape(shape) if shape is not None else recon
