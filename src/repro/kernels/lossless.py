"""Pallas TPU kernels: device-side lossless stage over the packed words.

The paper's LC pipeline wins its compression ratio in the lossless coder
that FOLLOWS quantize+pack — the stage GPU compressors keep resident
(cuSZ's Huffman over quantization codes, arXiv 2007.09625; FZ-GPU's
bitshuffle + zero-suppression fused after quantization, arXiv 2304.12557).
These kernels are the TPU-shaped equivalent of that stage for the chunked
zero/narrow scheme of DESIGN.md §6 (reference: core.codec.encode_words_lc):

  * a chunk is LC_CHUNK = 512 words = 4 sublane rows x 128 lanes, so the
    per-chunk reduction (max word) and the width-narrowing are pure
    sublane operations on the VPU — narrowing IS the same _pack_block
    shift/or the quantize+pack kernels already use, at chunk granularity;
  * the fused path (`encode_packed_lc`) extends the quantize+pack kernel
    of kernels/pack.py with the chunk scan, so x is read ONCE from HBM
    and what comes back is already the narrowed chunk image + the 2-bit
    header codes — the lossless stage rides the existing memory stream;
  * the variable-length compaction and its inverse gather are XLA ops
    shared verbatim with the reference (core.codec.lc_compact_payload /
    lc_gather_chunks, whose row gather the reference's one-pass decode
    lc_expand_payload also runs), which is what makes kernel and
    reference bit-identical by construction.  They move whole 128-word
    rows from one per-chunk row offset: moved one word per index, they
    were not cheap — on a v5e over a 512^3 field the word gather took
    54% of the codec's device time, about 26 ns a word, and the encode's
    compaction with the outlier table 44% (PERF.md §5).

Everything validates in interpret mode on CPU (tests/test_lossless.py),
compiles for a TPU v5e (tests/test_tpu_compile.py) and runs bit-identical
to the reference on one (chip_smoke.py); PERF.md has its speed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core import QuantizerConfig
from repro.core import codec as C

from .pack import (LANES, _abs_quantize_block, _eb_row, _narrow_mask,
                   _pack_block, _rel_quantize_block, _tile_words,
                   _unpack_block, _use_interpret)
from .quantize_abs import DEFAULT_ROWS

CHUNK_ROWS = C.LC_CHUNK // LANES        # word rows per chunk (= 4)


# ------------------------------------------------------------- in-kernel --

def _chunk_select_block(words, stage):
    """words: uint32[wrows, 128], wrows % CHUNK_ROWS == 0.  Returns
    (sel uint32[wrows, 128], codes uint32[wrows/CHUNK_ROWS, 128]): each
    chunk's narrowed image left-aligned in its own rows (zero-padded), and
    its 2-bit width code broadcast across lanes."""
    wrows = words.shape[0]
    nck = wrows // CHUNK_ROWS
    grp = words.reshape(nck, CHUNK_ROWS, LANES)
    # per-word width code, reduced in int32 (Mosaic has no unsigned
    # reductions): a chunk's code is its widest word's
    if stage == "zero":
        wcode = jnp.where(grp != 0, 3, 0)
    else:
        wcode = ((grp != 0).astype(jnp.int32) + (grp >= (1 << 8))
                 + (grp >= (1 << 16)))
    codes = jnp.max(jnp.max(wcode.astype(jnp.int32), axis=2, keepdims=True),
                    axis=1, keepdims=True)                  # [nck, 1, 1]
    # CHUNK_ROWS == vpw at width 8 and 2*vpw at width 16, so the whole-block
    # _pack_block groups exactly one chunk per candidate row group — same
    # grouping as the reference's full-stream pack_words.
    cand1 = _pack_block(words, 4, 8).reshape(nck, 1, LANES)
    cand2 = _pack_block(words, 2, 16).reshape(nck, 2, LANES)
    z1 = jnp.zeros((nck, CHUNK_ROWS - 1, LANES), jnp.uint32)
    z2 = jnp.zeros((nck, CHUNK_ROWS - 2, LANES), jnp.uint32)
    pad1 = jnp.concatenate([cand1, z1], axis=1)
    pad2 = jnp.concatenate([cand2, z2], axis=1)
    sel = jnp.where(codes == 1, pad1,
                    jnp.where(codes == 2, pad2,
                              jnp.where(codes == 3, grp, jnp.uint32(0))))
    codes_b = jnp.broadcast_to(codes.astype(jnp.uint32)[:, 0, :],
                               (nck, LANES))
    return sel.reshape(wrows, LANES), codes_b


def _chunk_expand_block(padded, codes_b):
    """Inverse of _chunk_select_block: padded uint32[wrows, 128] +
    codes uint32[wrows/CHUNK_ROWS, 128] -> words uint32[wrows, 128]."""
    wrows = padded.shape[0]
    nck = wrows // CHUNK_ROWS
    grp = padded.reshape(nck, CHUNK_ROWS, LANES)
    exp1 = _unpack_block(grp[:, 0, :], 4, 8,
                         signed=False).reshape(nck, CHUNK_ROWS, LANES)
    exp2 = _unpack_block(grp[:, :2, :].reshape(nck * 2, LANES), 2, 16,
                         signed=False).reshape(nck, CHUNK_ROWS, LANES)
    cb = codes_b[:, :1].reshape(nck, 1, 1)     # lanes carry identical codes
    words = jnp.where(cb == 1, exp1,
                      jnp.where(cb == 2, exp2,
                                jnp.where(cb == 3, grp, jnp.uint32(0))))
    return words.reshape(wrows, LANES)


def _lc_select_kernel(words_ref, sel_ref, codes_ref, *, stage):
    sel, codes = _chunk_select_block(words_ref[...], stage)
    sel_ref[...] = sel
    codes_ref[...] = codes


def _lc_expand_kernel(padded_ref, codes_ref, words_ref):
    words_ref[...] = _chunk_expand_block(padded_ref[...], codes_ref[...])


def _abs_pack_lc_kernel(x_ref, eb_ref, words_ref, out_ref, sel_ref,
                        codes_ref, *, maxbin, tighten, eb_floor, bin_bits,
                        stage):
    """Quantize + pack + chunk-narrow in ONE pass over x (DESIGN.md §3/§6:
    elementwise codec work is memory-bound, so the lossless scan rides the
    same HBM stream the pack already pays for)."""
    bins, outlier = _abs_quantize_block(x_ref[...], _eb_row(eb_ref),
                                        maxbin=maxbin, tighten=tighten,
                                        eb_floor=eb_floor)
    words = _pack_block(bins.astype(jnp.uint32) & _narrow_mask(bin_bits),
                        32 // bin_bits, bin_bits)
    words_ref[...] = words
    out_ref[...] = outlier
    sel, codes = _chunk_select_block(words, stage)
    sel_ref[...] = sel
    codes_ref[...] = codes


def _rel_pack_lc_kernel(x_ref, words_ref, out_ref, sign_words_ref, sel_ref,
                        codes_ref, *, maxbin, tighten, eb, log_step,
                        inv_log_step, screen, tiny, mb, emask, bias,
                        bin_bits, stage):
    bins, outlier, neg = _rel_quantize_block(
        x_ref[...], maxbin=maxbin, tighten=tighten, eb=eb, log_step=log_step,
        inv_log_step=inv_log_step, screen=screen, tiny=tiny, mb=mb,
        emask=emask, bias=bias)
    words = _pack_block(bins.astype(jnp.uint32) & _narrow_mask(bin_bits),
                        32 // bin_bits, bin_bits)
    words_ref[...] = words
    out_ref[...] = outlier
    sign_words_ref[...] = _pack_block(neg.astype(jnp.uint32), 32, 1)
    sel, codes = _chunk_select_block(words, stage)
    sel_ref[...] = sel
    codes_ref[...] = codes


# -------------------------------------------------------------- wrappers --

def _check_wrows(wrows):
    assert wrows % CHUNK_ROWS == 0, \
        f"word rows per block must cover whole chunks, got {wrows}"


def chunk_select_pallas(words2d, stage, *, wrows=DEFAULT_ROWS,
                        interpret):
    """words2d: uint32[W_total, 128], W_total % wrows == 0.  Returns
    (sel [W_total, 128], codes [W_total/CHUNK_ROWS, 128])."""
    w_total, lanes = words2d.shape
    _check_wrows(wrows)
    assert lanes == LANES and w_total % wrows == 0
    return pl.pallas_call(
        functools.partial(_lc_select_kernel, stage=stage),
        grid=(w_total // wrows,),
        in_specs=[pl.BlockSpec((wrows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((wrows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((wrows // CHUNK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_total, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((w_total // CHUNK_ROWS, LANES), jnp.uint32),
        ],
        interpret=interpret,
        name="lc_chunk_select",
    )(words2d)


def chunk_expand_pallas(padded2d, codes2d, *, wrows=DEFAULT_ROWS,
                        interpret):
    w_total, lanes = padded2d.shape
    _check_wrows(wrows)
    assert lanes == LANES and w_total % wrows == 0
    return pl.pallas_call(
        _lc_expand_kernel,
        grid=(w_total // wrows,),
        in_specs=[
            pl.BlockSpec((wrows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((wrows // CHUNK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((wrows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w_total, LANES), jnp.uint32),
        interpret=interpret,
        name="lc_chunk_expand",
    )(padded2d, codes2d)


def _finish_encode(sel2d, codes2d, n_words):
    """Shared tail: truncate the kernel's (block-padded) chunk stream to
    the reference chunk count, then run the SAME compaction as the
    reference — pad chunks beyond n_words are all-zero by the zero-pad
    invariant, so truncation is exact."""
    n_chunks = C.lc_chunk_count(n_words)
    with obs.scope("codec.compact"):
        codes = codes2d.reshape(-1, LANES)[:n_chunks, 0].astype(jnp.int32)
        sel = sel2d.reshape(-1)[:n_chunks * C.LC_CHUNK].reshape(
            n_chunks, C.LC_CHUNK)
        payload, plen = C.lc_compact_payload(sel, codes)
        return C.pack_words(codes, 2), payload, plen


# ------------------------------------------------------ jit'd public API --

@functools.partial(jax.jit, static_argnames=("stage", "wrows", "interpret"))
def encode_words_lc(words, stage="narrow", *, wrows=DEFAULT_ROWS,
                    interpret=None):
    """Pallas twin of core.codec.encode_words_lc (bit-exact): lossless-code
    an existing packed word stream."""
    interpret = _use_interpret() if interpret is None else interpret
    n_words = words.shape[0]
    w2d = _tile_words(words, wrows)
    sel2d, codes2d = chunk_select_pallas(w2d, stage, wrows=wrows,
                                         interpret=interpret)
    return _finish_encode(sel2d, codes2d, n_words)


@functools.partial(jax.jit,
                   static_argnames=("n_words", "wrows", "interpret"))
def decode_words_lc(header_words, payload, n_words, *, wrows=DEFAULT_ROWS,
                    interpret=None):
    """Pallas twin of core.codec.decode_words_lc (bit-exact)."""
    interpret = _use_interpret() if interpret is None else interpret
    n_chunks = C.lc_chunk_count(n_words)
    codes = C.unpack_words(header_words, n_chunks, 2,
                           signed=False).astype(jnp.int32)
    padded = C.lc_gather_chunks(payload, codes)            # XLA row gather
    p2d = _tile_words(padded.reshape(-1), wrows)
    blocks = p2d.shape[0] // wrows
    c_need = blocks * (wrows // CHUNK_ROWS)
    cpad = jnp.pad(codes.astype(jnp.uint32), (0, c_need - n_chunks))
    c2d = jnp.broadcast_to(cpad[:, None], (c_need, LANES))
    words2d = chunk_expand_pallas(p2d, c2d, wrows=wrows, interpret=interpret)
    return words2d.reshape(-1)[:n_words]


def encode_lossless(enc: C.EncodedPacked, stage: str = "narrow", *,
                    wrows=DEFAULT_ROWS, interpret=None) -> C.EncodedLC:
    """Pallas twin of core.codec.encode_lossless for an EncodedPacked."""
    hw, payload, plen = encode_words_lc(enc.words, stage, wrows=wrows,
                                        interpret=interpret)
    return C.EncodedLC(hw, payload, plen, enc.out_idx, enc.out_payload,
                       enc.n_outliers, enc.overflow, enc.sign_words, enc.eb)


def decode_lossless(lc: C.EncodedLC, n_words: int, *, wrows=DEFAULT_ROWS,
                    interpret=None) -> C.EncodedPacked:
    words = decode_words_lc(lc.header_words, lc.payload, n_words,
                            wrows=wrows, interpret=interpret)
    return C.EncodedPacked(words, lc.out_idx, lc.out_payload, lc.n_outliers,
                           lc.overflow, lc.sign_words, lc.eb)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "stage", "rows", "interpret"))
def encode_packed_lc(x, cfg: QuantizerConfig, eb=None, stage="narrow", *,
                     rows=DEFAULT_ROWS, interpret=None) -> C.EncodedLC:
    """FUSED quantize + pack + lossless: one HBM pass over x emits packed
    words, the outlier mask, AND the narrowed chunk image + header codes.
    Bit-exact twin of core.codec.encode_lossless(encode_packed(x))."""
    import numpy as np

    interpret = _use_interpret() if interpret is None else interpret
    vpw = 32 // cfg.bin_bits
    assert rows % 32 == 0 and (rows // vpw) % CHUNK_ROWS == 0, rows
    with obs.scope("codec.quantize_pack"):
        flat = x.reshape(-1)
        n = flat.shape[0]
        k = cfg.outlier_cap(n)
        if cfg.mode == "noa":
            finite = jnp.isfinite(flat)
            big = jnp.asarray(np.finfo(flat.dtype).max, flat.dtype)
            hi = jnp.max(jnp.where(finite, flat, -big))
            lo = jnp.min(jnp.where(finite, flat, big))
            eb = jnp.asarray(cfg.error_bound, flat.dtype) * (hi - lo)

        block = rows * LANES
        pad = (-n) % block
        x2d = jnp.pad(flat, (0, pad)).reshape(-1, LANES)
        r_total = x2d.shape[0]
        grid = (r_total // rows,)
        sign_words = None
        if cfg.mode == "rel":
            eb_, log_step, inv_log_step = cfg.rel_constants()
            mb, emask, bias = ((23, 0xFF, 127) if x2d.dtype == jnp.float32
                               else (52, 0x7FF, 1023))
            body = functools.partial(
                _rel_pack_lc_kernel, maxbin=cfg.maxbin, tighten=cfg.tighten,
                eb=float(eb_), log_step=float(log_step),
                inv_log_step=float(inv_log_step),
                screen=float(cfg.rel_screen_threshold()),
                tiny=float(np.finfo(x2d.dtype).tiny), mb=mb, emask=emask,
                bias=bias, bin_bits=cfg.bin_bits, stage=stage)
            words2d, out2d, sw2d, sel2d, codes2d = pl.pallas_call(
                body,
                grid=grid,
                in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
                out_specs=[
                    pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows // 32, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows // vpw // CHUNK_ROWS, LANES),
                                 lambda i: (i, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
                    jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
                    jax.ShapeDtypeStruct((r_total // 32, LANES), jnp.uint32),
                    jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
                    jax.ShapeDtypeStruct((r_total // vpw // CHUNK_ROWS, LANES),
                                         jnp.uint32),
                ],
                interpret=interpret,
                name="quantize_pack_lc_rel",
            )(x2d)
            sign_words = sw2d.reshape(-1)[:C.packed_word_count(n, 1)]
        else:
            eb_arr = jnp.full((1, 1), cfg.error_bound if eb is None else eb,
                              x2d.dtype)
            body = functools.partial(_abs_pack_lc_kernel, maxbin=cfg.maxbin,
                                     tighten=cfg.tighten, eb_floor=cfg.eb_floor,
                                     bin_bits=cfg.bin_bits, stage=stage)
            words2d, out2d, sel2d, codes2d = pl.pallas_call(
                body,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((1, 1), lambda i: (0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows // vpw, LANES), lambda i: (i, 0)),
                    pl.BlockSpec((rows // vpw // CHUNK_ROWS, LANES),
                                 lambda i: (i, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
                    jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
                    jax.ShapeDtypeStruct((r_total // vpw, LANES), jnp.uint32),
                    jax.ShapeDtypeStruct((r_total // vpw // CHUNK_ROWS, LANES),
                                         jnp.uint32),
                ],
                interpret=interpret,
                name="quantize_pack_lc_abs",
            )(x2d, eb_arr)

    n_words = C.packed_word_count(n, cfg.bin_bits)
    n_out, idx, payload_out = C.compact_outliers(
        flat, out2d.reshape(-1)[:n], k)
    hw, payload, plen = _finish_encode(sel2d, codes2d, n_words)
    return C.EncodedLC(hw, payload, plen, idx.astype(jnp.int32),
                       payload_out.astype(jnp.uint32), n_out, n_out > k,
                       sign_words,
                       None if eb is None else jnp.asarray(eb, flat.dtype))
