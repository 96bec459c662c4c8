"""Pallas TPU kernel: fused dequantize + bit-exact outlier restore.

Decoder side of the ABS/REL codec over the DENSE layout: recon = bin * eb2
(or sign * pow2approx(bin * w)), then outlier positions are overwritten by
bitcasting the lossless payload back to float.  Elementwise, memory-bound;
the fusion saves one full HBM round-trip vs dequantize-then-select.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .quantize_abs import DEFAULT_ROWS, LANES
from .quantize_rel import _pow2approx


def _abs_kernel(bins_ref, payload_ref, out_mask_ref, eb_ref, y_ref, *,
                eb_floor):
    dt = y_ref.dtype
    eb = jnp.maximum(eb_ref[0, 0], jnp.asarray(eb_floor, dt))
    mant_mask = (1 << 23) - 1 if dt == jnp.float32 else (1 << 52) - 1
    int_t = jnp.int32 if dt == jnp.float32 else jnp.int64
    eb2 = lax.bitcast_convert_type(
        lax.bitcast_convert_type(jnp.asarray(2.0, dt) * eb, int_t) & ~mant_mask,
        dt)                                      # pow2 step, matches encoder
    recon = bins_ref[...].astype(dt) * eb2       # exact
    exact = lax.bitcast_convert_type(payload_ref[...], dt)
    y_ref[...] = jnp.where(out_mask_ref[...], exact, recon)


def _rel_kernel(bins_ref, payload_ref, out_mask_ref, sign_ref, y_ref, *,
                log_step, mb, bias):
    dt = y_ref.dtype
    mag = _pow2approx(bins_ref[...].astype(dt) * jnp.asarray(log_step, dt),
                      mb, bias)
    recon = jnp.where(sign_ref[...], -mag, mag)
    exact = lax.bitcast_convert_type(payload_ref[...], dt)
    y_ref[...] = jnp.where(out_mask_ref[...], exact, recon)


def dequantize_abs_pallas(bins2d, payload2d, outlier2d, eb, *, dtype,
                          eb_floor, rows=DEFAULT_ROWS, interpret):
    r_total, lanes = bins2d.shape
    assert lanes == LANES and r_total % rows == 0
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_abs_kernel, eb_floor=eb_floor),
        grid=(r_total // rows,),
        in_specs=[spec, spec, spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r_total, LANES), dtype),
        interpret=interpret,
        name="dequantize_abs",
    )(bins2d, payload2d, outlier2d, eb)


def dequantize_rel_pallas(bins2d, payload2d, outlier2d, sign2d, *, cfg,
                          dtype, rows=DEFAULT_ROWS, interpret):
    r_total, lanes = bins2d.shape
    assert lanes == LANES and r_total % rows == 0
    _, log_step, _ = cfg.rel_constants()
    mb, bias = (23, 127) if jnp.dtype(dtype) == jnp.float32 else (52, 1023)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_rel_kernel, log_step=float(log_step), mb=mb,
                          bias=bias),
        grid=(r_total // rows,),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r_total, LANES), dtype),
        interpret=interpret,
        name="dequantize_rel",
    )(bins2d, payload2d, outlier2d, sign2d)
