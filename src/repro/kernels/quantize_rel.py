"""Pallas TPU kernel: fused REL quantize with the paper's bit-manipulation
log2/pow2 INSIDE the kernel.

The parity-safe transcendentals are bitcast + integer ops — exactly the
operations the TPU VPU does natively, so the paper's CPU/GPU trick becomes
a zero-transcendental TPU kernel (no lookup-table exp/log units touched,
fully deterministic).  Math is the bit-exact twin of
core.quantizer.quantize_rel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .quantize_abs import DEFAULT_ROWS, LANES


def _log2approx(x, mb, emask, bias):
    int_t = jnp.int32 if x.dtype == jnp.float32 else jnp.int64
    orig_i = lax.bitcast_convert_type(x, int_t)
    expo = (orig_i >> mb) & emask
    frac_i = (bias << mb) | (orig_i & ((1 << mb) - 1))
    frac_f = lax.bitcast_convert_type(frac_i.astype(int_t), x.dtype)
    return frac_f + (expo - (bias + 1)).astype(x.dtype)


def _pow2approx(l, mb, bias):
    int_t = jnp.int32 if l.dtype == jnp.float32 else jnp.int64
    biased = l + bias        # FMA-immune: l is an exact pow2-step product
    expo = biased.astype(int_t)
    frac_f = biased - (expo - 1).astype(l.dtype)
    frac_i = lax.bitcast_convert_type(frac_f, int_t)
    exp_i = (expo << mb) | (frac_i & ((1 << mb) - 1))
    return lax.bitcast_convert_type(exp_i, l.dtype)


def _kernel(x_ref, bins_ref, out_ref, recon_ref, sign_ref, *, maxbin, tighten,
            eb, log_step, inv_log_step, screen, tiny, mb, emask, bias):
    x = x_ref[...]
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

    bins_ref[...] = jnp.where(outlier, 0, bin_i)
    out_ref[...] = outlier
    recon_ref[...] = jnp.where(outlier, jnp.zeros((), dt), recon)
    sign_ref[...] = neg


def quantize_rel_pallas(x2d: jnp.ndarray, *, cfg, rows: int = DEFAULT_ROWS,
                        interpret: bool):
    """x2d: [R_total, 128] with R_total % rows == 0."""
    import numpy as np

    r_total, lanes = x2d.shape
    assert lanes == LANES and r_total % rows == 0
    dt = x2d.dtype
    eb_, log_step, inv_log_step = cfg.rel_constants()
    mb, emask, bias = (23, 0xFF, 127) if dt == jnp.float32 else (52, 0x7FF, 1023)
    body = functools.partial(
        _kernel, maxbin=cfg.maxbin, tighten=cfg.tighten, eb=float(eb_),
        log_step=float(log_step), inv_log_step=float(inv_log_step),
        screen=float(cfg.rel_screen_threshold()), tiny=float(np.finfo(dt).tiny),
        mb=mb, emask=emask, bias=bias)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        body,
        grid=(r_total // rows,),
        in_specs=[spec],
        out_specs=[spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((r_total, LANES), jnp.int32),
            jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
            jax.ShapeDtypeStruct((r_total, LANES), dt),
            jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
        ],
        interpret=interpret,
        name="quantize_rel",
    )(x2d)
