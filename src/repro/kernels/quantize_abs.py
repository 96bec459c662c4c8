"""Pallas TPU kernel: fused ABS quantize + double-check + outlier flag.

One pass over HBM: reads x, writes (bins, outlier, recon).  The math is the
bit-exact twin of core.quantizer.quantize_abs (the oracle); the kernel
exists because on TPU the quantize step of gradient/KV compression runs on
the critical path between the backward pass and the inter-pod collective.

Design notes (TPU adaptation of the paper's GPU codec, DESIGN.md §3):
  * pure VPU elementwise work at ~1 flop/byte -> memory-bound; the paper's
    "double-checking is throughput-free" claim holds structurally because
    the extra compare/select ops ride along under the same HBM stream.
  * block shape (ROWS, 128): lane-dim 128 matches the VPU; ROWS=256 gives
    128 KiB per f32 buffer, 4 buffers ~= 0.5 MiB VMEM of ~16 MiB -> plenty
    of headroom for double buffering.
  * eb arrives as a (1,1) operand (not a compile-time constant) so the SAME
    compiled kernel serves per-tensor traced bounds (NOA-style gradient
    compression) and static config bounds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_ROWS = 256
LANES = 128


def _kernel(x_ref, eb_ref, bins_ref, out_ref, recon_ref, *, maxbin, tighten,
            eb_floor):
    x = x_ref[...]
    dt = x.dtype
    eb_in = eb_ref[0, 0]
    degenerate = ~(eb_in >= eb_floor)            # FTZ guard (see core.config)
    eb = jnp.maximum(eb_in, eb_floor)
    mant_mask = (1 << 23) - 1 if dt == jnp.float32 else (1 << 52) - 1
    int_t = jnp.int32 if dt == jnp.float32 else jnp.int64
    # pow2-floored step: bin*eb2 and x*inv_eb2 become exact -> FMA-immune
    eb2 = lax.bitcast_convert_type(
        lax.bitcast_convert_type(jnp.asarray(2.0, dt) * eb, int_t) & ~mant_mask,
        dt)
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

    bins_ref[...] = jnp.where(outlier, 0, bin_i)
    out_ref[...] = outlier
    recon_ref[...] = jnp.where(outlier, jnp.zeros((), dt), recon)


def quantize_abs_pallas(x2d: jnp.ndarray, eb: jnp.ndarray, *, maxbin: int,
                        tighten: float, eb_floor: float,
                        rows: int = DEFAULT_ROWS, interpret: bool):
    """x2d: [R_total, 128] with R_total % rows == 0.  eb: [1, 1]."""
    r_total, lanes = x2d.shape
    assert lanes == LANES and r_total % rows == 0
    grid = (r_total // rows,)
    dt = x2d.dtype
    body = functools.partial(_kernel, maxbin=maxbin, tighten=tighten,
                             eb_floor=eb_floor)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),      # eb broadcast
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_total, LANES), jnp.int32),
            jax.ShapeDtypeStruct((r_total, LANES), jnp.bool_),
            jax.ShapeDtypeStruct((r_total, LANES), dt),
        ],
        interpret=interpret,
        name="quantize_abs",
    )(x2d, eb)
