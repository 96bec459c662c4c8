"""jit'd public wrappers around the Pallas kernels.

Handles layout (flatten -> pad -> [rows,128] tiles -> unpad), backend
selection (interpret=True off-TPU so the same code validates on CPU), and
dtype plumbing.  API mirrors core.quantizer so callers can switch between
the pure-jnp path and the kernel path with one flag.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import QuantizerConfig
from repro.core.bitops import float_to_bits
from repro.core.quantizer import Quantized

from . import dequantize as _dq
from . import quantize_abs as _qa
from . import quantize_rel as _qr
from .pack import _use_interpret

LANES = _qa.LANES


def _tile(x: jnp.ndarray, rows: int, pad_value=1.0):
    """Flatten + pad to a [R_total, 128] tile grid; returns (tiled, n).

    Default pad 1.0 quantizes cleanly for any eb; padding is stripped after
    the call either way."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    block = rows * LANES
    pad = (-n) % block
    flat = jnp.pad(flat, (0, pad), constant_values=pad_value)
    return flat.reshape(-1, LANES), n


def _untile(y2d: jnp.ndarray, n: int, shape):
    return y2d.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "interpret"))
def quantize_abs(x, cfg: QuantizerConfig, eb=None, *, rows=_qa.DEFAULT_ROWS,
                 interpret=None) -> Quantized:
    interpret = _use_interpret() if interpret is None else interpret
    x2d, n = _tile(x, rows)
    eb_arr = jnp.full((1, 1), cfg.error_bound if eb is None else eb, x2d.dtype)
    bins, outlier, recon = _qa.quantize_abs_pallas(
        x2d, eb_arr, maxbin=cfg.maxbin, tighten=cfg.tighten,
        eb_floor=cfg.eb_floor, rows=rows, interpret=interpret)
    return Quantized(_untile(bins, n, x.shape), _untile(outlier, n, x.shape),
                     _untile(recon, n, x.shape))


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "interpret"))
def quantize_rel(x, cfg: QuantizerConfig, *, rows=_qa.DEFAULT_ROWS,
                 interpret=None) -> Quantized:
    interpret = _use_interpret() if interpret is None else interpret
    x2d, n = _tile(x, rows)
    bins, outlier, recon, sign = _qr.quantize_rel_pallas(
        x2d, cfg=cfg, rows=rows, interpret=interpret)
    return Quantized(_untile(bins, n, x.shape), _untile(outlier, n, x.shape),
                     _untile(recon, n, x.shape), _untile(sign, n, x.shape))


@functools.partial(jax.jit, static_argnames=("cfg", "rows", "interpret"))
def dequantize_abs(bins, payload_bits, outlier, cfg: QuantizerConfig,
                   eb=None, *, rows=_qa.DEFAULT_ROWS, interpret=None):
    interpret = _use_interpret() if interpret is None else interpret
    dt = jnp.dtype(cfg.dtype)
    shape = bins.shape
    b2d, n = _tile(bins.astype(jnp.int32), rows, pad_value=0)
    p2d, _ = _tile(payload_bits.astype(jnp.int32), rows, pad_value=0)
    o2d, _ = _tile(outlier, rows, pad_value=False)
    eb_arr = jnp.full((1, 1), cfg.error_bound if eb is None else eb, dt)
    y2d = _dq.dequantize_abs_pallas(b2d, p2d, o2d, eb_arr, dtype=dt,
                                    eb_floor=cfg.eb_floor, rows=rows,
                                    interpret=interpret)
    return _untile(y2d, n, shape)
