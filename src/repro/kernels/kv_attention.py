"""Pallas TPU kernel: flash-decode attention over an int8-quantized KV cache
with inline guaranteed-error-bound outlier corrections.

This is the paper's technique fused into the serving hot loop: the cache
stays compressed in HBM (int8 bins + per-page pow2 scale + exact-outlier
side table), and ONE kernel streams it page by page, dequantizing in VMEM
and applying outlier corrections before the MXU dot — the attention never
sees a value outside the guaranteed bound.

TPU adaptation (DESIGN.md §3): a GPU codec would scatter outlier fixes into
shared memory; TPUs have no efficient scatter, so corrections are applied
as DENSE ONE-HOT EINSUMS — `corr = onehot_t(idx)ᵀ @ (val ⊙ onehot_d(idx))`,
[P,cap] @ [cap,D] on the MXU.  Because the encoder zeroes outlier bins, the
correction is a pure add of the exact value (bit-exact restore).

Memory/roofline: per (b, g, page) step the kernel reads P*D int8 (K) + P*D
int8 (V) + 2*cap*8 B sides vs P*D*2*2 B for a bf16 cache — 4x less HBM
traffic for the bandwidth-bound decode attention.  Arithmetic per step:
2*Hg*P*D (scores) + 2*Hg*P*D (acc) + 2*2*cap*P*D (corrections) MACs; at
cap=8 corrections are ~2x the attention dots for Hg=8 — still far below
the bandwidth roofline (decode attention AI ~ Hg flops/byte << ridge).

Layout: grid (B, G, S/P); flash accumulation in VMEM scratch across the
innermost (page) grid axis.  Blocks: K/V page [P=128, D=128] int8 (16 KiB),
q [Hg<=16, 128], acc f32 [Hg, 128] — comfortably < 1 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pack import _use_interpret

NEG_BIG = -1e30


def _kernel(len_ref, q_ref, kb_ref, keb_ref, ki_ref, kv_ref_,
            vb_ref, veb_ref, vi_ref, vv_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page, softmax_scale, cap):
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                   # [Hg, D]
    hg, d = q.shape

    def dequant_corrected(bins_ref, eb_ref, idx_ref, val_ref):
        x = bins_ref[0, 0].astype(jnp.float32) * eb_ref[0, 0, 0]  # exact mul
        idx = idx_ref[0, 0, 0]                        # [cap], -1 = empty
        val = val_ref[0, 0, 0]                        # [cap] exact values
        t = idx // d
        dd = jnp.where(idx >= 0, idx % d, -1)
        # dense one-hot correction: encoder zeroed outlier bins, so adding
        # the exact value restores it bit-for-bit
        oh_t = (jax.lax.broadcasted_iota(jnp.int32, (cap, page), 1)
                == t[:, None]).astype(jnp.float32)
        oh_d = (jax.lax.broadcasted_iota(jnp.int32, (cap, d), 1)
                == dd[:, None]).astype(jnp.float32)
        corr = jnp.dot(oh_t.T, val[:, None] * oh_d,
                       preferred_element_type=jnp.float32)
        return x + corr                               # [P, D]

    k = dequant_corrected(kb_ref, keb_ref, ki_ref, kv_ref_)
    v = dequant_corrected(vb_ref, veb_ref, vi_ref, vv_ref)

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    scores = scores * jnp.float32(softmax_scale)      # [Hg, P]
    t0 = p * page
    valid = (t0 + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
             < len_ref[0])
    scores = jnp.where(valid, scores, NEG_BIG)

    m_prev = m_ref[...]                               # [Hg, 1]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(scores - m_new)                    # [Hg, P]
    l_ref[...] = l_ref[...] * alpha + pexp.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pexp, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def kv_decode_attention(q, kq, vq, lengths, *, page=128, cap=8,
                        interpret=None):
    """q: [B, G, Hg, D]; kq/vq: compression.kv.QuantizedKV with
    bins [B, G, S, D]; lengths: int32 [B].  Returns [B, G, Hg, D].
    interpret=None compiles on TPU and interprets elsewhere."""
    interpret = _use_interpret() if interpret is None else interpret
    b, g, hg, d = q.shape
    s = kq.bins.shape[2]
    assert s % page == 0
    n_pages = s // page
    scale = 1.0 / (d ** 0.5)

    grid = (b, g, n_pages)
    body = functools.partial(_kernel, page=page, softmax_scale=scale, cap=cap)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j, p: (i,)),                 # lengths
            pl.BlockSpec((1, 1, hg, d), lambda i, j, p: (i, j, 0, 0)),  # q
            pl.BlockSpec((1, 1, page, d), lambda i, j, p: (i, j, p, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, p: (i, j, p)),       # k eb2
            pl.BlockSpec((1, 1, 1, cap), lambda i, j, p: (i, j, p, 0)),
            pl.BlockSpec((1, 1, 1, cap), lambda i, j, p: (i, j, p, 0)),
            pl.BlockSpec((1, 1, page, d), lambda i, j, p: (i, j, p, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, p: (i, j, p)),       # v eb2
            pl.BlockSpec((1, 1, 1, cap), lambda i, j, p: (i, j, p, 0)),
            pl.BlockSpec((1, 1, 1, cap), lambda i, j, p: (i, j, p, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hg, d), lambda i, j, p: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, hg, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hg, d), jnp.float32),    # acc
            pltpu.VMEM((hg, 1), jnp.float32),    # running max m
            pltpu.VMEM((hg, 1), jnp.float32),    # running denom l
        ],
        interpret=interpret,
        name="kv_decode_attention",
    )(lengths, q, kq.bins, kq.eb2, kq.out_idx, kq.out_val,
      vq.bins, vq.eb2, vq.out_idx, vq.out_val)
