"""Decode-step (serving) paths with KV caches — raw bf16 or
guaranteed-error-bounded quantized (the paper's technique in the serving
hot loop).

Quantized mode cache layout per layer (compression/kv.py):
    bins   int8 [L, B, G, S, hd]     4x smaller than bf16 K+V
    eb2    f32  [L, B, G, nP]        per-page pow2 step
    out_idx/out_val [L, B, G, nP, cap]  exact outliers (bit-exact restore)
    hot    bf16 [L, B, page, G, hd]  write buffer for the open page
When the open page fills ((pos+1) % page == 0) it is quantized in-step via
lax.cond.  The XLA decode path dequantizes history explicitly; on real TPU
the fused Pallas kernel (kernels/kv_attention.py) streams int8 directly.

PREFILL→DECODE DISAGGREGATION (DESIGN.md §8): a prefill host builds the
QuantCache and hands it to a decode host.  KV pages cross that link ONLY
as `PackedKV` wires moved by `Transport.send_pages` — never as raw f32/
bf16 planes: `pack_cache` converts a QuantCache to the `PackedCache`
wire (closed pages bit-packed per page, optionally chunk-coded; the open
hot page rides raw because it is not quantized yet), `transfer_cache`
moves it across a mesh axis, `unpack_cache` restores the decode layout
bit-exactly.  The §1 guarantee survives the transfer verbatim because
pack/unpack are exact inverses (tests/test_transport.py pins both the
bit-exactness and the page error bound after transfer).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import QuantizerConfig
from repro.core.transport import TRANSPORT, Transport
from repro.compression import kv as KVC
from . import layers as L
from . import mamba as M
from .transformer import DTYPE


class RawCache(NamedTuple):
    k: jnp.ndarray            # [L, B, S, G, hd]
    v: jnp.ndarray


class QuantCache(NamedTuple):
    k: KVC.QuantizedKV        # bins [L, B, G, S, hd], ...
    v: KVC.QuantizedKV
    hot_k: jnp.ndarray        # [L, B, page, G, hd]
    hot_v: jnp.ndarray


PAGE = 128
CAP = 8


def make_raw_cache(cfg: ArchConfig, batch, seq, n_layers=None):
    l_ = n_layers if n_layers is not None else cfg.n_layers
    shape = (l_, batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return RawCache(jnp.zeros(shape, DTYPE), jnp.zeros(shape, DTYPE))


def make_quant_cache(cfg: ArchConfig, batch, seq, n_layers=None):
    l_ = n_layers if n_layers is not None else cfg.n_layers
    g, hd = cfg.n_kv_heads, cfg.head_dim
    np_ = seq // PAGE

    def one():
        return KVC.QuantizedKV(
            bins=jnp.zeros((l_, batch, g, seq, hd), jnp.int8),
            eb2=jnp.zeros((l_, batch, g, np_), jnp.float32),
            out_idx=jnp.full((l_, batch, g, np_, CAP), -1, jnp.int32),
            out_val=jnp.zeros((l_, batch, g, np_, CAP), jnp.float32),
            overflow=jnp.zeros((l_, batch, g, np_), bool),
        )

    hot = jnp.zeros((l_, batch, PAGE, g, hd), DTYPE)
    return QuantCache(one(), one(), hot, hot)


class PackedCache(NamedTuple):
    """The prefill→decode transfer wire for a QuantCache: closed pages as
    per-page `PackedKV` wires, the open hot page raw (it is not quantized
    yet — at PAGE=128 it amortizes away at production context lengths).
    `core.transport.wire_bytes` accounts it field by field."""
    k: KVC.PackedKV
    v: KVC.PackedKV
    hot_k: jnp.ndarray
    hot_v: jnp.ndarray


def pack_cache(cache: QuantCache, *, stages=()) -> PackedCache:
    """QuantCache -> transfer wire.  `stages` is a per-page chain spec in
    the two-domain grammar — or "auto"/"auto:SET", which hands the
    per-page choice to the §11 selector (`pack_kv` resolves it; the wire
    carries one chain-id byte per page, so decode needs no side
    channel): optional leading pred stages (DESIGN.md §9 —
    "kvdelta|zero|narrow" runs the previous-token delta on each page's
    bin plane before coding; the prediction is decode-side and page-local
    so migrated pages stay bit-exact) then word stages ("zero", "narrow",
    "shuffle|narrow", ...) — zero chunks drop the unwritten tail of a
    mid-decode cache."""
    return PackedCache(KVC.pack_kv(cache.k, page=PAGE, stages=stages),
                       KVC.pack_kv(cache.v, page=PAGE, stages=stages),
                       cache.hot_k, cache.hot_v)


def unpack_cache(wire: PackedCache) -> QuantCache:
    """Exact inverse of pack_cache: restore the int8 decode layout."""
    return QuantCache(KVC.unpack_kv(wire.k, page=PAGE),
                      KVC.unpack_kv(wire.v, page=PAGE),
                      wire.hot_k, wire.hot_v)


def transfer_cache(cache: QuantCache, src: int, dst: int, axis: str, *,
                   stages=(), transport: Transport | None = None):
    """Move a serving cache from mesh rank `src` (prefill) to `dst`
    (decode) along `axis` — call inside shard_map.  KV pages cross the
    link only as PackedKV wires through `Transport.send_pages`
    (DESIGN.md §8); rank `dst` returns the bit-identical QuantCache,
    other ranks return zeros (ppermute semantics)."""
    tp = TRANSPORT if transport is None else transport
    return unpack_cache(tp.send_pages(pack_cache(cache, stages=stages),
                                      src, dst, axis))


def _project_token(cfg: ArchConfig, p, x, pos):
    """x: [B, 1, D] -> q [B,1,H,hd], k/v [B,1,G,hd] with rope at pos."""
    b = x.shape[0]
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hx = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = (hx @ p["wq"]).reshape(b, 1, h, hd)
    kv = (hx @ p["wkv"]).reshape(b, 1, 2, g, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    positions = jnp.full((1, 1), pos, jnp.int32)
    cos, sin = L.rope_tables(positions,
                             hd if cfg.rope == "full" else hd // 2)
    q = L.apply_rope(q, cos, sin, cfg.rope)
    k = L.apply_rope(k, cos, sin, cfg.rope)
    return q, k, v


def _attn_decode_raw(cfg: ArchConfig, p, x, kc, vc, pos):
    """kc/vc: [B, S, G, hd] one layer's cache."""
    b = x.shape[0]
    q, k, v = _project_token(cfg, p, x, pos)
    kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, pos, 0, 0))
    lengths = jnp.full((b,), pos + 1, jnp.int32)
    o = L.decode_attention(q, kc, vc, lengths)
    h, hd = cfg.n_heads, cfg.head_dim
    return x + o.reshape(b, 1, h * hd) @ p["wo"], kc, vc


def _quantize_page(qkv: KVC.QuantizedKV, hot, page_idx, kv_cfg):
    """Quantize the filled hot page [B, page, G, hd] into history slot."""
    b, page, g, hd = hot.shape
    x = hot.transpose(0, 2, 1, 3).astype(jnp.float32)        # [B, G, P, hd]
    q = KVC.quantize_kv(x.reshape(b, g, page, hd), kv_cfg, page=page,
                        cap=CAP)
    bins = jax.lax.dynamic_update_slice(
        qkv.bins, q.bins, (0, 0, page_idx * page, 0))
    upd = lambda dst, src: jax.lax.dynamic_update_slice(
        dst, src, (0, 0, page_idx) + (0,) * (src.ndim - 3))
    return KVC.QuantizedKV(bins, upd(qkv.eb2, q.eb2),
                           upd(qkv.out_idx, q.out_idx),
                           upd(qkv.out_val, q.out_val),
                           upd(qkv.overflow, q.overflow))


def _attn_decode_quant(cfg: ArchConfig, p, x, qk, qv, hot_k, hot_v, pos,
                       kv_cfg):
    b = x.shape[0]
    g, hd = cfg.n_kv_heads, cfg.head_dim
    s = qk.bins.shape[3]
    q, k, v = _project_token(cfg, p, x, pos)

    in_page = pos % PAGE
    hot_k = jax.lax.dynamic_update_slice(
        hot_k, k.astype(hot_k.dtype), (0, in_page, 0, 0))
    hot_v = jax.lax.dynamic_update_slice(
        hot_v, v.astype(hot_v.dtype), (0, in_page, 0, 0))

    # attention = closed (quantized) pages + open (hot) page
    with obs.scope("serve.kv_dequant"):
        hist_k = KVC.dequantize_kv(qk, page=PAGE, dtype=DTYPE)  # [B,G,S,hd]
        hist_v = KVC.dequantize_kv(qv, page=PAGE, dtype=DTYPE)
    page_start = (pos // PAGE) * PAGE
    hist_len = jnp.full((b,), page_start, jnp.int32)
    hot_len = jnp.full((b,), in_page + 1, jnp.int32)

    o_hist, l_hist, m_hist = _partial_attn(q, hist_k.transpose(0, 2, 1, 3),
                                           hist_v.transpose(0, 2, 1, 3),
                                           hist_len)
    o_hot, l_hot, m_hot = _partial_attn(q, hot_k, hot_v, hot_len)
    m = jnp.maximum(m_hist, m_hot)
    w1 = l_hist * jnp.exp(m_hist - m)
    w2 = l_hot * jnp.exp(m_hot - m)
    o = (o_hist * w1[..., None] + o_hot * w2[..., None]) / (
        w1 + w2)[..., None]
    o = o.reshape(b, 1, cfg.n_heads * hd).astype(x.dtype)

    # close the page when it fills
    kv_c = kv_cfg
    with obs.scope("serve.page_close"):
        qk, qv, hot_k, hot_v = jax.lax.cond(
            (pos + 1) % PAGE == 0,
            lambda a: (_quantize_page(a[0], a[2], pos // PAGE, kv_c),
                       _quantize_page(a[1], a[3], pos // PAGE, kv_c),
                       jnp.zeros_like(a[2]), jnp.zeros_like(a[3])),
            lambda a: a,
            (qk, qv, hot_k, hot_v))
    return x + o @ p["wo"], qk, qv, hot_k, hot_v


def _partial_attn(q, kc, vc, lengths):
    """Un-normalized attention piece for two-segment combination.
    q [B,1,H,hd]; kc/vc [B,T,G,hd]; returns (acc/l, l, m) per [B,G*gs]."""
    b, _, h, hd = q.shape
    t, g = kc.shape[1], kc.shape[2]
    gs = h // g
    qg = q.reshape(b, g, gs, hd)
    scores = jnp.einsum("bgqd,bsgd->bgqs", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / (hd ** 0.5)
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, L.NEG_BIG)
    m = scores.max(-1)                                       # [B,G,gs]
    p = jnp.exp(scores - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bgqs,bsgd->bgqd", p, vc.astype(jnp.float32))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, g * gs, hd), l.reshape(b, g * gs), m.reshape(b, g * gs)


def _ffn_decode(cfg: ArchConfig, p, x, mesh):
    from .transformer import _ffn_block
    y, _ = _ffn_block(cfg, p, x, mesh)
    return y


def serve_step(cfg: ArchConfig, params, cache, tokens, pos, mesh=None,
               kv_cfg: QuantizerConfig | None = None):
    """One decode step.  tokens: int32 [B, 1]; pos: scalar int32 (aligned
    batch).  Returns (logits [B, V] f32, new_cache).

    Device scopes (DESIGN.md §14): `serve.embed`, `serve.layers` (the
    layer scan: per-layer slices and stacked cache writes), inside it
    `serve.attn` (with `serve.kv_dequant`, `serve.page_close`) and
    `serve.ffn`, then `serve.lm_head`."""
    with obs.scope("serve.embed"):
        x = params["emb"][tokens].astype(DTYPE)

    if cfg.family == "hybrid":
        with obs.scope("serve.layers"):
            x, cache = _serve_hybrid(cfg, params, cache, x, pos, mesh)
    elif isinstance(cache, QuantCache):
        assert kv_cfg is not None

        def body(h, xs):
            lp, qk, qv, hk, hv = xs      # scan slices the leading L axis
            with obs.scope("serve.attn"):
                h, qk, qv, hk, hv = _attn_decode_quant(
                    cfg, lp, h, qk, qv, hk, hv, pos, kv_cfg)
            with obs.scope("serve.ffn"):
                h = _ffn_decode(cfg, lp, h, mesh)
            return h, (qk, qv, hk, hv)

        with obs.scope("serve.layers"):
            x, (qk, qv, hk, hv) = jax.lax.scan(
                body, x, (params["layers"], cache.k, cache.v,
                          cache.hot_k, cache.hot_v))
        cache = QuantCache(qk, qv, hk, hv)
    else:
        def body(h, xs):
            lp, kc, vc = xs
            with obs.scope("serve.attn"):
                h, kc, vc = _attn_decode_raw(cfg, lp, h, kc, vc, pos)
            with obs.scope("serve.ffn"):
                h = _ffn_decode(cfg, lp, h, mesh)
            return h, (kc, vc)

        with obs.scope("serve.layers"):
            x, (kc, vc) = jax.lax.scan(body, x,
                                       (params["layers"], cache.k, cache.v))
        cache = RawCache(kc, vc)

    with obs.scope("serve.lm_head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ params["emb"].T.astype(DTYPE))[:, 0].astype(
            jnp.float32)
    return logits, cache


def _serve_hybrid(cfg: ArchConfig, params, cache, x, pos, mesh):
    """jamba: cache = (RawCache for the per-period attn layers,
    (conv_tail [P, n_mamba, B, K-1, Di], ssm_h [P, n_mamba, B, Di, N]))."""
    attn_cache, (tails, hs) = cache
    n_per = cfg.attn_period

    def period(h, xs):
        pp, kc, vc, tail_p, h_p = xs
        mamba_i = dense_i = moe_i = 0
        new_tails, new_hs = [], []
        for blk in range(n_per):
            if blk == n_per - 1:
                ap = pp["attn"]
                with obs.scope("serve.attn"):
                    h, kc, vc = _attn_decode_raw(cfg, ap, h, kc, vc, pos)
            else:
                mp = jax.tree.map(lambda t: t[mamba_i], pp["mamba"])
                hn = L.rms_norm(h, mp["ln1"], cfg.norm_eps)
                y, (tail, hh) = M.mamba_block(
                    mp, hn, state=(tail_p[mamba_i], h_p[mamba_i]))
                h = h + y
                new_tails.append(tail)
                new_hs.append(hh)
                mamba_i += 1
            if (blk % cfg.moe_every) == cfg.moe_every - 1:
                fp = jax.tree.map(lambda t: t[moe_i], pp["moe_ffn"])
                moe_i += 1
            else:
                fp = jax.tree.map(lambda t: t[dense_i], pp["dense_ffn"])
                dense_i += 1
            with obs.scope("serve.ffn"):
                h = _ffn_decode(cfg, fp, h, mesh)
        return h, (kc, vc, jnp.stack(new_tails), jnp.stack(new_hs))

    x, (kc, vc, tails, hs) = jax.lax.scan(
        period, x, (params["periods"], attn_cache.k, attn_cache.v,
                    tails, hs))
    return x, (RawCache(kc, vc), (tails, hs))
