"""Plain reference of a dense decoder-only transformer of the LLaMA family
(DeepSeek LLM is one): RMSNorm, rotary embeddings over the whole head,
grouped-query causal attention, SwiGLU, an output head tied to the
embedding.  Straightforward `jax.numpy` in float32 at `HIGHEST` matmul
precision, with no cache, no batching and no kernels; it imports nothing
of the program under test.

It runs layer by layer and in blocks of query rows, so one sequence of a
few thousand tokens at full width fits beside the weights on one chip.
Logits are never kept: each block of them is reduced at once to what the
check compares (the gap of a given token below the best logit, or the
index of the best logit).

`precision="fp8"` is the control: every weight matrix and every matmul
input is rounded to float8 (e4m3, one scale per tensor) before the f32
product, the nearest precision below the bf16 the model is served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512


def _fp8(t):
    """Round to float8 e4m3 with one scale per tensor, back in f32."""
    s = jnp.max(jnp.abs(t)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if low:
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate the two halves of every head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv            # [S, hd/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _page_quant(t, eb_rel: float, page: int = 128):
    """Round t [S, G, hd] to bins of 2 eb, eb = eb_rel x the largest |t|
    of each page of positions and head."""
    s_len, g, hd = t.shape
    p = t.reshape(s_len // page, page, g, hd)
    eb = eb_rel * jnp.max(jnp.abs(p), axis=(1, 3), keepdims=True)
    eb = jnp.where(eb > 0, eb, 1.0)
    return (jnp.round(p / (2 * eb)) * (2 * eb)).reshape(t.shape)


def _layer(m, lp, x, low: bool, kv_eb: float = 0.0):
    """One decoder layer over a whole sequence x [S, D] (f32)."""
    s_len = x.shape[0]
    h, g, hd = m["heads"], m["kv_heads"], m["head_dim"]
    pos = jnp.arange(s_len)
    hx = _rms_norm(x, lp["ln1"].astype(jnp.float32), m["eps"])
    q = _rope(_mm(hx, lp["wq"], low).reshape(s_len, h, hd), pos, m["theta"])
    kv = _mm(hx, lp["wkv"], low).reshape(s_len, 2, g, hd)
    k = _rope(kv[:, 0], pos, m["theta"])
    v = kv[:, 1]
    if low:
        k, v = _fp8(k), _fp8(v)
    if kv_eb:
        k, v = _page_quant(k, kv_eb), _page_quant(v, kv_eb)
    qg = q.reshape(s_len, g, h // g, hd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * BLOCK, BLOCK, 0)
        sc = jnp.einsum("sgqd,tgd->gqst", qb, k, precision=HIGHEST)
        sc = sc / jnp.sqrt(jnp.float32(hd))
        qpos = i * BLOCK + jnp.arange(BLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if low:
            p = _fp8(p)
        o = jnp.einsum("gqst,tgd->sgqd", p, v, precision=HIGHEST)
        return o.reshape(BLOCK, h * hd)

    o = jax.lax.map(block, jnp.arange(s_len // BLOCK)).reshape(s_len, h * hd)
    x = x + _mm(o, lp["wo"], low)
    hx = _rms_norm(x, lp["ln2"].astype(jnp.float32), m["eps"])
    y = jax.nn.silu(_mm(hx, lp["w1"], low)) * _mm(hx, lp["w3"], low)
    return x + _mm(y, lp["w2"], low)


def _hidden(m, params, tokens, low: bool, kv_eb: float = 0.0):
    """Final normed hidden states [S, D] of one sequence."""
    x = params["emb"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(m, lp, x, low, kv_eb), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms_norm(x, params["final_norm"].astype(jnp.float32), m["eps"])


def _logit_blocks(m, params, x, low: bool, fn):
    """fn(logits block [BLOCK, V], block index) over the whole sequence,
    stacked; only the vocab's first `vocab` rows are logits."""
    emb = params["emb"][: m["vocab"]]

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * BLOCK, BLOCK, 0)
        return fn(_mm(xb, emb.T, low), i)

    return jax.lax.map(block, jnp.arange(x.shape[0] // BLOCK))


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(mt, params, tokens, targets):
    m = dict(mt)
    x = _hidden(m, params, tokens, False)

    def fn(lg, i):
        t = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK, 0)
        at = jnp.take_along_axis(lg, jnp.maximum(t, 0)[:, None], 1)[:, 0]
        return jnp.max(lg, -1) - at

    return _logit_blocks(m, params, x, False, fn).reshape(-1)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _argmax_low(mt, params, tokens, low, kv_eb):
    m = dict(mt)
    x = _hidden(m, params, tokens, low, kv_eb)
    return _logit_blocks(m, params, x, low,
                         lambda lg, i: jnp.argmax(lg, -1)).reshape(-1)


def model(config: dict) -> tuple:
    """The static sizes the reference reads from a configuration file."""
    c = config
    return tuple(sorted(dict(
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], eps=float(c["rms_norm_eps"]),
        theta=float(c["rope_theta"]), vocab=c["vocab_size"]).items()))


def _pad(seq, length):
    import numpy as np
    out = np.zeros((length,), np.int32)
    out[: len(seq)] = seq
    return jnp.asarray(out)


def token_gaps(config: dict, params, tokens, targets, length: int):
    """Per position i < len(targets): the best f32 logit after tokens[:i+1]
    minus the logit of targets[i].  `length` (a multiple of 512) is the
    padded sequence length; the model is causal, so padding never reaches
    a compared position."""
    import numpy as np
    n = len(targets)
    t = np.full((length,), -1, np.int32)
    t[:n] = targets
    out = _gaps(model(config), params, _pad(tokens, length), jnp.asarray(t))
    return np.asarray(out)[:n]


def low_argmax(config: dict, params, tokens, n: int, length: int, *,
               fp8: bool = True, kv_eb: float = 0.0):
    """A lower-precision reference's best token after tokens[:i+1], for
    i < n: fp8 weights and activations, and/or K and V rounded per page to
    bins of 2 eb (eb = kv_eb x the page's largest |value|)."""
    import numpy as np
    out = _argmax_low(model(config), params, _pad(tokens, length), fp8,
                      float(kv_eb))
    return np.asarray(out)[:n]
