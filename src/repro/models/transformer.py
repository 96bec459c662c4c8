"""Decoder-only transformer stack: dense (internlm2 / stablelm / chatglm3 /
deepseek / chameleon), MoE (olmoe / qwen3-moe), and the jamba hybrid
(Mamba+attention 1:7 with MoE every 2nd layer).

Layout principles:
  * per-layer params are STACKED on a leading 'layers' axis and the stack
    runs under jax.lax.scan -> HLO is O(1) in depth (95-layer deepseek
    compiles in seconds on the 512-device dry-run).
  * each scan body is jax.checkpoint'd (full remat baseline; policy is a
    §Perf lever) so train memory is one layer's activations.
  * attention is the pure-JAX flash pattern (O(S) memory), GQA KV repeat
    for train/prefill, grouped-einsum for decode (no repeat at 512k).
  * MoE goes through shard_map expert parallelism (models/moe.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import layers as L
from . import mamba as M
from .moe import moe_ffn
from .params import ParamSpec

DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

def _attn_specs(cfg: ArchConfig, lead=()):
    d, hd = cfg.d_model, cfg.head_dim
    h, g = cfg.n_heads, cfg.n_kv_heads
    ax = tuple(None for _ in lead)
    return {
        "ln1": ParamSpec(lead + (d,), jnp.float32, ax + (None,), -1.0),
        "wq": ParamSpec(lead + (d, h * hd), DTYPE, ax + ("embed", "heads")),
        "wkv": ParamSpec(lead + (d, 2 * g * hd), DTYPE, ax + ("embed", "heads")),
        "wo": ParamSpec(lead + (h * hd, d), DTYPE, ax + ("heads", "embed")),
    }


def _ffn_specs(cfg: ArchConfig, lead=()):
    d, f = cfg.d_model, cfg.d_ff
    ax = tuple(None for _ in lead)
    s = {
        "ln2": ParamSpec(lead + (d,), jnp.float32, ax + (None,), -1.0),
        "w1": ParamSpec(lead + (d, f), DTYPE, ax + ("embed", "mlp")),
        "w2": ParamSpec(lead + (f, d), DTYPE, ax + ("mlp", "embed")),
    }
    if cfg.act == "swiglu":
        s["w3"] = ParamSpec(lead + (d, f), DTYPE, ax + ("embed", "mlp"))
    return s


def _moe_specs(cfg: ArchConfig, lead=()):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ax = tuple(None for _ in lead)
    return {
        "ln2": ParamSpec(lead + (d,), jnp.float32, ax + (None,), -1.0),
        "router": ParamSpec(lead + (d, e), jnp.float32, ax + ("embed", None)),
        "w1": ParamSpec(lead + (e, d, f), DTYPE,
                        ax + ("experts", "embed", None)),
        "w3": ParamSpec(lead + (e, d, f), DTYPE,
                        ax + ("experts", "embed", None)),
        "w2": ParamSpec(lead + (e, f, d), DTYPE,
                        ax + ("experts", None, "embed")),
    }


_MAMBA_AXES = {
    # explicit FSDP ('embed'->data) + TP ('mlp'->model) per projection;
    # a divisibility matcher missed (d, 4d) shapes and left jamba's
    # in_proj master copies REPLICATED (63 GiB/device, measured)
    "in_proj": ("embed", "mlp"),
    "conv_w": (None, "mlp"),
    "a_log": ("mlp", None),
    "d_skip": ("mlp",),
    "bc_proj": ("mlp", None),
    "dt_proj": ("embed", "mlp"),
    "dt_bias": ("mlp",),
    "out_proj": ("mlp", "embed"),
}


def _mamba_specs(cfg: ArchConfig, lead=()):
    ax = tuple(None for _ in lead)
    out = {"ln1": ParamSpec(lead + (cfg.d_model,), jnp.float32,
                            ax + (None,), -1.0)}
    for name, (shape, dt) in M.mamba_params_shape(
            cfg.d_model, cfg.ssm_state, DTYPE).items():
        scale = 0.02 if name not in ("a_log", "d_skip", "dt_bias") else -1.0
        out[name] = ParamSpec(lead + shape, dt, ax + _MAMBA_AXES[name],
                              scale)
    return out


def param_specs(cfg: ArchConfig):
    v, d, l_ = cfg.vocab, cfg.d_model, cfg.n_layers
    specs: dict = {
        "emb": ParamSpec((cfg.padded_vocab, d), DTYPE, ("vocab", "embed")),
        "final_norm": ParamSpec((d,), jnp.float32, (None,), -1.0),
    }
    if cfg.family in ("dense", "vlm"):
        specs["layers"] = {**_attn_specs(cfg, (l_,)), **_ffn_specs(cfg, (l_,))}
    elif cfg.family == "moe":
        specs["layers"] = {**_attn_specs(cfg, (l_,)), **_moe_specs(cfg, (l_,))}
    elif cfg.family == "hybrid":
        n_per = cfg.attn_period                  # blocks per period
        periods = l_ // n_per
        n_mamba = n_per - 1
        n_moe = n_per // cfg.moe_every
        n_dense = n_per - n_moe
        specs["periods"] = {
            "mamba": _mamba_specs(cfg, (periods, n_mamba)),
            "attn": _attn_specs(cfg, (periods,)),
            "dense_ffn": _ffn_specs(cfg, (periods, n_dense)),
            "moe_ffn": _moe_specs(cfg, (periods, n_moe)),
        }
    else:
        raise ValueError(cfg.family)
    return specs


# --------------------------------------------------------------------------
# blocks (global math; scan over stacked layer params)
# --------------------------------------------------------------------------

def _attention(cfg: ArchConfig, p, x, positions, ctx=L.NULL_CTX, *,
               causal=True):
    b, s, d = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hx = ctx(L.rms_norm(x, p["ln1"], cfg.norm_eps), 'dp', None, None)
    q = ctx((hx @ p["wq"]).reshape(b, s, h, hd), 'dp', None, 'model', None)
    kv = (hx @ p["wkv"]).reshape(b, s, 2, g, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    cos, sin = L.rope_tables(positions, hd if cfg.rope == "full" else hd // 2)
    q = L.apply_rope(q, cos, sin, cfg.rope)
    k = L.apply_rope(k, cos, sin, cfg.rope)
    # un-shard S BEFORE the GQA broadcast: feeding an S-sharded KV into
    # repeat_kv makes GSPMD emit a pathological resharding copy that
    # crashes XLA's AllReducePromotion pass (seen on jamba prefill)
    k = ctx(k, 'dp', None, None, None)
    v = ctx(v, 'dp', None, None, None)
    k = ctx(L.repeat_kv(k, cfg.group_size), 'dp', None, 'model', None)
    v = ctx(L.repeat_kv(v, cfg.group_size), 'dp', None, 'model', None)
    o = L.flash_attention(q, k, v, causal=causal, ctx=ctx)
    # NOTE: a full Megatron-SP residual (S-sharded between sublayers) was
    # measured in §Perf A-2 (memory −8%, temp −37%) but destabilizes
    # XLA:CPU's SPMD partitioner on some archs (upstream crash) — the
    # boundary-seam variant below is the stable default.
    return x + ctx(o.reshape(b, s, h * hd) @ p["wo"], 'dp', None, None)


def _ffn_block(cfg: ArchConfig, p, x, mesh, moe_data_axes=None,
               ctx=L.NULL_CTX):
    hx = ctx(L.rms_norm(x, p["ln2"], cfg.norm_eps), 'dp', None, None)
    if "router" in p:
        if moe_data_axes is None:
            moe_data_axes = ("pod", "data") if (
                mesh is not None and "pod" in mesh.axis_names) else ("data",)
        y, aux = moe_ffn(hx, p["router"], p["w1"], p["w3"], p["w2"],
                         top_k=cfg.moe_top_k, mesh=mesh,
                         data_axes=moe_data_axes, act=cfg.act)
        return x + y, aux
    y = L.ffn(hx, p["w1"], p.get("w3"), p["w2"], cfg.act, ctx=ctx)
    return x + y, jnp.float32(0)


def _layer_group(n_layers: int, max_group: int = 8) -> int:
    """Largest divisor of n_layers <= max_group (hierarchical remat)."""
    for g in range(min(max_group, n_layers), 0, -1):
        if n_layers % g == 0:
            return g
    return 1


def scan_grouped_remat(body, carry, stacked, n: int, max_group: int = 8):
    """Two-level remat: outer scan over layer GROUPS with only group
    boundaries saved; each group's backward replays its inner scan.  Also
    defeats an XLA pessimization where the full per-layer bf16 carry stack
    was hoisted to one f32 buffer (measured: 20 GiB on stablelm-3b
    train_4k before this change)."""
    g = _layer_group(n, max_group)
    grouped = jax.tree.map(lambda t: t.reshape(n // g, g, *t.shape[1:]),
                           stacked)

    body_ckpt = jax.checkpoint(body)   # inner: attention/ffn rematted

    @jax.checkpoint
    def group_body(c, gp):
        c, _ = jax.lax.scan(body_ckpt, c, gp)
        return c, None

    carry, _ = jax.lax.scan(group_body, carry, grouped)
    return carry


def _dense_or_moe_stack(cfg: ArchConfig, params, x, positions, mesh,
                        remat=True, moe_data_axes=None):
    # inside the pod-manual compressed-DP region, constraints must not
    # name the manual 'pod' axis -> dp follows moe_data_axes
    ctx = L.ShardCtx(mesh, dp=moe_data_axes)

    def body(carry, lp):
        h, aux = carry
        h = ctx(h, 'dp', None, None)
        h = _attention(cfg, lp, h, positions, ctx)
        h, a = _ffn_block(cfg, lp, h, mesh, moe_data_axes, ctx)
        # sequence-parallel seam: the layer boundary (what remat SAVES) is
        # S-sharded over 'model' -> boundary-save memory /16
        h = ctx(h, 'dp', 'model', None)
        return (h, aux + a), None

    if not remat:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                                   params["layers"])
        return x, aux
    x, aux = scan_grouped_remat(body, (x, jnp.float32(0)),
                                params["layers"], cfg.n_layers)
    return x, aux


def _hybrid_stack(cfg: ArchConfig, params, x, positions, mesh, remat=True,
                  moe_data_axes=None):
    n_per = cfg.attn_period
    ctx = L.ShardCtx(mesh, dp=moe_data_axes)

    def period(carry, pp):
        h, aux = carry
        h = ctx(h, 'dp', None, None)
        _seam = True
        mamba_i = dense_i = moe_i = 0
        for blk in range(n_per):
            is_attn = blk == n_per - 1
            if is_attn:
                ap = pp["attn"]
                h = _attention(cfg, ap, h, positions, ctx)
            else:
                mp = jax.tree.map(lambda t: t[mamba_i], pp["mamba"])
                hn = L.rms_norm(h, mp["ln1"], cfg.norm_eps)
                y, _ = M.mamba_block(mp, hn, ctx=ctx)
                h = h + y
                mamba_i += 1
            if (blk % cfg.moe_every) == cfg.moe_every - 1:
                fp = jax.tree.map(lambda t: t[moe_i], pp["moe_ffn"])
                moe_i += 1
            else:
                fp = jax.tree.map(lambda t: t[dense_i], pp["dense_ffn"])
                dense_i += 1
            h, a = _ffn_block(cfg, fp, h, mesh, moe_data_axes, ctx)
            aux = aux + a
        h = ctx(h, 'dp', 'model', None)   # sequence-parallel boundary save
        return (h, aux), None

    periods = cfg.n_layers // n_per
    if not remat:
        (x, aux), _ = jax.lax.scan(period, (x, jnp.float32(0)),
                                   params["periods"])
        return x, aux
    # a period (8 blocks) is already a big remat unit: group=1
    x, aux = scan_grouped_remat(period, (x, jnp.float32(0)),
                                params["periods"], periods, max_group=1)
    return x, aux


def forward(cfg: ArchConfig, params, tokens, mesh=None, remat=True,
            moe_data_axes=None):
    """tokens: int32 [B, S] -> logits [B, S, V], aux loss.  Activations
    and logits take the embedding's dtype (bf16 as specified), so an f32
    reference is this same code over an f32 embedding."""
    x = params["emb"][tokens]
    positions = jnp.arange(tokens.shape[1])[None, :]
    if cfg.family == "hybrid":
        x, aux = _hybrid_stack(cfg, params, x, positions, mesh, remat,
                               moe_data_axes)
    else:
        x, aux = _dense_or_moe_stack(cfg, params, x, positions, mesh, remat,
                                     moe_data_axes)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    ctx = L.ShardCtx(mesh, dp=moe_data_axes)
    logits = ctx(x @ params["emb"].T, 'dp', None, 'model')
    return logits, aux


def loss_fn(cfg: ArchConfig, params, tokens, labels, mesh=None, remat=True,
            aux_weight=0.01):
    logits, aux = forward(cfg, params, tokens, mesh, remat)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None],
                             axis=-1)[..., 0]
    ce = jnp.mean(lse - ll)
    return ce + aux_weight * aux, (ce, aux)
