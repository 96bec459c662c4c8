"""Compressed data-parallel gradient reduce: one layer's gradient tree, a
distinct gradient on every chip, flattened into fixed-size buckets as a
bucketed all-reduce sends them, each bucket averaged over the chips by
`compression.grads.compressed_mean_tree` (with its error-feedback residual),
pass after pass for the whole window.

Configuration keys: the layer's widths (`hidden_size`,
`intermediate_size`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`), `bucket_values`, and the guarantee: `eb_rel`, the bound on
each chip's wire relative to the rms of its gradient.  Traffic keys:
`chain` (a registry preset).

The check holds every bucket of the window's last pass, on every chip, to
the stated bound against `lax.pmean` of the same gradients: the mean of
the chips' bounds, plus the f32 rounding of two p-term sums.

`ratio` counts what the timed program shipped: each call returns, beside
its mean, the wire bits of every wire it handed the transport and each
chip's overflow flag.  A bucket that overflowed (the program then sums
raw f32 instead), or that handed the transport no wire, counts 32 bits a
value.
"""
from __future__ import annotations

import time

import numpy as np

AXIS = "pod"


def leaf_sizes(c: dict) -> dict[str, int]:
    """Values of each gradient leaf of one dense transformer layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, g, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    return {"ln1": d, "ln2": d, "wq": d * h * hd, "wkv": d * 2 * g * hd,
            "wo": h * hd * d, "w1": d * f, "w2": f * d, "w3": d * f}


def buckets(c: dict) -> list[int]:
    """Bucket sizes: whole buckets of `bucket_values` where a leaf divides
    into them, else the leaf as one bucket."""
    b = int(c["bucket_values"])
    out = []
    for size in leaf_sizes(c).values():
        out += [b] * (size // b) if size % b == 0 else [size]
    return out


def _mesh(devices):
    import jax
    return jax.make_mesh((len(devices),), (AXIS,), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))


def _shard_map(fn, mesh):
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(AXIS),
                                 out_specs=P(AXIS), axis_names={AXIS},
                                 check_vma=False))


def make_gradients(seed: int, sizes: list[int], mesh):
    """Every bucket's gradient, [p, n] with row i on chip i, all distinct,
    built on the chips in one call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    p = mesh.devices.size
    sh = NamedSharding(mesh, P(AXIS))

    def build(key):
        return tuple(jax.random.normal(jax.random.fold_in(key, i), (p, n),
                                       jnp.float32)
                     for i, n in enumerate(sizes))

    build = jax.jit(build, out_shardings=tuple(sh for _ in sizes))
    return list(build(jax.random.PRNGKey(seed)))


class ShippedWires:
    """The program's default transport, keeping every wire it is handed
    (arrays of the calling program's own trace) so that the timed program
    can return their size."""

    def __init__(self):
        from repro.core.transport import TRANSPORT
        self.tp, self.wires = TRANSPORT, []

    def reduce_sum(self, enc, pipe, n, axis, **kw):
        self.wires.append((enc, pipe, n))
        return self.tp.reduce_sum(enc, pipe, n, axis, **kw)

    def __getattr__(self, name):
        return getattr(self.tp, name)


def reduce_fn(gcfg, mesh):
    """The timed program: one bucket through compressed_mean_tree.  Returns
    the mean, the residual, the bits a chip shipped through the transport
    and whether its encode overflowed (-1 where it shipped no wire)."""
    import jax.numpy as jnp

    from repro.compression.grads import compressed_mean_tree

    def body(g):
        g = g[0]
        tp = ShippedWires()
        (m,), (r,) = compressed_mean_tree((g,), (jnp.zeros_like(g),), gcfg,
                                          AXIS, transport=tp)
        bits, ovf = jnp.float32(0), jnp.int32(-1 if not tp.wires else 0)
        for enc, pipe, n in tp.wires:
            bits = bits + jnp.asarray(pipe.wire_bits(enc, n), jnp.float32)
            ovf = jnp.maximum(ovf, enc.overflow.astype(jnp.int32))
        return m[None], r[None], bits[None], ovf[None]

    return _shard_map(body, mesh)


def shipped_bits(bits, overflow, n: int) -> float:
    """Bits one bucket's call moved over all chips: its wires, or 32 a
    value on every chip where any chip overflowed or none shipped a
    wire."""
    bits, overflow = np.asarray(bits), np.asarray(overflow)
    if np.any(overflow != 0):
        return 32.0 * n * bits.size
    return float(np.sum(bits))


def error_fn(eb_rel: float, mesh):
    """|mean - pmean| over the stated bound, worst value, per chip."""
    import jax
    import jax.numpy as jnp

    def body(g, m):
        g, m = g[0], m[0]
        ref = jax.lax.pmean(g, AXIS)
        eb = jnp.float32(eb_rel) * jnp.sqrt(jnp.mean(g * g))
        mag = jax.lax.pmax(jnp.max(jnp.abs(g)), AXIS)
        p = jax.lax.axis_size(AXIS)
        bound = (jax.lax.pmean(eb, AXIS)
                 + 4 * p * jnp.finfo(jnp.float32).eps * mag)
        return (jnp.max(jnp.abs(m - ref)) / bound)[None]

    return _shard_map(body, mesh)


def gcfg_for(config: dict, traffic: dict, eb_scale: float = 1.0):
    from repro.compression.grads import GradCompressionConfig
    from repro.configs.registry import get_pipeline
    return GradCompressionConfig(eb_rel=float(config["eb_rel"]) * eb_scale,
                                 pipeline=get_pipeline(traffic["chain"]))


def setup(ctx):
    import jax

    mesh = _mesh(ctx.devices)
    sizes = buckets(ctx.config)
    st = dict(mesh=mesh, sizes=sizes, config=ctx.config,
              traffic=ctx.traffic, gcfg=gcfg_for(ctx.config, ctx.traffic))
    with ctx.spans("make_gradients"):
        st["g"] = make_gradients(ctx.seed, sizes, mesh)
        jax.block_until_ready(st["g"])
    st["fn"] = reduce_fn(st["gcfg"], mesh)
    with ctx.spans("warmup"):
        done = set()
        for g in st["g"]:
            if g.shape not in done:          # one program per bucket shape
                jax.block_until_ready(st["fn"](g))
                done.add(g.shape)
    return st


def one_pass(st, spans):
    import jax
    out = []
    for g in st["g"]:
        with spans("bucket"):
            out.append(st["fn"](g))
    with spans("drain"):
        jax.block_until_ready(out)
    return out


def window(st, seconds: float, spans) -> dict:
    passes, shipped = 0, []
    t0 = time.perf_counter()
    while True:
        st["out"] = None          # one pass's results alive at a time
        with spans("pass"):
            st["out"] = one_pass(st, spans)
        shipped += [(b, o) for _, _, b, o in st["out"]]
        passes += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    values = sum(st["sizes"])
    p = st["mesh"].devices.size
    bits = sum(shipped_bits(b, o, n) for (b, o), n in
               zip(shipped, st["sizes"] * passes)) / passes
    return {"window_s": elapsed, "attempted": passes * len(st["sizes"]),
            "failed": 0,
            "metrics": {"codec_GBps": 4.0 * values * passes / elapsed / 1e9,
                        "ratio": 32.0 * values * p / bits},
            "counters": {"passes": passes, "buckets": len(st["sizes"]),
                         "values": values, "wire_bits": bits}}


def release(st):
    """The residuals are the program's state; the means are what is
    checked."""
    st["out"] = [out[0] for out in st["out"]]


def _worst(st, means) -> float:
    fn = error_fn(float(st["config"]["eb_rel"]), st["mesh"])
    return max(float(np.max(np.asarray(fn(g, m))))
               for g, m in zip(st["g"], means))


def check(st) -> list[dict]:
    return [{"name": "worst error over the bound",
             "value": _worst(st, st["out"]), "limit": 1.0}]


def control(st) -> list[dict]:
    """The same reduce with a wire two bits coarser (eb x 4), judged
    against the bound the configuration states."""
    fn = reduce_fn(gcfg_for(st["config"], st["traffic"], 4.0), st["mesh"])
    means = [fn(g)[0] for g in st["g"]]
    return [{"name": "worst error over the bound",
             "value": _worst(st, means), "limit": 1.0}]
