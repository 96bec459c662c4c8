"""Codec round trip: one scientific field, encoded and decoded back to back
through one chain for the whole window.

Configuration keys: `shape`, `n_special`, `special_bits`, `field`
(`noise_log_sigma`, `mode_amps`: the amplitude in log density of each
sine mode along every axis).  Traffic keys: `chain` (a
registry preset), and the guarantee the chain states: `mode` (`abs` or
`rel`) and `error_bound`.

The check holds every decoded value of the window's last round trip, and
of one more drawn from the seed, to the stated bound: |x - y| <= eb (ABS)
or <= eb |x| (REL), or y bit-identical to x (NaN, Inf).  The reference is
the field itself; nothing of the program takes part in it.
"""
from __future__ import annotations

import time

import numpy as np


def make_field(seed: int, config: dict):
    """A smooth log-normal density field with NaN/Inf/denormal specials,
    built on the device in one call.  Returns the field, f32[shape]."""
    import jax
    import jax.numpy as jnp

    shape = tuple(config["shape"])
    f = config["field"]
    n = int(np.prod(shape))
    n_special = int(config["n_special"])
    bits = jnp.asarray(config["special_bits"], jnp.uint32)

    @jax.jit
    def build(key):
        ks, kp, kn, ko = jax.random.split(key, 4)
        # every seed gets the same mode amplitudes (so the same value
        # statistics, and the same work); the seed draws their signs,
        # their phases and the noise
        amp = jnp.asarray(f["mode_amps"], jnp.float32)
        sign = jnp.where(jax.random.bernoulli(ks, 0.5, (len(shape),
                                                        amp.size)), 1., -1.)
        ph = jax.random.uniform(kp, (len(shape), amp.size)) * 2 * jnp.pi
        k = jnp.arange(1, amp.size + 1, dtype=jnp.float32)
        log_rho = f["noise_log_sigma"] * jax.random.normal(kn, shape)
        for d, s in enumerate(shape):
            t = jnp.linspace(0, 2 * jnp.pi, s, dtype=jnp.float32)
            wave = jnp.sum((sign[d] * amp)[:, None] * jnp.sin(
                k[:, None] * t[None, :] + ph[d][:, None]), axis=0)
            log_rho = log_rho + wave.reshape(
                [s if i == d else 1 for i in range(len(shape))])
        x = jnp.exp(log_rho).reshape(-1)
        # specials spread over the whole field, at a seeded offset
        off = jax.random.randint(ko, (), 0, n // n_special)
        idx = jnp.arange(n_special) * (n // n_special) + off
        pat = bits[jnp.arange(n_special) % bits.shape[0]]
        x = x.at[idx].set(jax.lax.bitcast_convert_type(pat, jnp.float32))
        return x.reshape(shape)

    return build(jax.random.PRNGKey(seed))


def violations(x, y, mode: str, eb: float):
    """Values of y outside the stated bound of x and not bit-identical."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(x, y):
        bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)
        err = jnp.abs(x - y)
        lim = jnp.float32(eb) * (jnp.abs(x) if mode == "rel" else 1.0)
        ok = (err <= lim) | (bits(x) == bits(y))
        return jnp.sum(~ok)

    return int(count(x, y))


def naive_roundtrip(x, mode: str, eb: float):
    """The control: a plain quantizer with no double check, y =
    round(x / 2eb) * 2eb (ABS) or its log-domain twin (REL), in f32, as
    the paper's lossy quantizers did before it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rt(x):
        if mode == "rel":
            s = jnp.log1p(jnp.float32(eb))
            b = jnp.round(jnp.log(jnp.abs(x)) / (2 * s)).astype(jnp.int32)
            return jnp.sign(x) * jnp.exp(b.astype(jnp.float32) * 2 * s)
        b = jnp.round(x / (2 * jnp.float32(eb))).astype(jnp.int32)
        return b.astype(jnp.float32) * (2 * jnp.float32(eb))

    return rt(x)


def setup(ctx):
    import jax

    from repro.configs.registry import get_pipeline
    from repro.core.pipeline import parse_pipeline

    tr = ctx.traffic
    st = dict(mode=tr["mode"], eb=float(tr["error_bound"]),
              pipe=parse_pipeline(get_pipeline(tr["chain"])))
    with ctx.spans("make_field"):
        x = make_field(ctx.seed, ctx.config)
        x.block_until_ready()
    st["x"] = x
    with ctx.spans("warmup"):
        t0 = time.perf_counter()
        enc = st["pipe"].encode(x)
        jax.block_until_ready(enc)
        y = st["pipe"].decode(enc, shape=x.shape)
        y.block_until_ready()
        st["rt_s"] = time.perf_counter() - t0
        bits = float(st["pipe"].wire_bits(enc, x.size))
    st["wire_bits"] = bits
    del enc, y
    # which round trip of the window the check keeps besides the last
    st["rng"] = np.random.default_rng(ctx.seed)
    return st


def window(st, seconds: float, spans) -> dict:
    import jax

    pipe, x = st["pipe"], st["x"]
    est = max(1, int(seconds / max(st["rt_s"], 1e-3)))
    keep_at = int(st["rng"].integers(0, est))
    kept = []
    n = 0
    t0 = time.perf_counter()
    while True:
        with spans("encode"):
            enc = pipe.encode(x)
            jax.block_until_ready(enc)
        with spans("decode"):
            y = pipe.decode(enc, shape=x.shape)
            y.block_until_ready()
        if n == keep_at:
            kept.append((enc, y))
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    if n - 1 != keep_at:
        kept.append((enc, y))
    st["kept"] = kept
    in_bytes = 4.0 * x.size * n
    return {"window_s": elapsed, "attempted": n, "failed": 0,
            "metrics": {"codec_GBps": in_bytes / elapsed / 1e9,
                        "ratio": 32.0 * x.size / st["wire_bits"]},
            "counters": {"values": int(x.size), "round_trips": n,
                         "wire_bytes": st["wire_bits"] / 8.0}}


def release(st):
    """The program keeps no state beyond the kept outputs the check reads."""


def check(st) -> list[dict]:
    x = st["x"]
    bad = sum(violations(x, y, st["mode"], st["eb"]) for _, y in st["kept"])
    # the wire of every kept round trip has the size the window counted
    sizes = {float(st["pipe"].wire_bits(enc, x.size)) for enc, _ in
             st["kept"]}
    drift = max(abs(s - st["wire_bits"]) for s in sizes)
    return [{"name": "values outside the bound", "value": bad, "limit": 0},
            {"name": "wire bits off the counted size", "value": drift,
             "limit": 0}]


def control(st) -> list[dict]:
    """The check's numbers for the naive quantizer in the program's place."""
    y = naive_roundtrip(st["x"], st["mode"], st["eb"])
    return [{"name": "values outside the bound",
             "value": violations(st["x"], y, st["mode"], st["eb"]),
             "limit": 0}]
