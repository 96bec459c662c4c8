"""Transport semantics (DESIGN.md §8): the one choke point that moves
compressed wires must be invisible in the bits.

  * `Transport.reduce_mean` vs the pre-transport gather+dequantize+reduce
    path, frozen verbatim below as `_legacy_gather_sum` — bit-identical
    on every registry pipeline preset (the acceptance pin).
  * The packed-domain ring vs the gather path on a real multi-device
    mesh (subprocess, like test_grad_compression) — bit-identical when
    the §8 compatibility rule fires, and reduce_sum agrees with the
    legacy path whether it rings or gathers.
  * serve.py prefill→decode roundtrip: pages cross only as PackedKV
    wires through `Transport.send_pages`, arrive bit-exact, and the
    reconstructed pages still meet the error bound.
  * `transport.wire_bytes` is the single accounting accessor:
    `CompressedShard.nbytes` / `PackedKV.wire_nbytes` delegate to it.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compression.grads import GradCompressionConfig, compress_shard
from repro.compression.kv import (kv_error_bound_holds, kv_quantizer_config,
                                  pack_kv, quantize_kv)
from repro.configs.registry import PIPELINES, get_pipeline
from repro.core import codec
from repro.core.bitops import bits_to_float
from repro.core.pipeline import parse_pipeline
from repro.core.quantizer import dequantize_abs
from repro.core.transport import (TRANSPORT, Transport, axis_size_static,
                                  wire_bytes)
from repro.models import serve

RNG = np.random.default_rng(83)


def _smap(f, mesh):
    """`f` manual over the one-pod mesh axis, replicated in and out."""
    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names={"pod"}, check_vma=False)


def _legacy_gather_sum(enc, pipe, n, axis):
    """The pre-transport compressed_mean gather/dequantize path (ABS
    chains), frozen verbatim from the PR-3 grads.py as the parity
    reference — any bit moved by the Transport refactor fails here."""
    qc = pipe.qcfg()
    n_words = pipe.n_words(n)

    def dequant_one(w, e, ii, pp):
        bins = codec.unpack_words(w, n, qc.bin_bits)
        vals = dequantize_abs(bins, qc, eb=e, dtype=jnp.float32)
        exact = bits_to_float(pp.astype(jnp.int32), jnp.float32)
        return vals.at[ii].set(exact, mode="drop")

    eb_all = jax.lax.all_gather(enc.eb, axis)
    idx_all = jax.lax.all_gather(enc.out_idx, axis)
    pay_all = jax.lax.all_gather(enc.out_payload, axis)
    if pipe.stages:
        hdrs_all = jax.tree.map(
            lambda h: jax.lax.all_gather(h, axis), enc.headers)
        pw_all = jax.lax.all_gather(enc.payload, axis)
        words_all = jax.vmap(
            lambda hs, pw: pipe.decode_words(hs, pw, n_words))(
                hdrs_all, pw_all)
    else:
        words_all = jax.lax.all_gather(enc.payload, axis)
    return jnp.sum(jax.vmap(dequant_one)(words_all, eb_all, idx_all,
                                         pay_all), axis=0)


def _mix(n):
    x = (RNG.standard_normal(n) * 3e-3).astype(np.float32)
    x[RNG.random(n) < 0.5] = 0.0
    x[7] = 5.0                                     # an exact outlier
    return x


# -------------------------------------------- reduce_mean preset parity ---

@pytest.mark.parametrize("preset", sorted(PIPELINES))
def test_reduce_mean_matches_pre_refactor_path_on_presets(preset):
    """On every registry preset, Transport.reduce_mean under shard_map
    must be bit-identical to the pre-refactor decode: for ABS chains the
    frozen legacy gather+dequantize path, and for every chain the
    pipeline's own local decode (axis size 1 makes them comparable
    in-process; the multi-pod case is the subprocess test below)."""
    pipe = parse_pipeline(get_pipeline(preset))
    n = 20_000
    x = jnp.asarray(_mix(n))
    mesh = jax.make_mesh((1,), ("pod",))

    def eb_of(v):
        if pipe.quant.mode != "abs":
            return None
        rms = jnp.sqrt(jnp.mean(v * v))
        return jnp.float32(2.0 ** -6) * rms

    def run_transport(v):
        enc = pipe.encode(v, eb=eb_of(v), kernels=False)
        return TRANSPORT.reduce_mean(enc, pipe, n, "pod")

    mean = jax.jit(_smap(run_transport, mesh))(x)

    # reference 1: the pipeline's local decode (p == 1 -> mean == decode)
    enc = pipe.encode(x, eb=eb_of(x), kernels=False)
    ref = pipe.decode(enc, n=n, kernels=False).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(mean).view(np.uint32),
                                  np.asarray(ref).view(np.uint32))

    # reference 2 (ABS chains): the frozen legacy collective path — it
    # predates the value domain (§9), so pred-bearing presets pin against
    # reference 1 only: the legacy decoder would read folded residual
    # codes as raw bins
    if pipe.quant.mode == "abs" and not pipe.pred:
        def run_legacy(v):
            e = pipe.encode(v, eb=eb_of(v), kernels=False)
            return _legacy_gather_sum(e, pipe, n, "pod") / jax.lax.psum(
                1, "pod")

        legacy = jax.jit(_smap(run_legacy, mesh))(x)
        np.testing.assert_array_equal(np.asarray(mean).view(np.uint32),
                                      np.asarray(legacy).view(np.uint32))


def test_reduce_gather_transport_pins_reference_path():
    """Transport(reduce='gather') must produce the same bits as the
    default auto transport (which may ring) — here at p=1 both gather."""
    pipe = GradCompressionConfig(bin_bits=8).pipe()
    n = 8192
    x = jnp.asarray(_mix(n))
    mesh = jax.make_mesh((1,), ("pod",))

    def run(tp):
        def f(v):
            shard, _ = compress_shard(v, GradCompressionConfig(bin_bits=8))
            return tp.reduce_mean(shard.enc, pipe, n, "pod")
        return jax.jit(_smap(f, mesh))(x)

    a = run(TRANSPORT)
    b = run(Transport(reduce="gather"))
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32))


def test_transport_rejects_unknown_reduce():
    with pytest.raises(ValueError, match="reduce"):
        Transport(reduce="tree")


def test_axis_size_static_outside_shard_map_is_none():
    assert axis_size_static("no-such-axis") is None


# ------------------------------------------- multi-pod ring bit-identity --

RING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compression.grads import GradCompressionConfig, compress_shard
    from repro.core.transport import TRANSPORT, Transport, axis_size_static

    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names={"pod"},
                             check_vma=False)

    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=8,
                                outlier_cap_frac=1 / 16)
    pipe = cfg.pipe()
    n = 4096
    rng = np.random.default_rng(5)

    def paths(g):
        # explicit ring and gather on the same shard, plus the auto path
        shard, _ = compress_shard(g, cfg)
        p = axis_size_static("pod")
        assert p == 4, p
        ring = TRANSPORT._ring_sum(shard.enc, pipe.qcfg(), n, "pod", p)
        gather = TRANSPORT._gather_sum(shard.enc, pipe, n, "pod")
        auto = TRANSPORT.reduce_sum(shard.enc, pipe, n, "pod")
        pinned = Transport(reduce="gather").reduce_sum(
            shard.enc, pipe, n, "pod")
        return ring, gather, auto, pinned

    mapped = smap(paths, P("pod", None), (P("pod"),) * 4)

    def run(g_global):
        gd = jax.device_put(jnp.asarray(g_global),
                            NamedSharding(mesh, P("pod", None)))
        out = jax.jit(mapped)(gd)
        # each rank's rank-1 result, stacked back to per-rank rows
        return [np.asarray(o).reshape(4, n) for o in out]

    # CASE 1: identical shards -> identical eb, no outliers -> the §8
    # rule fires; ring must be bit-identical to gather (and auto to both).
    # Values stay inside the 8-bit bin range (2 sigma << 127 steps), and
    # the wire is checked outlier-free: the ring is only defined there.
    base = (np.clip(rng.standard_normal(n), -2, 2) * 1e-2).astype(np.float32)
    g_same = np.broadcast_to(base, (4, n)).copy()
    shard0, _ = compress_shard(jnp.asarray(base), cfg)
    assert int(shard0.enc.n_outliers) == 0, "ring precondition broken"
    ring, gather, auto, pinned = run(g_same)
    for i in range(4):
        assert np.array_equal(ring[i].view(np.uint32),
                              gather[i].view(np.uint32)), "ring != gather"
        assert np.array_equal(auto[i].view(np.uint32),
                              gather[i].view(np.uint32)), "auto != gather"
        assert np.array_equal(pinned[i].view(np.uint32),
                              gather[i].view(np.uint32))
    print("RING_OK")

    # CASE 2: different shards -> different per-tensor eb -> the runtime
    # rule must route auto to the gather path (ring output is NOT asserted
    # here: grids differ), still bit-identical to the pinned reference
    g_diff = (rng.standard_normal((4, n)) * 1e-2).astype(np.float32)
    g_diff[0, 7] = 9.0                      # outliers on pod 0 too
    _, gather, auto, pinned = run(g_diff)
    for i in range(4):
        assert np.array_equal(auto[i].view(np.uint32),
                              gather[i].view(np.uint32))
        assert np.array_equal(pinned[i].view(np.uint32),
                              gather[i].view(np.uint32))
    print("FALLBACK_OK")

    # CASE 3: compressed_mean end-to-end is transport-invariant
    from repro.compression.grads import compressed_mean
    m_auto = smap(lambda g: compressed_mean(g, cfg, "pod"),
                  P("pod", None), (P("pod"),) * 2)
    m_pin = smap(lambda g: compressed_mean(
                     g, cfg, "pod", transport=Transport(reduce="gather")),
                 P("pod", None), (P("pod"),) * 2)
    gd = jax.device_put(jnp.asarray(g_diff),
                        NamedSharding(mesh, P("pod", None)))
    (ma, ra) = jax.jit(m_auto)(gd)
    (mp, rp) = jax.jit(m_pin)(gd)
    assert np.array_equal(np.asarray(ma).view(np.uint32),
                          np.asarray(mp).view(np.uint32))
    assert np.array_equal(np.asarray(ra).view(np.uint32),
                          np.asarray(rp).view(np.uint32))
    print("MEAN_OK")
""")


@pytest.mark.slow
def test_packed_domain_ring_bit_identical_multipod():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", RING_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("RING_OK", "FALLBACK_OK", "MEAN_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr)


TRANSFER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.compression.kv import (kv_error_bound_holds,
                                      kv_quantizer_config, quantize_kv)
    from repro.models import serve

    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names={"pod"},
                             check_vma=False)

    rng = np.random.default_rng(11)
    # token-correlated cache so the kvdelta residuals are genuinely small
    x = np.cumsum(rng.standard_normal((2, 1, 2, 256, 64)), axis=3)
    x = (x * 0.05).astype(np.float32)
    x[:, :, :, 160:, :] = 0.0                      # unwritten tail pages
    kv_cfg = kv_quantizer_config()
    qk = quantize_kv(jnp.asarray(x), kv_cfg)
    qv = quantize_kv(jnp.asarray(x * 0.5), kv_cfg)
    hot = jnp.zeros((2, 1, serve.PAGE, 2, 64), jnp.float32)
    cache = serve.QuantCache(qk, qv, hot, hot)
    leaves, treedef = jax.tree.flatten(cache)

    for st in ("kvdelta|zero|narrow", "kvdelta|narrow|ent"):
        def send(c, st=st):
            moved = serve.transfer_cache(c, 0, 1, "pod", stages=st)
            return tuple(jnp.expand_dims(l, 0)
                         for l in jax.tree.leaves(moved))

        out = jax.jit(smap(send, P(), (P("pod"),) * len(leaves)))(cache)
        # rank 1 received the cache bit-identically; rank 0 holds zeros
        for a, b in zip(leaves, out):
            got = np.asarray(b)
            assert np.array_equal(np.asarray(a), got[1]), st
            assert not got[0].any(), st
        recv = jax.tree.unflatten(treedef,
                                  [jnp.asarray(np.asarray(b)[1])
                                   for b in out])
        assert bool(kv_error_bound_holds(jnp.asarray(x), recv.k, kv_cfg))
        print("TRANSFER_OK", st)
""")


@pytest.mark.slow
def test_transfer_cache_kvdelta_bit_exact_across_two_devices():
    """Prefill→decode migration on a REAL 2-device mesh: the kvdelta
    page chains cross via Transport.send_pages and arrive bit-exact on
    the receiving device (decode-side, page-local prediction — §9)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", TRANSFER_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    for st in ("kvdelta|zero|narrow", "kvdelta|narrow|ent"):
        assert f"TRANSFER_OK {st}" in r.stdout, (st, r.stdout, r.stderr)


# -------------------------------------------- serve prefill→decode wire ---

def _toy_cache(l_=2, b=2, g_=2, s=256, hd=64):
    x = RNG.standard_normal((l_, b, g_, s, hd)).astype(np.float32)
    x[:, :, :, 160:, :] = 0.0                    # unwritten tail pages
    kv_cfg = kv_quantizer_config()
    qk = quantize_kv(jnp.asarray(x), kv_cfg)
    qv = quantize_kv(jnp.asarray(x * 0.5), kv_cfg)
    hot = jnp.zeros((l_, b, serve.PAGE, g_, hd), jnp.float32)
    return serve.QuantCache(qk, qv, hot, hot), x, kv_cfg


@pytest.mark.parametrize("stages", ["", "zero", "shuffle|narrow",
                                    "kvdelta|zero|narrow",
                                    "kvdelta|narrow|ent"])
def test_serve_transfer_cache_roundtrip_holds_bound(stages):
    """Prefill→decode disaggregation: the cache crosses the axis only as
    PackedKV wires via Transport.send_pages, arrives bit-identical, and
    the reconstructed pages still satisfy the §1 error bound."""
    cache, x, kv_cfg = _toy_cache()
    mesh = jax.make_mesh((1,), ("pod",))

    def send(c):
        moved = serve.transfer_cache(c, 0, 0, "pod", stages=stages)
        return moved

    received = jax.jit(_smap(send, mesh))(cache)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(received)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the bound survives the transfer (pack/send/unpack are exact)
    assert bool(kv_error_bound_holds(jnp.asarray(x), received.k, kv_cfg))


def test_transfer_wire_is_smaller_than_raw_pages():
    cache, _, _ = _toy_cache()
    wire = serve.pack_cache(cache, stages="zero")
    moved = float(TRANSPORT.bytes_moved(wire, op="send_pages"))
    raw = 2 * cache.k.bins.size * 4 + 2 * cache.hot_k.size * 4
    assert moved < 0.5 * raw, (moved, raw)
    # unwritten tail pages were dropped by the zero stage
    packed_only = float(TRANSPORT.bytes_moved(
        serve.pack_cache(cache), op="send_pages"))
    assert moved < packed_only


# --------------------------------------------------- unified accounting ---

def test_wire_bytes_is_the_single_accessor():
    n = 1 << 15
    g = jnp.asarray(_mix(n))
    cfg = GradCompressionConfig(
        bin_bits=16, pipeline="abs:1.0:cap=0.015625|pack:16|narrow")
    shard, _ = compress_shard(g, cfg)
    assert float(shard.nbytes()) == float(wire_bytes(shard))
    assert float(wire_bytes(shard.enc, pipe=shard.pipe, n=n)) == float(
        wire_bytes(shard))

    x = RNG.standard_normal((2, 256, 64)).astype(np.float32)
    q = quantize_kv(jnp.asarray(x), kv_quantizer_config())
    for stages in ((), "narrow"):
        pk = pack_kv(q, stages=stages)
        assert float(pk.wire_nbytes()) == float(wire_bytes(pk))

    cache, _, _ = _toy_cache(l_=1, b=1, g_=1, s=128)
    wire = serve.pack_cache(cache)
    parts = (float(wire_bytes(wire.k)) + float(wire_bytes(wire.v))
             + wire.hot_k.size * 4 + wire.hot_v.size * 4)
    assert float(wire_bytes(wire)) == parts

    arr = jnp.zeros((7, 3), jnp.float32)
    assert wire_bytes(arr) == 7 * 3 * 4
    with pytest.raises(TypeError):
        wire_bytes(object())
    with pytest.raises(TypeError):
        wire_bytes(shard.enc)                 # Encoded needs its pipe


def test_kv_wire_bytes_equals_per_page_pipeline_accounting():
    """Regression (per-page byte flooring): `_kv_wire_bytes` must agree
    bit-for-bit with summing each page's `Pipeline.wire_bytes` — bits
    accumulated across stages and pages, divided once — for staged
    chains including `ent`."""
    from repro.core.pipeline import (Encoded, PackStage, Pipeline,
                                     QuantStage)

    x = RNG.standard_normal((2, 256, 64)).astype(np.float32)
    x[:, 160:, :] = 0.0
    q = quantize_kv(jnp.asarray(x), kv_quantizer_config())
    table_bytes = (q.eb2.size * 4 + q.out_idx.size * 4
                   + q.out_val.size * 4 + q.overflow.size)
    none = jnp.zeros((0,), jnp.int32)
    for stages in ("zero", "narrow", "shuffle|narrow", "narrow|ent",
                   "kvdelta|narrow|ent"):
        pk = pack_kv(q, stages=stages)
        # pred stages live in pk.pred and ship 0 header bits per page, so
        # the word-stage Pipeline accounts the full wire
        pipe = Pipeline(QuantStage("abs", 1.0), PackStage(8), pk.stages)
        n_page = 128 * 64
        pages = pk.payload.reshape(-1, pk.payload.shape[-1])
        plens = pk.payload_len.reshape(-1)
        hdrs = [h.reshape(pages.shape[0], h.shape[-1]) for h in pk.headers]
        per_page = 0.0
        for i in range(pages.shape[0]):
            enc = Encoded(pages[i], plens[i],
                          tuple(h[i] for h in hdrs), none,
                          none.astype(jnp.uint32), jnp.int32(0),
                          jnp.bool_(False), None, None)
            # the page shares nothing with the §4 outlier/eb header —
            # subtract the empty-table base the Pipeline accessor adds
            per_page += float(pipe.wire_bytes(enc, n_page)) - 64 / 8
        assert float(wire_bytes(pk)) == per_page + table_bytes, stages


def test_kv_wire_bytes_keeps_sub_byte_header_content():
    """Regression: a stage whose transmitted header content is not a
    whole byte per page (the §7 contract allows any bit count) must not
    be floored to 0 bytes — bits accumulate and divide once."""
    from types import SimpleNamespace

    class TwoBitHeaderStage:
        """Contract-minimal stage: 2 bits of header content, length-
        variable payload."""
        transmits_len = True

        def header_content_bits(self, n_in):
            return 2

    pages, cap = 3, 8
    wire = SimpleNamespace(
        payload=jnp.zeros((pages, cap), jnp.uint32),
        payload_len=jnp.asarray([5, 0, 2], jnp.int32),
        stages=(TwoBitHeaderStage(),),
        eb2=jnp.zeros((pages,), jnp.float32),
        out_idx=jnp.zeros((pages, 0), jnp.int32),
        out_val=jnp.zeros((pages, 0), jnp.float32),
        overflow=jnp.zeros((pages,), bool))
    want = (pages * 2                       # 2 bits/page of header content
            + pages * 32                    # transmitted length fields
            + 32 * (5 + 0 + 2)              # payload words
            + pages * 32                    # eb2
            + pages * 8) / 8                # overflow bytes
    assert float(wire_bytes(wire)) == want


def test_kv_wire_bytes_exact_past_2p24_words():
    """Regression: the per-page f32 length sum silently rounded once the
    running total passed 2^24 words; the int32 word accumulation with
    one final conversion must stay exact."""
    from types import SimpleNamespace

    from repro.core.pipeline import parse_word_stages

    pages = 4096
    wire = SimpleNamespace(
        payload=jnp.zeros((pages, codec.LC_CHUNK), jnp.uint32),
        payload_len=jnp.full((pages,), 4097, jnp.int32),
        stages=parse_word_stages("narrow", 8),
        eb2=jnp.zeros((pages,), jnp.float32),
        out_idx=jnp.zeros((pages, 0), jnp.int32),
        out_val=jnp.zeros((pages, 0), jnp.float32),
        overflow=jnp.zeros((pages,), bool))
    total_words = pages * 4097                     # 2^24 + 2^12 > 2^24
    hdr_bits = pages * wire.stages[0].header_content_bits(codec.LC_CHUNK)
    want = (hdr_bits + pages * 32 + 32 * total_words
            + pages * 32 + pages * 8) / 8          # exact python int / 8
    got = float(wire_bytes(wire))
    assert got == want, (got, want)


def test_pipeline_wire_bits_exact_past_2p24_words():
    """Regression: Pipeline.wire_bits added the static header bits to a
    traced f32 bit total, which rounds past 2^24 words; the int32 word
    accumulation must stay exact (and provably differs from the old
    formula at this size)."""
    from repro.core.pipeline import Encoded, parse_pipeline

    pipe = parse_pipeline("abs:1.0|pack:8|narrow")
    n = 1 << 20                               # -> 512 chunks of header
    static_bits = (64 + pipe.stages[0].header_content_bits(
        pipe.n_words(n)) + 32)
    assert static_bits % 32 == 0
    # > 2^24 transmitted words; the exact total word count (payload +
    # static header words) is f32-representable, so the single final
    # conversion is lossless
    plen = (1 << 24) + 3
    enc = Encoded(jnp.zeros((0,), jnp.uint32), jnp.int32(plen),
                  (jnp.zeros((0,), jnp.uint32),),
                  jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.uint32),
                  jnp.int32(0), jnp.bool_(False), None, None)
    total_words = plen + static_bits // 32
    assert int(np.float32(float(total_words))) == total_words
    want = 32 * total_words                   # exact python int
    assert float(pipe.wire_bits(enc, n)) == want
    # the old bits-domain f32 arithmetic rounds away at this magnitude
    old = np.float32(32.0) * np.float32(float(plen)) + np.float32(
        static_bits)
    assert float(old) != want


def test_bytes_moved_per_op():
    x = RNG.standard_normal((2, 256, 64)).astype(np.float32)
    pk = pack_kv(quantize_kv(jnp.asarray(x), kv_quantizer_config()))
    w = float(wire_bytes(pk))
    assert float(TRANSPORT.bytes_moved(pk, op="send_pages")) == w
    assert float(TRANSPORT.bytes_moved(pk, op="all_gather",
                                       axis_size=4)) == 4 * 3 * w
    assert float(TRANSPORT.bytes_moved(pk, op="reduce_mean",
                                       axis_size=2)) == 2 * 1 * w
    with pytest.raises(ValueError, match="op"):
        TRANSPORT.bytes_moved(pk, op="broadcast")
    # a degenerate axis must error, not silently report 0 moved bytes
    with pytest.raises(ValueError, match="axis_size"):
        TRANSPORT.bytes_moved(pk, op="all_gather")


def test_all_gather_is_pytree_wide():
    """Transport.all_gather == lax.all_gather on every array leaf, with
    static aux (pipelines, stage chains) untouched."""
    x = RNG.standard_normal((2, 256, 64)).astype(np.float32)
    pk = pack_kv(quantize_kv(jnp.asarray(x), kv_quantizer_config()),
                 stages="narrow")
    mesh = jax.make_mesh((1,), ("pod",))

    def f(p):
        return TRANSPORT.all_gather(p, "pod")

    out = jax.jit(_smap(f, mesh))(pk)
    assert out.stages == pk.stages
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(pk)):
        assert a.shape == (1,) + b.shape
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b))
