"""Guarantee-audit plane (DESIGN.md §12): the acceptance pins.

  * Clean-path bit identity: `integrity=True` may not move one bit of
    any transmitted plane — the checksum rides as aux only.
  * Detection coverage: every `runtime.guard` fault class flips the
    checksum verdict on every wire shape (Encoded / SelectedWire /
    PackedKV, static and `auto`-selected), with zero false positives
    on clean wires.
  * `verify=` audit reports: clean encodes audit to zero violations
    (with TIGHTEN margin); non-finite inputs surface in n_nonfinite and
    never as violations.
  * Decode-side length validation: transmitted payload_len beyond the
    wire's capacity raises a structured `WireIntegrityError` host-side;
    truncated-but-consistent wires decode without crashing.
  * Degradation policies: 'raise' raises, `compressed_mean`'s 'drop'
    renormalizes a corrupted shard out of the mean (2-device
    subprocess), the engine's 'rerequest' refuses the insert and counts
    per-slot audit failures.
  * Special-value hardening (the §1 taxonomy): ABS/REL/NOA agree with
    the numpy oracle bit-for-bit on the full special-value sweep, the
    Pallas kernel wire is identical on it, and NaN payloads survive
    the roundtrip.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import QuantizerConfig, audit, oracle_np as onp
from repro.core.pipeline import parse_pipeline
from repro.core.quantizer import quantize_abs, quantize_noa, quantize_rel
from repro.core.select import get_kv_selector, get_selector, parse_selector
from repro.compression.kv import (kv_quantizer_config, pack_kv, quantize_kv,
                                  unpack_kv)
from repro.configs.registry import PIPELINES, SELECTOR_SETS, get_pipeline
from repro.runtime import guard

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import datasets  # noqa: E402

RNG = np.random.default_rng(41)


def _grad(n=1 << 16):
    return jnp.asarray(datasets.GRAD_SUITES["gradsmooth"]()[:n])


def _swap(wire, leaf, arr):
    flat, treedef = jax.tree_util.tree_flatten(wire)
    flat = [jnp.asarray(arr) if f is leaf else f for f in flat]
    return jax.tree_util.tree_unflatten(treedef, flat)


# ------------------------------------------------ clean-path bit identity --

def test_integrity_wire_is_bit_identical_to_plain_encode():
    """The checksum is aux: every transmitted plane of an
    integrity=True encode equals the checksum-free encode bit-for-bit,
    on a pipeline, a selector, and a KV pack."""
    x = _grad()
    pipe = parse_pipeline(get_pipeline("grad-wire-16-ent"))
    eb = float(jnp.sqrt(jnp.mean(x * x))) * 2.0 ** -8
    e0 = pipe.encode(x, eb=eb)
    e1 = pipe.encode(x, eb=eb, integrity=True)
    assert e0.checksum is None and e1.checksum is not None
    for a, b in zip(e0[:-1], e1[:-1]):          # all fields but checksum
        if a is None:
            assert b is None
            continue
        jax.tree.map(lambda p, q: np.testing.assert_array_equal(
            np.asarray(p), np.asarray(q)), a, b)

    sel = parse_selector("auto:grad-wire")
    w0 = sel.encode(x, eb=eb)
    w1 = sel.encode(x, eb=eb, integrity=True)
    assert w0.checksum is None and w1.checksum is not None
    np.testing.assert_array_equal(np.asarray(w0.payload),
                                  np.asarray(w1.payload))
    assert int(w0.chain_id) == int(w1.chain_id)

    q = quantize_kv(jnp.asarray(
        RNG.standard_normal((2, 2, 256, 64)).astype(np.float32)),
        kv_quantizer_config())
    p0 = pack_kv(q, stages="narrow")
    p1 = pack_kv(q, stages="narrow", integrity=True)
    np.testing.assert_array_equal(np.asarray(p0.payload),
                                  np.asarray(p1.payload))
    np.testing.assert_array_equal(np.asarray(p0.payload_len),
                                  np.asarray(p1.payload_len))


def test_checksum_survives_pytree_roundtrip_and_accounts_4_bytes():
    x = _grad()
    pipe = parse_pipeline("abs:0.001:cap=0.015625|pack:16|narrow")
    e0, e1 = pipe.encode(x), pipe.encode(x, integrity=True)
    leaves, treedef = jax.tree_util.tree_flatten(e1)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.checksum is not None and bool(audit.verify_wire(back))
    assert pipe.capacity_bytes(e1) == pipe.capacity_bytes(e0) + 4


# ---------------------------------------------------- detection coverage --

@pytest.mark.parametrize("preset", sorted(PIPELINES))
def test_fault_detection_on_every_pipeline_preset(preset):
    """Every applicable guard fault class must flip the checksum, and
    the clean wire must pass (detection_matrix asserts it)."""
    pipe = parse_pipeline(get_pipeline(preset))
    x = (jnp.asarray(datasets.rel_mixed()[:1 << 16])
         if pipe.quant.mode == "rel" else _grad())
    eb = (float(jnp.sqrt(jnp.mean(x * x))) * 2.0 ** -8
          if pipe.quant.eb == 1.0 else None)
    enc = pipe.encode(x, eb=eb, integrity=True)
    matrix = guard.detection_matrix(enc, suite=preset)
    assert set(matrix) == {"payload_bitflip", "header_bitflip",
                           "length_truncate"}
    assert all(matrix.values()), matrix
    plan = guard.FaultPlan(preset, "nan_input")
    _, rep = pipe.encode(plan.corrupt_input(x), eb=eb, verify=True)
    assert int(rep.n_nonfinite) > 0
    assert int(rep.violations) == 0      # non-finites route to outliers


def test_fault_detection_on_auto_selector_and_kv_wires():
    x = _grad()
    eb = float(jnp.sqrt(jnp.mean(x * x))) * 2.0 ** -8
    sel = get_selector("grad-wire")
    wire = sel.encode(x, eb=eb, integrity=True)
    m = guard.detection_matrix(
        wire, suite="grad-wire",
        n_chains=len(SELECTOR_SETS["grad-wire"]["chains"]))
    assert set(m) == {"payload_bitflip", "header_bitflip",
                      "length_truncate", "chainid_swap"}
    assert all(m.values()), m

    cache = RNG.standard_normal((2, 2, 512, 64)).astype(np.float32)
    cache[:, :, 300:, :] = 0.0
    q = quantize_kv(jnp.asarray(cache), kv_quantizer_config())
    p = pack_kv(q, stages=get_kv_selector("kv-page"), integrity=True)
    m = guard.detection_matrix(p, suite="kv-page", n_chains=3)
    assert "chainid_swap" in m and all(m.values()), m
    m = guard.detection_matrix(pack_kv(q, stages="narrow", integrity=True),
                               suite="kv-page")
    assert "chainid_swap" not in m and all(m.values()), m


def test_even_multiplicity_corruption_is_detected():
    """The fold avalanches (word, position) pairs: the same value change
    at an even number of positions must NOT cancel (a plain xor fold
    would pass it — e.g. every page's chain id bumping together)."""
    cache = RNG.standard_normal((2, 2, 512, 64)).astype(np.float32)
    q = quantize_kv(jnp.asarray(cache), kv_quantizer_config())
    p = pack_kv(q, stages=get_kv_selector("kv-page"), integrity=True)
    cid = np.asarray(p.chain_id)
    assert cid.size % 2 == 0
    bad = _swap(p, p.chain_id, (cid + 1) % 3)
    assert not bool(audit.verify_wire(bad))


def test_detection_matrix_requires_a_checksum():
    pipe = parse_pipeline("abs:0.001|pack:16")
    with pytest.raises(ValueError, match="integrity=True"):
        guard.detection_matrix(pipe.encode(_grad()))


# ------------------------------------------------------- verify= reports --

def test_audit_report_clean_encode_zero_violations():
    x = _grad()
    for spec in ("abs:0.001:cap=0.015625|pack:16|narrow",
                 "rel:0.001|pack:32|shuffle|narrow"):
        pipe = parse_pipeline(spec)
        data = (jnp.asarray(datasets.rel_mixed()[:1 << 16])
                if pipe.quant.mode == "rel" else x)
        enc, rep = pipe.encode(data, verify=True)
        assert int(rep.violations) == 0
        assert int(rep.n) == data.size
        bound = pipe.qcfg().error_bound
        assert float(rep.max_err) <= bound
        assert bool(rep.ok()) == (not bool(enc.overflow))


def test_audit_report_flags_nonfinite_never_violations():
    x = jnp.asarray(datasets.special_values())
    pipe = parse_pipeline("abs:0.001:cap=1.0|pack:16")
    _, rep = pipe.encode(x, verify=True)
    assert int(rep.n_nonfinite) > 0
    assert int(rep.violations) == 0
    assert int(rep.n_outliers) >= int(rep.n_nonfinite)


def test_audit_report_composes_with_jit_and_return_quantized():
    x = _grad()
    pipe = parse_pipeline("abs:0.001:cap=0.015625|pack:16|narrow")
    f = jax.jit(lambda v: pipe.encode(v, verify=True))
    enc, rep = f(x)
    assert int(rep.violations) == 0
    enc2, qt, rep2 = pipe.encode(x, verify=True, return_quantized=True)
    assert int(rep2.violations) == 0
    np.testing.assert_array_equal(np.asarray(enc.payload),
                                  np.asarray(enc2.payload))


def test_selector_encode_verify_and_kernels_warning():
    x = _grad()
    sel = parse_selector("auto:grad-wire")
    eb = float(jnp.sqrt(jnp.mean(x * x))) * 2.0 ** -8
    wire, rep = sel.encode(x, eb=eb, verify=True)
    assert int(rep.violations) == 0
    with pytest.warns(UserWarning, match="fused selector kernel"):
        sel.encode(x, eb=eb, kernels=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")    # no warning on the default path
        sel.encode(x, eb=eb)


# --------------------------------------------------- length validation ----

def test_overlong_payload_len_raises_structured_error():
    x = _grad()
    pipe = parse_pipeline("abs:0.001:cap=0.015625|pack:16|narrow")
    enc = pipe.encode(x)
    cap = enc.payload.shape[0]
    bad = _swap(enc, enc.payload_len,
                np.asarray(enc.payload_len) * 0 + cap + 7)
    with pytest.raises(audit.WireIntegrityError, match="payload_len"):
        pipe.decode(bad, n=x.size)

    cache = RNG.standard_normal((2, 2, 256, 64)).astype(np.float32)
    q = quantize_kv(jnp.asarray(cache), kv_quantizer_config())
    p = pack_kv(q, stages="narrow")
    plen = np.asarray(p.payload_len).copy()
    plen.flat[0] = p.payload.shape[-1] + 1
    with pytest.raises(audit.WireIntegrityError, match="PackedKV"):
        unpack_kv(_swap(p, p.payload_len, plen))


def test_truncated_wire_decodes_without_crash():
    """A truncated-but-consistent wire (half the words, zeroed tail) is
    in-capacity: decode must not crash or read out of bounds — the
    CHECKSUM is what flags the loss, not the decoder."""
    x = _grad()
    pipe = parse_pipeline("abs:0.001:cap=0.015625|pack:16|narrow")
    enc = pipe.encode(x, integrity=True)
    bad = guard.FaultPlan("t", "length_truncate").corrupt_wire(enc)
    y = pipe.decode(bad, n=x.size)               # no verify: must not raise
    assert np.asarray(y).shape == (x.size,)
    assert not bool(audit.verify_wire(bad))
    with pytest.raises(audit.WireIntegrityError, match="checksum"):
        pipe.decode(bad, n=x.size, verify=True)


def test_traced_decode_skips_host_length_check():
    x = _grad()
    pipe = parse_pipeline("abs:0.001:cap=0.015625|pack:16|narrow")
    enc = pipe.encode(x)
    y = jax.jit(lambda e: pipe.decode(e, n=x.size))(enc)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(pipe.decode(enc, n=x.size)))


# ------------------------------------------------- degradation policies ---

def test_policy_registry_raise_drop_rerequest():
    with pytest.raises(audit.WireIntegrityError, match="engine.insert"):
        audit.get_policy("raise")(dict(site="engine.insert"))
    assert audit.get_policy("drop")(dict()) == "drop"
    assert audit.get_policy("rerequest")(dict()) == "rerequest"
    with pytest.raises(KeyError):
        audit.get_policy("no-such-policy")
    audit.register_policy("test-noop", lambda ctx: "noop")
    try:
        assert audit.get_policy("test-noop")({}) == "noop"
    finally:
        del audit.DEGRADATION_POLICIES["test-noop"]


DROP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, ".")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.compression.grads import (GradCompressionConfig,
                                         compress_shard, compressed_mean)
    from repro.core.transport import Transport
    mesh = jax.make_mesh((2,), ("pod",))
    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=16)
    n = 8192
    rng = np.random.default_rng(9)
    g = jnp.asarray((rng.standard_normal((2, n)) * 3e-3)
                    .astype(np.float32))

    def corrupt_shard1(gathered):
        pay = gathered.payload
        return gathered._replace(
            payload=pay.at[1, 0].set(pay[1, 0] ^ jnp.uint32(1 << 9)))

    tp_clean = Transport()
    tp_bad = Transport(fault=corrupt_shard1)

    def run(tp):
        def body(gs):
            m, r = compressed_mean(gs.reshape(-1), cfg, "pod",
                                   transport=tp, integrity="drop")
            return m, r
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("pod"), out_specs=(P(), P("pod")),
            axis_names={"pod"}, check_vma=False))(g)

    mean_clean, _ = run(tp_clean)

    # clean: integrity-drop mean == both-shard mean (no false drop)
    shard0, q0 = compress_shard(g[0], cfg)
    shard1, q1 = compress_shard(g[1], cfg)
    d0 = shard0.pipe.decode(shard0.enc, n=n, kernels=False)
    d1 = shard1.pipe.decode(shard1.enc, n=n, kernels=False)
    ref_both = (d0 + d1) / 2.0
    assert np.array_equal(np.asarray(mean_clean),
                          np.asarray(ref_both)), "clean drop-mean moved"
    print("CLEAN_OK")

    # corrupt shard 1 on the wire: mean renormalizes to shard 0 alone
    mean_bad, _ = run(tp_bad)
    assert np.array_equal(np.asarray(mean_bad), np.asarray(d0)), (
        "corrupt shard not dropped/renormalized")
    print("DROP_OK")
""")


@pytest.mark.slow
def test_compressed_mean_drop_renormalizes_corrupt_shard():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", DROP_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("CLEAN_OK", "DROP_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr)


def test_engine_insert_rerequest_policy_and_stats():
    from repro.configs.base import ArchConfig
    from repro.models import build
    from repro.models import engine as E

    tiny = ArchConfig(name="tiny-audit", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=512, head_dim=16)
    params = build(tiny).init(jax.random.PRNGKey(0))
    prompt = RNG.integers(0, tiny.vocab, size=150).astype(np.int32)

    eng = E.DecodeEngine(tiny, params, n_slots=2, seq=256,
                         integrity="rerequest")
    pre = eng.prefill(prompt)
    assert pre.pages.k.checksum is not None
    assert eng.insert(0, pre) is True
    st = eng.stats()
    assert st["audit_checks"] == 2 and st["audit_failures"] == 0
    assert st["slot_audit"][0] == dict(checks=2, failures=0)

    out = eng.evict(0)
    pay = np.asarray(out.pages.k.payload).copy()
    pay.flat[0] ^= 1
    bad = out._replace(pages=out.pages._replace(
        k=_swap(out.pages.k, out.pages.k.payload, pay)))
    assert eng.insert(0, bad) is False           # refused, slot stays free
    assert eng.requests[0] is None
    st = eng.stats()
    assert st["audit_failures"] == 1
    assert st["slot_audit"][0]["failures"] == 1

    with pytest.raises(KeyError):
        E.DecodeEngine(tiny, params, n_slots=1, seq=256, integrity="bogus")

    eng2 = E.DecodeEngine(tiny, params, n_slots=1, seq=256,
                          integrity="raise")
    bad2 = pre._replace(pages=pre.pages._replace(
        k=_swap(pre.pages.k, pre.pages.k.payload, pay)))
    with pytest.raises(audit.WireIntegrityError):
        eng2.insert(0, bad2)

    eng3 = E.DecodeEngine(tiny, params, n_slots=1, seq=256)
    pre3 = eng3.prefill(prompt)
    assert pre3.pages.k.checksum is None         # integrity off: unchanged
    assert eng3.insert(0, pre3) is True
    assert eng3.stats()["audit_checks"] == 0


# ------------------------------------------- special-value hardening (§1) --

def test_special_values_quantizer_oracle_agreement():
    """ABS / REL / NOA vs the numpy oracle, bit-for-bit, on the paper's
    special-value sweep (±Inf, NaN payloads, denormals, ±0.0)."""
    x = datasets.special_values()
    xj = jnp.asarray(x)

    cfg = QuantizerConfig(mode="abs", error_bound=1e-3)
    ja = quantize_abs(xj, cfg)
    ab, ao, ar = onp.quantize_abs(x, cfg)
    np.testing.assert_array_equal(np.asarray(ja.bins), ab)
    np.testing.assert_array_equal(np.asarray(ja.outlier), ao)
    np.testing.assert_array_equal(np.asarray(ja.recon).view(np.uint32),
                                  ar.view(np.uint32))

    cfgr = QuantizerConfig(mode="rel", error_bound=1e-3)
    jr = quantize_rel(xj, cfgr)
    rb, ro, rr, rsgn = onp.quantize_rel(x, cfgr)
    np.testing.assert_array_equal(np.asarray(jr.bins), rb)
    np.testing.assert_array_equal(np.asarray(jr.outlier), ro)
    np.testing.assert_array_equal(np.asarray(jr.sign), rsgn)

    # NOA: the sweep's finite range overflows f32 -> derived eb inf ->
    # EVERYTHING routes to the lossless outlier path, identically
    cfgn = QuantizerConfig(mode="noa", error_bound=1e-3)
    qn, ebn = quantize_noa(xj, cfgn)
    with np.errstate(over="ignore", invalid="ignore"):
        ob, oo, orr, oeb = onp.quantize_noa(x, cfgn)
    np.testing.assert_array_equal(np.asarray(qn.bins), ob)
    np.testing.assert_array_equal(np.asarray(qn.outlier), oo)
    assert float(ebn) == oeb
    assert bool(np.asarray(qn.outlier).all())


def test_special_values_pinned_classes():
    x = datasets.special_values()
    xj = jnp.asarray(x)
    neg0 = np.where(x.view(np.uint32) == np.uint32(0x80000000))[0]
    assert neg0.size > 0

    # ABS: -0.0 is bin 0, NOT an outlier (|x| <= eb trivially)
    ja = quantize_abs(xj, QuantizerConfig(mode="abs", error_bound=1e-3))
    assert (np.asarray(ja.bins)[neg0] == 0).all()
    assert not np.asarray(ja.outlier)[neg0].any()

    # REL: -0.0 is below the screen threshold -> outlier, and its
    # bit-pattern sign is NEGATIVE (parity with the oracle's int view)
    jr = quantize_rel(xj, QuantizerConfig(mode="rel", error_bound=1e-3))
    assert np.asarray(jr.outlier)[neg0].all()
    assert np.asarray(jr.sign)[neg0].all()


def test_special_values_roundtrip_preserves_nan_payloads_and_kernel_wire():
    x = datasets.special_values()
    xj = jnp.asarray(x)
    pipe = parse_pipeline("abs:0.001:cap=1.0|pack:16|narrow")
    ref = pipe.encode(xj, kernels=False)
    ker = pipe.encode(xj, kernels=True, interpret=True)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(ker)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    y = np.asarray(pipe.decode(ref, n=x.size))
    nf = ~np.isfinite(x)
    np.testing.assert_array_equal(y[nf].view(np.uint32),
                                  x[nf].view(np.uint32))
    payload = np.where(x.view(np.uint32) == np.uint32(0x7FC00123))[0]
    assert payload.size > 0          # the sweep plants payload NaNs
    np.testing.assert_array_equal(y[payload].view(np.uint32),
                                  x[payload].view(np.uint32))


# ------------------------------------------ §12 in-flight hop integrity ---

def test_hop_bitflip_plan_is_not_a_stored_wire_fault():
    plan = guard.FaultPlan("ring", "hop_bitflip")
    enc = parse_pipeline("abs:0.001|pack:16").encode(_grad(1 << 12),
                                                     integrity=True)
    assert "hop_bitflip" in guard.FAULT_CLASSES
    assert "hop_bitflip" not in guard.applicable_classes(enc)
    with pytest.raises(AssertionError):
        plan.corrupt_wire(enc)
    # the in-graph hook is deterministic and hashable (Transport needs
    # a hashable fault for its frozen-dataclass identity)
    hash(plan.corrupt_hop)
    pay = jnp.zeros(64, jnp.uint32)
    a = np.asarray(plan.corrupt_hop((pay, jnp.uint32(0)))[0])
    b = np.asarray(plan.corrupt_hop((pay, jnp.uint32(0)))[0])
    np.testing.assert_array_equal(a, b)
    assert int(np.count_nonzero(a)) == 1     # exactly one flipped bit


def test_reduce_integrity_arg_validation():
    from repro.core.transport import TRANSPORT

    pipe = parse_pipeline("abs:0.001|pack:16")
    enc_plain = pipe.encode(_grad(1 << 12))
    enc_ck = pipe.encode(_grad(1 << 12), integrity=True)
    with pytest.raises(KeyError):
        TRANSPORT.reduce_mean(enc_ck, pipe, 1 << 12, "pod",
                              integrity="no-such-policy")
    with pytest.raises(ValueError, match="drop"):
        TRANSPORT.reduce_mean(enc_ck, pipe, 1 << 12, "pod",
                              integrity="raise")
    with pytest.raises(ValueError, match="integrity=True"):
        TRANSPORT.reduce_mean(enc_plain, pipe, 1 << 12, "pod",
                              integrity="drop")


RING_INTEGRITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compression.grads import GradCompressionConfig, compress_shard
    from repro.core.transport import TRANSPORT, Transport
    from repro.runtime.guard import FaultPlan

    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    def smap(f):
        return jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                             out_specs=(P("pod"), P("pod")),
                             axis_names={"pod"}, check_vma=False)

    # bin_bits=16 keeps the data outlier-free (range ~ +-5 >> the 1e-2
    # values) so the §8 ring genuinely fires — with outliers the compat
    # gate would fall back to gather and never exercise the hop digests
    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, bin_bits=16,
                                outlier_cap_frac=1 / 16)
    pipe, n = cfg.pipe(), 4096

    def run(tp, g):
        def f(v):
            shard, _ = compress_shard(v, cfg, integrity=True)
            mean, nv = tp.reduce_mean(shard.enc, pipe, n, "pod",
                                      integrity="drop", return_valid=True)
            return mean, nv[None]
        gd = jax.device_put(jnp.asarray(g),
                            NamedSharding(mesh, P("pod", None)))
        mean, nv = jax.jit(smap(f))(gd)
        # the global mean comes back flat (p * n); fold to per-rank rows
        return np.asarray(mean).reshape(2, n), np.asarray(nv).tolist()

    r = np.random.default_rng(__import__("zlib").crc32(b"ring-hop-test"))
    g = np.broadcast_to((r.standard_normal(n) * 1e-2).astype(np.float32),
                        (2, n)).copy()

    # clean verified ring: every hop passes and the mean matches the
    # unchecked reduce bit-for-bit (identical shards -> the ring fires)
    mean_c, valid_c = run(TRANSPORT, g)
    assert valid_c == [2, 2], valid_c
    def ref(v):
        shard, _ = compress_shard(v, cfg, integrity=True)
        m = TRANSPORT.reduce_mean(shard.enc, pipe, n, "pod")
        nv = jax.lax.psum(jnp.int32(1), "pod")
        return m, nv[None]
    gd = jax.device_put(jnp.asarray(g), NamedSharding(mesh, P("pod", None)))
    mean_ref, _ = jax.jit(smap(ref))(gd)
    assert np.array_equal(mean_c.reshape(-1).view(np.uint32),
                          np.asarray(mean_ref).reshape(-1).view(np.uint32)), (
        "verified clean ring moved a bit vs the unchecked reduce")
    print("CLEAN_OK")

    # hop corruption: every received hop fails its owner digest, each
    # rank renormalizes down to its own contribution
    plan = FaultPlan("ring", "hop_bitflip")
    mean_f, valid_f = run(Transport(fault=plan.corrupt_hop), g)
    assert valid_f == [1, 1], valid_f
    shard0, _ = compress_shard(jnp.asarray(g[0]), cfg)
    assert int(shard0.enc.n_outliers) == 0, (
        "ring precondition broken: data has outliers, gather would fire")
    own = np.asarray(shard0.pipe.decode(shard0.enc, n=n, kernels=False))
    assert np.array_equal(mean_f[0].view(np.uint32), own.view(np.uint32)), (
        "rank 0's degraded mean is not its own decode")
    print("HOP_DROP_OK")
""")


@pytest.mark.slow
def test_ring_reduce_drops_corrupt_hops():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", RING_INTEGRITY_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("CLEAN_OK", "HOP_DROP_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr)
