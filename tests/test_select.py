"""Adaptive chain selector (DESIGN.md §11): the runtime choice must be
invisible in the bits and honest in the accounting.

  * Bit-transparency: a selected wire decodes bit-identically to
    encoding directly with the chosen chain (the `lax.switch` branch IS
    that chain's own encode) — pinned pointwise and as a hypothesis
    property over adversarial inputs.
  * The §1 guarantee survives selection verbatim: every decoded value is
    within the bound or bit-identical.
  * Acceptance: on the gradient suites + iid + the NYX-like plane, the
    statistics pick the true per-suite best candidate on most suites and
    the auto wire is never more than 2% above the per-suite best.
  * Accounting: `Selector.wire_bits` = the chosen chain's own
    `Pipeline.wire_bits` + the 8-bit chain id; the KV per-page wire adds
    exactly one id byte per page over the same pages packed statically.
  * The selector grad wire rides `compressed_mean` unchanged
    (shard_map), bit-identical to the decode-then-sum reference.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compression.grads import GradCompressionConfig, compress_shard
from repro.compression.kv import (kv_error_bound_holds, kv_quantizer_config,
                                  pack_kv, quantize_kv, unpack_kv)
from repro.core import select as SEL
from repro.core.pipeline import parse_pipeline

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from benchmarks import datasets  # noqa: E402

RNG = np.random.default_rng(17)
N = 1 << 14
EB = 1e-3


def _u32(a):
    return np.asarray(a).view(np.uint32)


def _suite_cut(gen, cut=1 << 16):
    return np.asarray(gen())[:cut]


@pytest.fixture(scope="module")
def sel():
    return SEL.get_selector("grad-wire")


# ------------------------------------------------------ bit-transparency --

def test_selected_wire_is_the_chosen_chains_wire(sel):
    """The switch branch is the candidate's own encode: every field of
    the re-split view must be byte-equal to the direct encoding."""
    x = jnp.asarray((RNG.standard_normal(N) * 3e-3).astype(np.float32))
    wire = sel.encode(x, EB)
    cid = int(wire.chain_id)
    pipe = sel.chains[cid]
    direct = pipe.encode(x, EB, kernels=False)
    view = sel._view(wire, cid, N)
    assert np.array_equal(_u32(view.payload), _u32(direct.payload))
    assert int(view.payload_len) == int(direct.payload_len)
    for hv, hd in zip(view.headers, direct.headers):
        assert np.array_equal(_u32(hv), _u32(hd.reshape(-1)))
    # and the decode is bit-identical both ways
    y_auto = sel.decode(wire, shape=x.shape)
    y_direct = pipe.decode(direct, shape=x.shape, kernels=False)
    assert np.array_equal(_u32(y_auto), _u32(y_direct))


def test_auto_roundtrip_property():
    """Hypothesis twin: adversarial float32 inputs (zeros, huge values,
    specials) through every registered full-pipeline set — selection
    never moves a bit vs the chosen chain, and the §1 bound holds."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st
    del hyp

    sel = SEL.get_selector("grad-wire")

    @settings(max_examples=20, deadline=None)
    @given(st.lists(
        st.floats(width=32, allow_nan=True, allow_infinity=True),
        min_size=1, max_size=600), st.integers(0, 2 ** 31))
    def prop(vals, seed):
        r = np.random.default_rng(seed)
        n = 1024
        x = np.zeros(n, np.float32)
        x[: len(vals)] = np.asarray(vals, np.float32)
        r.shuffle(x)
        xj = jnp.asarray(x)
        wire = sel.encode(xj, EB)
        cid = int(wire.chain_id)
        y = np.asarray(sel.decode(wire, shape=(n,)))
        y_direct = np.asarray(sel.chains[cid].decode(
            sel.chains[cid].encode(xj, EB, kernels=False), shape=(n,),
            kernels=False))
        assert np.array_equal(_u32(y), _u32(y_direct))
        ok = (np.abs(y - x) <= EB) | (_u32(y) == _u32(x))
        assert bool(np.all(ok)) or bool(wire.overflow)

    prop()


def test_error_bound_holds_through_auto(sel):
    for gen in (datasets.grad_smooth, datasets.grad_sparse, datasets.iid):
        x = _suite_cut(gen, 1 << 15)
        y = np.asarray(sel.roundtrip(jnp.asarray(x), EB))
        ok = (np.abs(y - x) <= EB) | (_u32(y) == _u32(x))
        assert bool(np.all(ok)), gen.__name__


# ----------------------------------------------------------- acceptance --

def test_auto_tracks_the_best_static_chain(sel):
    """The §11 acceptance bar: auto never pays more than 2% over the
    per-suite best candidate, and the statistics pick the true argmin
    candidate on most suites (the iid suite additionally pins that the
    choice is never the pred chain — predictors cannot win on iid)."""
    suites = dict(datasets.GRAD_SUITES, iid=datasets.iid)
    hits, rows = 0, []
    for name, gen in suites.items():
        x = jnp.asarray(_suite_cut(gen))
        eb = jnp.float32(2.0 ** -8) * jnp.sqrt(jnp.mean(x * x))
        n = x.size
        actual = [float(p.wire_bits(p.encode(x, eb, kernels=False), n))
                  for p in sel.chains]
        wire = sel.encode(x, eb)
        auto_bits = float(sel.wire_bits(wire, n))
        cid, best = int(wire.chain_id), int(np.argmin(actual))
        assert auto_bits <= 1.02 * actual[best], (name, auto_bits, actual)
        hits += cid == best
        rows.append((name, sel.chains[cid].spec(),
                     sel.chains[best].spec()))
        if name == "iid":
            assert not sel.chains[cid].pred, rows[-1]
    assert hits >= len(suites) - 1, rows


def test_sci_plane_auto(sel):
    """The 2-D set: lorenzo must fire on the NYX-like plane (via
    pred_shape threading) and auto must track the best chain."""
    ssel = SEL.get_selector("sci-plane")
    x = jnp.asarray(datasets.nyx_plane(256))
    n = x.size
    actual = [float(p.wire_bits(p.encode(x, kernels=False), n))
              for p in ssel.chains]
    wire = ssel.encode(x)
    cid, best = int(wire.chain_id), int(np.argmin(actual))
    assert float(ssel.wire_bits(wire, n)) <= 1.02 * actual[best]
    assert cid == best
    y = np.asarray(ssel.decode(wire, shape=x.shape))
    xs = np.asarray(x)
    ok = (np.abs(y - xs) <= ssel.quant.eb) | (_u32(y) == _u32(xs))
    assert bool(np.all(ok))


# ----------------------------------------------------------- accounting --

def test_wire_bits_is_chosen_chain_plus_id_byte(sel):
    x = jnp.asarray(_suite_cut(datasets.grad_smooth, 1 << 15))
    wire = sel.encode(x, EB)
    cid = int(wire.chain_id)
    direct = sel.chains[cid].encode(x, EB, kernels=False)
    assert float(sel.wire_bits(wire, x.size)) == pytest.approx(
        float(sel.chains[cid].wire_bits(direct, x.size))
        + SEL.CHAIN_ID_BITS)


def test_selector_rejects_unscoreable_and_mixed_sets():
    base = parse_pipeline("abs:1e-3|pack:16|shuffle|narrow")
    with pytest.raises(ValueError, match="scoreab"):
        SEL.Selector("bad", (base,))
    a = parse_pipeline("abs:1e-3|pack:16|narrow")
    b = parse_pipeline("abs:1e-3|pack:8|narrow")
    with pytest.raises(ValueError, match="share"):
        SEL.Selector("mixed", (a, b))
    with pytest.raises(ValueError, match="bias"):
        SEL.Selector("nobias", (a,), bias=(0.0, 1.0))


# --------------------------------------------------------- grad wire ------

def test_selector_grad_wire_through_compressed_mean():
    """pipeline='auto' rides the §8 gather path unchanged: the
    shard_map `compressed_mean` result is bit-identical to decoding the
    selector wire and averaging by hand."""
    from repro.compression.grads import compressed_mean

    cfg = GradCompressionConfig(pipeline="auto")
    pipe = cfg.pipe()
    assert isinstance(pipe, SEL.Selector)
    g = jnp.asarray((RNG.standard_normal(N) * 3e-3).astype(np.float32))

    shard, _ = compress_shard(g, cfg)
    ref = pipe.decode(shard.enc, n=N)

    mesh = jax.make_mesh((1,), ("pod",))
    m, resid = jax.shard_map(
        lambda gg: compressed_mean(gg[0], cfg, "pod"),
        mesh=mesh, in_specs=P("pod"), out_specs=(P(), P()),
        axis_names={"pod"}, check_vma=False)(g[None])
    assert np.array_equal(_u32(m), _u32(ref))
    assert np.all(np.abs(np.asarray(resid))
                  <= float(shard.enc.eb) * 1.0000001)


# ------------------------------------------------------------- KV pages ---

def test_kv_auto_pages_roundtrip_and_account():
    cache = RNG.standard_normal((2, 2, 512, 64)).astype(np.float32)
    cache[:, :, 300:, :] = 0.0                     # unwritten decode tail
    cfg = kv_quantizer_config()
    q = quantize_kv(jnp.asarray(cache), cfg, page=128)

    sel = SEL.get_kv_selector("kv-page")
    packed = pack_kv(q, page=128, stages=sel)
    assert packed.select is sel
    n_pages = packed.chain_id.size

    # bit-exact per-page roundtrip + the §1 bound on the cache
    q2 = unpack_kv(packed, page=128)
    assert np.array_equal(np.asarray(q2.bins), np.asarray(q.bins))
    assert bool(kv_error_bound_holds(jnp.asarray(cache), q2, cfg))

    # accounting: where every page picks fragment i, the auto wire costs
    # exactly the static fragment wire + one id byte per page
    ids = np.unique(np.asarray(packed.chain_id))
    if ids.size == 1:
        from repro.configs.registry import SELECTOR_SETS
        frag = SELECTOR_SETS["kv-page"]["chains"][int(ids[0])]
        static = pack_kv(q, page=128, stages=frag)
        assert float(packed.wire_nbytes()) == pytest.approx(
            float(static.wire_nbytes()) + n_pages)

    # pytree roundtrip keeps the selection (device_put runs flatten)
    leaves, treedef = jax.tree.flatten(packed)
    packed2 = jax.tree.unflatten(treedef, leaves)
    assert packed2.select is sel
    assert np.array_equal(np.asarray(packed2.chain_id),
                          np.asarray(packed.chain_id))


def test_kv_auto_correlated_picks_kvdelta():
    """Token-correlated KV rows are kvdelta's case (§9): when rows
    repeat along the token axis the raw bins are dense (nothing for
    `zero`/`narrow` to drop) but the previous-token residuals vanish —
    the per-page statistics must route those pages to the kvdelta
    fragment."""
    row = RNG.standard_normal((1, 2, 1, 64)).astype(np.float32)
    corr = np.broadcast_to(row, (1, 2, 512, 64)).copy()
    q = quantize_kv(jnp.asarray(corr), kv_quantizer_config(), page=128)
    sel = SEL.get_kv_selector("kv-page")
    packed = pack_kv(q, page=128, stages=sel)
    from repro.configs.registry import SELECTOR_SETS
    frags = SELECTOR_SETS["kv-page"]["chains"]
    chosen = [frags[i] for i in np.asarray(packed.chain_id).ravel()]
    assert any("kvdelta" in c for c in chosen), chosen
    assert np.array_equal(np.asarray(unpack_kv(packed, page=128).bins),
                          np.asarray(q.bins))


# ------------------------------------------------------------ plumbing ----

def test_parse_chain_grammar():
    assert isinstance(SEL.parse_chain("auto"), SEL.Selector)
    assert SEL.parse_chain("auto:sci-plane").name == "sci-plane"
    assert isinstance(SEL.parse_chain("abs:1e-3|pack:8|zero"),
                      type(parse_pipeline("abs:1e-3|pack:8|zero")))
    with pytest.raises(KeyError):
        SEL.get_selector("kv-page")        # page set via the wrong getter
    with pytest.raises(KeyError):
        SEL.get_kv_selector("grad-wire")


def test_grads_config_cap_semantics():
    """Same rule as plain specs: an explicit cap= in the set's base spec
    wins over the config (the registry grad-wire base pins 1/64), and a
    REL base is rejected — the per-tensor eb override is an ABS bound."""
    cfg = GradCompressionConfig(pipeline="auto", outlier_cap_frac=1 / 32)
    pipe = cfg.pipe()
    assert isinstance(pipe, SEL.Selector)
    for p in pipe.chains:
        assert p.quant.cap == pytest.approx(1 / 64)
        assert p.quant.mode == "abs"
