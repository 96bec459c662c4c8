"""The fused kernels compile for a TPU v5e at the chip smoke's size.

Each case lowers public kernel entry points at 2^27 values (one 512^3
f32 field) with `interpret=False` for one chip of a described `v5e:2x2`
topology, and checks that Mosaic emitted the kernel (`tpu_custom_call`).
Nothing runs: this is the compile rehearsal that guards the chip path
without a chip.  The topology is described inside a fixture, never while
a module is imported, so every xdist worker collects the same tests and
only the worker given this file loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import QuantizerConfig
from repro.core import codec as C
from repro.kernels import lossless as klc
from repro.kernels import pack as kpack

N = 1 << 27


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _kernel_in(f, *args) -> bool:
    return "tpu_custom_call" in jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode,bits", [("abs", 8), ("abs", 16), ("abs", 32),
                                       ("rel", 32)])
def test_pack_kernels_compile_for_v5e(one_chip, mode, bits):
    cfg = QuantizerConfig(mode=mode, error_bound=1e-3, bin_bits=bits)
    x = jax.ShapeDtypeStruct((N,), jnp.float32)
    assert _kernel_in(lambda v: kpack.encode_packed(v, cfg, interpret=False),
                      _on(x, one_chip))
    wire = _on(jax.eval_shape(lambda v: C.encode_packed(v, cfg), x), one_chip)
    assert _kernel_in(
        lambda e: kpack.decode_packed(e, cfg, n=N, interpret=False), wire)


@pytest.mark.parametrize("stage,mode,bits", [("zero", "abs", 8),
                                             ("narrow", "rel", 32)])
def test_lossless_kernels_compile_for_v5e(one_chip, stage, mode, bits):
    cfg = QuantizerConfig(mode=mode, error_bound=1e-3, bin_bits=bits)
    x = jax.ShapeDtypeStruct((N,), jnp.float32)
    assert _kernel_in(
        lambda v: klc.encode_packed_lc(v, cfg, stage=stage, interpret=False),
        _on(x, one_chip))
    n_words = C.packed_word_count(N, bits)
    hw, payload, _ = jax.eval_shape(
        lambda w: C.encode_words_lc(w, stage),
        jax.ShapeDtypeStruct((n_words,), jnp.uint32))
    assert _kernel_in(
        lambda h, p: klc.decode_words_lc(h, p, n_words, interpret=False),
        *_on((hw, payload), one_chip))
