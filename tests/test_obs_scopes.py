"""Device scopes (DESIGN.md §14): every op that the codec, the transport,
the gradient reduce and the serving step trace carries its layer's
`<layer>.<part>` scope in the op-name metadata of the compiled HLO, which
is what a device trace names each op by (`bench/scopes.py`)."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compression import kv as KVC
from repro.configs.base import ArchConfig
from repro.core.pipeline import parse_pipeline
from repro.models import build
from repro.models import engine as E
from repro.models import serve as S

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
from bench.scopes import scope_parts  # noqa: E402

TINY = ArchConfig(name="tiny-scopes", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=512,
                  head_dim=16)
SERVE = {"serve.embed", "serve.layers", "serve.attn", "serve.kv_dequant",
         "serve.page_close", "serve.ffn", "serve.lm_head"}


def hlo_scopes(compiled_text: str) -> set:
    """Every name in the op-name metadata of a compiled module."""
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled_text):
        out |= scope_parts(op_name)
    return out


def compiled_scopes(fn, *args) -> set:
    return hlo_scopes(jax.jit(fn).lower(*args).compile().as_text())


@pytest.fixture(scope="module")
def tiny_engine():
    params = build(TINY).init(jax.random.PRNGKey(0))
    return E.DecodeEngine(TINY, params, n_slots=2, seq=256,
                          kv_cfg=KVC.kv_quantizer_config())


def test_serve_step_scopes(tiny_engine):
    eng = tiny_engine
    cache = S.make_quant_cache(TINY, 1, 256)
    got = compiled_scopes(eng._one_step, eng.params, cache,
                          jnp.zeros((1, 1), jnp.int32), jnp.int32(3))
    assert SERVE <= got, SERVE - got


def test_slots_step_scopes(tiny_engine):
    eng = tiny_engine
    got = compiled_scopes(eng._slots_step, eng.params, eng._cache, eng._tok,
                          eng._pos, jnp.asarray([True, False]))
    assert (SERVE | {"serve.slots"}) <= got, (SERVE | {"serve.slots"}) - got


X = jnp.asarray(np.random.default_rng(3).standard_normal(4096)
                .astype(np.float32))
CHAIN = "abs:1e-3|pack:8|narrow"


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["reference", "kernel"])
def test_pipeline_encode_scopes(kernels):
    pipe = parse_pipeline(CHAIN)
    got = compiled_scopes(
        lambda x: pipe.encode(x, kernels=kernels, interpret=True), X)
    want = {"codec.quantize_pack", "codec.compact", "codec.compact.outliers",
            "codec.compact.chunks"}
    if not kernels:        # the fused kernel narrows inside itself
        want |= {"codec.word_stages", "narrow"}
    assert want <= got, want - got


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["reference", "kernel"])
def test_pipeline_decode_scopes(kernels):
    pipe = parse_pipeline(CHAIN)
    enc = pipe.encode(X, kernels=False)
    got = compiled_scopes(
        lambda e: pipe.decode(e, n=X.size, kernels=kernels, interpret=True),
        enc)
    want = {"codec.word_stages", "narrow", "codec.dequantize"}
    assert want <= got, want - got


REDUCE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re, sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    sys.path.insert(0, os.getcwd())
    from bench.scopes import scope_parts
    from repro.compression.grads import (GradCompressionConfig,
                                         compressed_mean)
    from repro.core.pipeline import parse_pipeline
    from repro.core.transport import TRANSPORT

    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4 * 2048)
                    .astype(np.float32))

    def scopes(f):
        m = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                  out_specs=P("pod"), axis_names={"pod"},
                                  check_vma=False))
        text = m.lower(x).compile().as_text()
        out = set()
        for op in re.findall(r'op_name="([^"]*)"', text):
            out |= scope_parts(op)
        return sorted(out)

    def reduce_with(chain):
        pipe = parse_pipeline(chain)
        def f(g):
            enc = pipe.encode(g, eb=jnp.float32(1e-2), kernels=False)
            return TRANSPORT.reduce_sum(enc, pipe, g.size, "pod")
        return f

    cfg = GradCompressionConfig(eb_rel=2.0 ** -6, pipeline="abs:1|pack:8|narrow")
    out = {
        "gather": scopes(reduce_with("abs:1e-2|pack:8|narrow")),
        "ring": scopes(reduce_with("abs:1e-2|pack:8")),
        "mean": scopes(lambda g: compressed_mean(g, cfg, "pod")[0]),
    }
    print("SCOPES " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def reduce_scopes():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", REDUCE_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("SCOPES ")]
    assert line, r.stdout + r.stderr
    return {k: set(v) for k, v in json.loads(line[-1][7:]).items()}


@pytest.mark.parametrize("case,want", [
    ("gather", {"transport.gather", "codec.word_stages", "narrow",
                "codec.dequantize"}),
    ("ring", {"transport.ring", "transport.gather"}),
    ("mean", {"grads.compress", "codec.quantize_pack", "codec.compact",
              "codec.compact.outliers", "codec.compact.chunks",
              "transport.gather", "transport.psum_fallback"}),
])
def test_reduce_scopes_under_shard_map(reduce_scopes, case, want):
    got = reduce_scopes[case]
    assert want <= got, want - got
