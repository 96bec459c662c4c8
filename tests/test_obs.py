"""Host spans (`repro.obs`, DESIGN.md §14): a span is a profiler
`TraceAnnotation` that carries its ids, is skipped where its inputs are
tracers, and the engine's and the codec's spans land in a profiler trace
with the request they belong to."""
from __future__ import annotations

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import obs
from repro.compression import kv as KVC
from repro.configs.base import ArchConfig
from repro.core.pipeline import parse_pipeline
from repro.models import build
from repro.models import engine as E

TINY = ArchConfig(name="tiny-obs", family="dense", n_layers=1, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=512, head_dim=16)


def traced(fn, log_dir) -> dict:
    """Run fn under the profiler; returns {span name: [stats dict, ...]}
    of the host events it recorded."""
    with jax.profiler.trace(str(log_dir)):
        fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out: dict = {}
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host"):
            continue
        for ln in p.lines:
            for e in ln.events:
                out.setdefault(e.name, []).append(
                    {k: str(v) for k, v in e.stats})
    return out


def test_span_is_a_trace_annotation_with_its_ids(tmp_path):
    sp = obs.span("codec.encode", jnp.ones(3), chain="abs:1|pack:8", n=3,
                  request=None)
    assert isinstance(sp, jax.profiler.TraceAnnotation)

    def work():
        with obs.span("engine.prefill", request=11, tokens=5, slot=None):
            jnp.ones(4).block_until_ready()

    got = traced(work, tmp_path)
    assert got["engine.prefill"] == [{"request": "11", "tokens": "5"}]


def test_span_is_skipped_on_tracer_inputs():
    seen = []

    @jax.jit
    def f(x):
        seen.append(obs.span("codec.encode", 1.0, x))
        return x + 1

    f(jnp.ones(2))
    assert seen and not isinstance(seen[0], jax.profiler.TraceAnnotation)
    with seen[0]:                     # a no-op context, reusable
        pass
    assert isinstance(obs.span("codec.encode", np.ones(2)),
                      jax.profiler.TraceAnnotation)


def test_engine_spans_carry_the_request(tmp_path):
    params = build(TINY).init(jax.random.PRNGKey(0))
    eng = E.DecodeEngine(TINY, params, n_slots=1, seq=256,
                         kv_cfg=KVC.kv_quantizer_config())
    prompt = np.arange(5, dtype=np.int32)
    eng.insert(0, eng.prefill(prompt, request=4), request=4)   # warm
    eng.generate_step()
    eng.release(0)

    def work():
        pre = eng.prefill(prompt, request=7)
        eng.insert(0, pre, request=7)
        eng.generate_step()
        eng.evict(0)

    got = traced(work, tmp_path)
    assert got["engine.prefill"] == [{"request": "7", "tokens": "5"}]
    for name in ("engine.prefill.tokens", "engine.prefill.pack"):
        assert got[name] == [{"request": "7"}], name
    assert got["engine.insert"] == [{"slot": "0", "request": "7"}]
    for name in ("engine.insert.verify", "engine.insert.unpack"):
        assert got[name] == [{"slot": "0", "request": "7"}], name
    assert got["engine.generate_step"] == [{"live": "1"}]
    for name in ("engine.step.slot_check", "engine.step.dispatch"):
        assert len(got[name]) == 1, name
    assert got["engine.evict"] == [{"slot": "0"}]


def test_codec_spans(tmp_path):
    pipe = parse_pipeline("abs:1e-3|pack:8|narrow")
    x = jnp.asarray(np.linspace(-1, 1, 4096, dtype=np.float32))
    enc = pipe.encode(x)
    pipe.decode(enc, n=x.size)                                  # warm

    def work():
        pipe.decode(pipe.encode(x), n=x.size).block_until_ready()

    got = traced(work, tmp_path)
    spec = pipe.spec()
    assert got["codec.encode"] == [{"chain": spec, "n": "4096"}]
    assert got["codec.decode"] == [{"chain": spec, "n": "4096"}]
    assert len(got["codec.decode.check_len"]) == 1
