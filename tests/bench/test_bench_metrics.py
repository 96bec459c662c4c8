"""The per-layer metric readers: their operation and byte counts against
hand counts at a small shape, their arithmetic on a fake run, and silence
(None) where they find nothing to read."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench_tiny import ROOT
from bench import run as R
from bench import trace as T

M = {p.name[:-3]: R.import_file(p)
     for p in sorted((ROOT / "bench" / "metrics").glob("*.py"))}

SMALL = {"hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 3,
         "vocab_size": 10}


def test_every_per_layer_metric_has_a_reader():
    import json
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert {m["name"] for m in manifest["per_layer"]} == set(M)


def test_engine_counts_by_hand():
    # per layer: wq 4x4, wo 4x4, wkv 4x(2*1*2), w1 w3 4x6, w2 6x4
    # -> 16+16+16+72 = 120; 3 layers + tied head 10x4 = 400 weights
    assert M["step_hbm_roofline.engine"].weight_bytes(SMALL) == 2 * 400
    assert M["mfu.engine"].flops(SMALL, tokens=5, attn_ctx=7) == (
        2 * 400 * 5 + 4 * 2 * 2 * 3 * 7)
    # 256 positions over 2 slot-steps: bins 2*3*1*2*256 = 3072 bytes;
    # scales 2*3*1*4*(256/128 + 2) = 96 bytes
    assert M["step_hbm_roofline.engine"].kv_bytes(SMALL, 256, 2) == 3072 + 96


def test_codec_least_bytes():
    assert M["encode_roofline.codec"].least_bytes(1000, 300.0) == 4300.0


def _spans(**secs):
    s = R.Spans()
    for name, durs in secs.items():
        t = 0.0
        for d in durs:
            s.items.append((name, t, t + d))
            t += d
    return s


def _trace(busy_ns, window_ns=1000.0, op="fusion", span=None, module=None):
    evs = [T.Event("/host:CPU", "python", "window", 0.0, window_ns),
           T.Event("/device:TPU:0", T.OPS_LINE, op, 0.0, busy_ns)]
    if module:        # two runs of the program, busy_ns / 2 each
        evs += [T.Event("/device:TPU:0", T.MODULES_LINE, module, t,
                        busy_ns / 2) for t in (0.0, busy_ns / 2)]
    if span:
        evs.append(T.Event("/host:CPU", "python", span, 0.0, window_ns))
    return T.Summary(evs)


def _run(spans=None, counters=None, trace=None, window_s=1.0):
    return SimpleNamespace(spans=spans or R.Spans(), counters=counters or {},
                           trace=trace, config=SMALL, window_s=window_s,
                           peaks={"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3},
                           chips=1)


def test_host_span_means():
    r = _run(_spans(encode=[0.01, 0.03], decode=[0.002], pass_=[]),
             {"buckets": 2})
    assert M["encode_ms.codec"].read(r) == pytest.approx(20.0)
    assert M["decode_ms.codec"].read(r) == pytest.approx(2.0)
    r = _run(_spans(**{"pass": [0.4, 0.6]}), {"buckets": 5})
    assert M["bucket_ms.reduce"].read(r) == pytest.approx(100.0)
    r = _run(_spans(generate_step=[0.01, 0.01], host_tokens=[0.002, 0.002],
                    prefill=[0.3]), {"steps": 2, "prefill_tokens": 100})
    assert M["step_ms.engine"].read(r) == pytest.approx(12.0)
    assert M["prefill_ms_per_token.engine"].read(r) == pytest.approx(3.0)


def test_trace_shares():
    r = _run(trace=_trace(250.0))
    assert M["device_idle.codec"].read(r) == pytest.approx(75.0)
    assert M["device_idle.engine"].read(r) == pytest.approx(75.0)
    r = _run(trace=_trace(400.0, op="all-gather.2"))
    assert M["collective_share.reduce"].read(r) == pytest.approx(100.0)
    # one encode call, 500 ns busy inside it; least bytes 4*10 + 10 = 50
    # at 1e3 B/s = 0.05 s -> far above 100% on this fake peak, as counted
    r = _run(_spans(encode=[0.5]), {"values": 10, "wire_bytes": 10.0},
             _trace(500.0, span="encode"))
    assert M["encode_roofline.codec"].read(r) == pytest.approx(
        100 * 0.05 / 500e-9)


def test_mfu_and_step_roofline():
    counters = {"steps": 2, "decode_tokens": 4, "prefill_tokens": 1,
                "attn_ctx": 7, "kv_ctx": 256, "slot_steps": 2}
    r = _run(counters=counters, window_s=2.0,
             trace=_trace(500.0, module="jit__slots_step(123)"))
    f = M["mfu.engine"].flops(SMALL, 5, 7)
    assert M["mfu.engine"].read(r) == pytest.approx(100 * f / 2.0 / 1e3)
    # per step: the weights once, half of the KV bytes; 250 ns a run
    least = 2 * 400 + (3072 + 96) / 2
    assert M["step_hbm_roofline.engine"].read(r) == pytest.approx(
        100 * least / 1e3 / 250e-9)


@pytest.mark.parametrize("name", sorted(M))
def test_silent_without_anything_to_read(name):
    counters = {"buckets": 0, "steps": 0, "prefill_tokens": 0,
                "decode_tokens": 0, "values": 0, "wire_bytes": 0.0}
    assert M[name].read(_run(counters=counters)) is None
