"""bench/trace.py: the reduction from trace events to busy time, per-op
time, device time inside host spans, idle gaps and the breakdown, on a
hand-built trace whose answers are counted by hand."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import trace as T

H, D0, D1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"


def ev(plane, name, start, dur, line=None):
    return T.Event(plane, line or (T.OPS_LINE if plane != H else "python"),
                   name, float(start), float(dur))


def hand_trace():
    # window [100, 1100); host spans: encode [100, 500), decode [600, 1000)
    # device 0 ops: [50, 150) clipped to [100, 150), [140, 300), [700, 800)
    # device 1 ops: [200, 400) all-gather, [900, 1200) clipped to [900, 1100)
    return [
        ev(H, "window", 100, 1000),
        ev(H, "encode", 100, 400), ev(H, "decode", 600, 400),
        ev(D0, "fusion.1", 50, 100), ev(D0, "fusion.2", 140, 160),
        ev(D0, "fusion.1", 700, 100),
        ev(D1, "all-gather.3", 200, 200), ev(D1, "fusion.1", 900, 300),
    ]


def test_union_and_clip():
    assert T.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3),
                                                                  (5, 7)]
    assert T.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert T.covered([(0, 3), (5, 7)], 1, 6) == 3


def test_busy_and_window():
    s = T.Summary(hand_trace())
    assert s.devices == [D0, D1]
    assert s.window_s == pytest.approx(1000e-9)
    # device 0: [100,300) + [700,800) = 300; device 1: 200 + 200 = 400
    assert s.busy_s == pytest.approx(350e-9)


def test_busy_inside_spans():
    s = T.Summary(hand_trace())
    # encode [100,500): d0 200, d1 200; decode [600,1000): d0 100, d1 100
    assert s.busy_in("encode") == pytest.approx(200e-9)
    assert s.busy_in("decode") == pytest.approx(100e-9)
    assert s.busy_in("absent") == 0.0


def test_op_seconds_and_collectives():
    s = T.Summary(hand_trace())
    ops = s.op_seconds()
    # fusion.1: d0 50 + 100, d1 200 -> 350 / 2 devices
    assert ops["fusion.1"] == pytest.approx(175e-9)
    assert ops["all-gather.3"] == pytest.approx(100e-9)
    assert "jit_f" not in ops                 # modules are not ops
    assert s.collective_s() == pytest.approx(100e-9)


def test_gaps_named_by_innermost_host_span():
    s = T.Summary(hand_trace())
    gaps = s.gaps(["window", "encode", "decode"])
    # device 0 idle: [300,700) mid 500 -> window only (encode ends at 500)
    # and [800,1100) mid 950 -> decode
    assert gaps == [("window", pytest.approx(400e-9)),
                    ("decode", pytest.approx(300e-9))]
    b = s.breakdown(["window", "encode", "decode"], top=1)
    assert b["device_ops"] == [["fusion.1", pytest.approx(175e-9)]]
    assert b["idle_gaps"] == [["window", pytest.approx(400e-9)]]


def test_ops_are_named_by_their_module():
    assert T.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] %p), kind=kLoop",
                     "jit_encode(123)") == "jit_encode:fusion.3"
    assert T.op_name("%all-gather.1 = u32[4,8] all-gather(...)") == \
        "all-gather.1"
    s = T.Summary(hand_trace() + [
        ev(D0, "jit_f(99)", 0, 200, line=T.MODULES_LINE)])
    ops = s.op_seconds()
    # the first two device-0 ops start inside jit_f; the third does not
    assert ops["jit_f:fusion.1"] == pytest.approx(25e-9)
    assert ops["jit_f:fusion.2"] == pytest.approx(80e-9)
    assert ops["fusion.1"] == pytest.approx(150e-9)


def test_nested_ops_count_once():
    # a loop [0, 100) running two ops; a lone op after it
    ops = [ev(D0, "while.1", 0, 100), ev(D0, "fusion.1", 10, 30),
           ev(D0, "fusion.2", 50, 40), ev(D0, "fusion.3", 120, 10)]
    assert [e.name for e in T.leaves(ops)] == ["fusion.1", "fusion.2",
                                               "fusion.3"]
    s = T.Summary([ev(H, "window", 0, 200)] + ops)
    assert s.busy_s == pytest.approx(110e-9)
    assert sum(s.op_seconds().values()) == pytest.approx(80e-9)


def test_no_device_reads_nothing():
    s = T.Summary([ev(H, "window", 0, 10)])
    assert s.devices == [] and s.busy_s == 0.0 and s.gaps(["window"]) == []


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.Summary([ev(D0, "fusion", 0, 10)])


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("encode"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = T.load(str(tmp_path))
    s = T.Summary(events)
    assert [e.name for e in s.host if e.name == "encode"] == ["encode"]
    assert s.window_s > 0
