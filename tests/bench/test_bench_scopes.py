"""The readers of the program's own marks: op scopes (`bench/scopes.py`:
`hlo_op_names`, `scope_parts`, the scopes noted when a trace loads,
`scope_seconds`), device-idle time inside program spans and under JAX's
compile events, and the split of idle time by the innermost mark
(`bench/attribution.py`), on hand-built traces whose answers are counted
by hand."""
from __future__ import annotations

import itertools
from types import SimpleNamespace

import pytest

from bench_tiny import ROOT
from bench import attribution as A
from bench import run as R
from bench import scopes as S
from bench import trace as T

M = {p.name[:-3]: R.import_file(p)
     for p in sorted((ROOT / "bench" / "metrics").glob("*.py"))}
H, D0, D1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
SCOPE: dict[str, str] = {}      # instruction -> scope of the hand-built ops
_N = itertools.count()


def op(plane, start, dur, scope, name="fusion"):
    name = f"{name}.{next(_N)}"
    SCOPE[name] = scope
    return T.Event(plane, T.OPS_LINE, name, float(start), float(dur))


def host(name, start, dur):
    return T.Event(H, "python", name, float(start), float(dur))


def summary(events):
    """The Summary of a hand-built trace, each op's scope noted as a loaded
    trace's would be: by instruction, under whichever module ran it."""
    mods = {e.name for e in events if e.line == T.MODULES_LINE} | {""}
    ops = {e.name: SCOPE[e.name] for e in events if e.line == T.OPS_LINE}
    S.note(events, {m: ops for m in mods})
    return T.Summary(events)


def engine_trace():
    # window [0, 1000) on one device.  Busy: [0,100) attn, [300,500)
    # kv_dequant inside attn, [650,800) ffn, [850,950) lm_head: 550 ns.
    # Idle gaps: [100,300), [500,650), [800,850), [950,1000): 450 ns.
    return [
        host("window", 0, 1000),
        op(D0, 0, 100, "jit(_slots_step)/serve.attn/dot_general"),
        op(D0, 300, 200, "jit(_one_step)/vmap(serve.attn)/serve.kv_dequant/"
                         "convert_element_type"),
        op(D0, 650, 150, "jit(_slots_step)/serve.layers/serve.ffn/dot"),
        op(D0, 850, 100, "jit(_slots_step)/serve.lm_head/dot_general"),
        host("prefill", 90, 420),                    # driver
        host("engine.prefill", 100, 400),            # program
        host("lower_sharding_computation", 150, 100),
        host("$compiler.py:396 compile_or_get_cached", 250, 30),
        host("backend_compile_and_load", 260, 10),   # nested in the lookup
        host("admit", 505, 95),                      # driver
        host("engine.generate_step", -50, 60),       # starts before window
        host("engine.generate_step", 600, 100),
        host("engine.step.slot_check", 600, 50),
        host("PjitFunction(_slots_step)", 650, 10),  # while busy
        host("engine.generate_step", 800, 100),
        host("$foo.py:1 bar", 940, 60),              # Python tracer: no mark
    ]


def reduce_trace():
    # two devices; op time d0 450 + d1 300 = 375 ns a device
    return [
        host("window", 0, 1000),
        op(D0, 0, 100, "jit(body)/grads.compress/codec.quantize_pack/round"),
        op(D0, 100, 200, "jit(body)/transport.gather/codec.word_stages/"
                         "narrow/gather"),
        op(D0, 300, 100, "jit(body)/transport.gather/codec.dequantize/mul"),
        op(D0, 400, 50, "jit(body)/grads.compress/codec.word_stages/narrow/"
                        "codec.compact/scatter"),
        op(D1, 0, 200, "jit(body)/transport.gather/all-gather",
           name="all-gather.1"),
        op(D1, 200, 100, "jit(body)/grads.compress/codec.compact/nonzero"),
    ]


def run_of(events):
    return SimpleNamespace(trace=summary(events))


def test_scope_parts_take_off_transformations():
    parts = S.scope_parts("jit(f)/transpose(jvp(serve.attn))/"
                          "vmap(jit(_one_step))/dot_general")
    assert parts == {"f", "serve.attn", "_one_step", "dot_general"}
    assert S.scope_parts("") == frozenset()
    assert "codec.compact" not in S.scope_parts("jit(f)/codec.compact_x/a")


def test_event_scope_is_optional():
    # loading a trace gives trace.load's events, unchanged; the scopes
    # ride beside them, and an op never noted reads as unscoped
    assert T.load is S.load and S.load.__wrapped__ is not S.load
    e = T.Event(D0, T.OPS_LINE, "fusion", 0.0, 1.0)
    S.note([], {})
    assert S.scope_of(e) == "" and S.scope_of(e._replace(name="m:x")) == ""


def test_scope_seconds():
    s = summary(engine_trace())
    assert S.scope_seconds(s, "serve.attn") == pytest.approx(300e-9)
    assert S.scope_seconds(s, "serve.kv_dequant") == pytest.approx(200e-9)
    assert S.scope_seconds(s, "serve.layers") == pytest.approx(150e-9)
    assert S.scope_seconds(s, "serve") == 0.0
    r = summary(reduce_trace())
    # mean over the two devices
    assert S.scope_seconds(r, "transport.gather") == pytest.approx(250e-9)


def test_note_takes_the_module_running_each_op():
    # one instruction name in two programs, each with its own scope; an
    # op outside every module run, or of a program without HLO, has none
    events = [host("window", 0, 100),
              T.Event(D0, T.MODULES_LINE, "jit_a(1)", 0.0, 40.0),
              T.Event(D0, T.MODULES_LINE, "jit_b(2)", 50.0, 40.0),
              T.Event(D1, T.MODULES_LINE, "jit_c(3)", 0.0, 90.0)]
    ops = [T.Event(D0, T.OPS_LINE, "%fusion.1 = f32[4] fusion(%p)", t,
                   10.0) for t in (10.0, 60.0, 45.0)]
    ops.append(T.Event(D1, T.OPS_LINE, "fusion.1", 10.0, 10.0))
    S.note(events + ops, {"jit_a(1)": {"fusion.1": "jit(a)/codec.compact"},
                          "jit_b(2)": {"fusion.1": "jit(b)/serve.attn"}})
    s = T.Summary(events + ops)
    got = {(e.plane, e.start_ns): S.scope_of(e) for v in s.ops.values()
           for e in v}
    assert got == {(D0, 10.0): "jit(a)/codec.compact",
                   (D0, 60.0): "jit(b)/serve.attn", (D0, 45.0): "",
                   (D1, 10.0): ""}
    S.note([], {})
    assert S.scope_of(ops[0]) == ""


def test_engine_readers_by_hand():
    run = run_of(engine_trace())
    # attention: 300 of 550 ns of op time
    assert M["attn_share.engine"].read(run) == pytest.approx(
        100 * 300 / 550)
    # engine.prefill [100,500): busy [300,500) -> idle 200 of 400
    assert M["prefill_idle.engine"].read(run) == pytest.approx(50.0)
    # generate_step: idle [600,650) + [800,850) over the 2 steps that start
    # in the window (the one from -50 is busy where it is inside it)
    assert M["step_host_idle_ms.engine"].read(run) == pytest.approx(50e-6)
    # compile events: union [150,280) is idle all through: 130 ns
    assert M["compile_idle_ms.engine"].read(run) == pytest.approx(130e-6)


def test_codec_and_reduce_readers_by_hand():
    run = run_of(reduce_trace())
    # codec.compact: d0 50 + d1 100 over 2 devices = 75 of 375 ns
    assert M["compact_share.codec"].read(run) == pytest.approx(20.0)
    # word stages: d0 200 + 50 -> 125 of 375
    assert M["word_stages_share.codec"].read(run) == pytest.approx(
        100 * 125 / 375)
    # gather: d0 300 + d1 200 -> 250 of 375
    assert M["gather_share.reduce"].read(run) == pytest.approx(
        100 * 250 / 375)


def test_idle_by_innermost_mark():
    s = summary(engine_trace())
    got = A.idle_by_span(s, ["window", "prefill", "admit", "host_tokens"])
    want = {"engine.prefill": 70e-9, "lower_sharding_computation": 100e-9,
            "$compiler.py:396 compile_or_get_cached": 20e-9,
            "backend_compile_and_load": 10e-9, "prefill": 5e-9,
            "admit": 95e-9, "engine.step.slot_check": 50e-9,
            "engine.generate_step": 50e-9, "none": 50e-9}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)


def test_scope_seconds_by_module():
    events = engine_trace() + [
        T.Event(D0, T.MODULES_LINE, "jit__slots_step(1)", 0.0, 100.0),
        T.Event(D0, T.MODULES_LINE, "jit__slots_step(1)", 640.0, 320.0)]
    got = A.scope_seconds_by_module(summary(events))
    step = got.pop("jit__slots_step")
    assert step["total"] == pytest.approx(350e-9)
    assert step["scoped"] == pytest.approx(350e-9)
    assert step["scopes"]["serve.ffn"] == pytest.approx(150e-9)
    # the op run outside both is listed under its own name
    (other,) = got.values()
    assert other["scopes"] == {"serve.attn": pytest.approx(200e-9),
                               "serve.kv_dequant": pytest.approx(200e-9)}


@pytest.mark.parametrize("name", ["attn_share.engine", "compact_share.codec",
                                  "word_stages_share.codec",
                                  "gather_share.reduce",
                                  "prefill_idle.engine",
                                  "step_host_idle_ms.engine"])
def test_readers_silent_without_the_programs_marks(name):
    # a program without scopes or spans: ops carry only their jit path
    events = [host("window", 0, 1000), op(D0, 0, 500, "jit(f)/cumsum")]
    assert M[name].read(run_of(events)) is None
    assert M[name].read(SimpleNamespace(trace=None)) is None


def test_compile_idle_reads_zero_without_compiles():
    events = [host("window", 0, 1000), op(D0, 0, 500, "jit(f)/cumsum")]
    assert M["compile_idle_ms.engine"].read(run_of(events)) == 0.0
    assert M["compile_idle_ms.engine"].read(
        SimpleNamespace(trace=None)) is None


def test_busy_covered_matches_the_plain_sum():
    busy = [(0.0, 10.0), (20.0, 30.0), (40.0, 45.0)]
    b = A.Busy(busy)
    for lo, hi in [(0, 50), (5, 25), (12, 18), (25, 42), (44, 100),
                   (-5, 3), (30, 40)]:
        assert b.covered(lo, hi) == pytest.approx(T.covered(busy, lo, hi))


# --- a serialized XSpace by hand: the HLO of one program on /host:metadata

def _key(field, wire):
    return _vint(field << 3 | wire)


def _vint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _msg(field, *parts):
    body = b"".join(parts)
    return _key(field, 2) + _vint(len(body)) + body


def _str(field, s):
    return _msg(field, s.encode())


def _int(field, v):
    return _key(field, 0) + _vint(v)


def _packed(field, vals):
    return _msg(field, b"".join(_vint(v) for v in vals))


def _inst(iid, name, op_name="", operands=(), calls=()):
    parts = [_str(1, name), _int(35, iid)]
    if op_name:
        parts.append(_msg(7, _str(2, op_name)))
    if operands:
        parts.append(_packed(36, operands))
    if calls:
        parts.append(_packed(38, calls))
    return _msg(2, *parts)


def hand_xspace():
    fused = _msg(3, _int(5, 2), _int(6, 11),
                 _inst(10, "param.1"),
                 _inst(11, "cumsum.1", "jit(f)/codec.compact/cumsum",
                       operands=[10]))
    entry = _msg(3, _int(5, 1), _int(6, 4),
                 _inst(1, "x", "x"),
                 _inst(2, "copy.1", operands=[1]),             # no scope
                 _inst(3, "fusion.1", "", operands=[2], calls=[2]),
                 _inst(4, "copy.2", "reduce_window_sum", operands=[3]))
    hlo = _msg(1, entry, fused)                     # HloProto.hlo_module
    stat = _msg(5, _int(1, 7), _msg(6, hlo))        # XStat bytes_value
    md = _msg(4, _int(1, 5), _msg(2, _str(2, "jit_f(99)"), stat))
    smd = _msg(5, _int(1, 7), _msg(2, _int(1, 7), _str(2, "Hlo Proto")))
    meta = _msg(1, _str(2, "/host:metadata"), md, smd)
    other = _msg(1, _str(2, "/device:TPU:0"), _msg(3, _str(2, "XLA Ops")))
    return other + meta


def test_hlo_op_names_from_a_hand_built_xspace():
    got = S.hlo_op_names(hand_xspace())
    assert list(got) == ["jit_f(99)"]
    ops = got["jit_f(99)"]
    # the fusion takes its fused root's scope; copies of it take theirs
    # from their operand; a parameter's copy has none to take
    assert ops == {"param.1": "", "cumsum.1": "jit(f)/codec.compact/cumsum",
                   "x": "", "copy.1": "",
                   "fusion.1": "jit(f)/codec.compact/cumsum",
                   "copy.2": "jit(f)/codec.compact/cumsum"}


def test_hlo_op_names_of_a_recorded_trace(tmp_path):
    import glob
    import os

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("codec.compact"):
            y = jnp.cumsum(x)
        with jax.named_scope("serve.attn"):
            return jnp.sin(y) * 2

    x = jnp.ones(256)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    with open(path, "rb") as fh:
        got = S.hlo_op_names(fh.read())
    (mod,) = [m for m in got if m.startswith("jit_f(")]
    parts = set().union(*(S.scope_parts(s) for s in got[mod].values()))
    assert {"codec.compact", "serve.attn"} <= parts


def test_load_notes_each_device_ops_scope(tmp_path):
    # an XSpace file with one device plane (a module run of jit_f(99) and
    # one op in it, fusion.1) beside hand_xspace's HLO of that program
    def md(i, name):        # XPlane.event_metadata map entry
        return _msg(4, _int(1, i), _msg(2, _int(1, i), _str(2, name)))

    def line(i, name, mid, off_ps, dur_ps):
        return _msg(3, _int(1, i), _str(2, name), _int(3, 1000),
                    _msg(4, _int(1, mid), _int(2, off_ps), _int(3, dur_ps)))

    dev = _msg(1, _int(1, 2), _str(2, D0),
               line(1, T.MODULES_LINE, 1, 0, 9_000_000),
               line(2, T.OPS_LINE, 2, 1_000_000, 2_000_000),
               md(1, "jit_f(99)"), md(2, "fusion.1"))
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(dev + hand_xspace())
    events = T.load(str(tmp_path))
    (e,) = [e for e in events if e.line == T.OPS_LINE]
    assert e.name == "fusion.1" and e.start_ns == 2000.0
    assert S.scope_of(e) == "jit(f)/codec.compact/cumsum"
    assert S.scope_of(e._replace(name="jit_f:fusion.1")) == S.scope_of(e)
