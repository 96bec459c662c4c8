"""The check that decides `correct`, shown to fail: a run driven through
the harness with the timed path broken underneath comes out not correct
(an answer or a token altered where it is produced, a step that leaves its
state unchanged, the exchange between chips left out), and so does each
cell's control, at sizes a test run can hold."""
from __future__ import annotations

import pytest

from bench_tiny import CPU_PEAKS, ROOT, make_root, run_four
from bench import controls as C, run as R

SEED = 2 ** 31 + 11


def _run(root, workload):
    return R.run_cell(workload, SEED, 1.0, False, root=root,
                      require_tpu=False, peaks=CPU_PEAKS)


def _control(root, workload):
    """The cell's check on the program and on its control, same inputs,
    as `bench/controls.py` reads them."""
    line = C.read(workload, SEED, 0.5, root=root, require_tpu=False,
                  peaks=CPU_PEAKS)
    return line["program"], line["control"]


def test_codec_answer_altered_is_not_correct(tmp_path, monkeypatch):
    from repro.core.pipeline import Pipeline
    decode = Pipeline.decode

    def altered(self, *a, **kw):
        y = decode(self, *a, **kw)
        return y.reshape(-1).at[7].add(0.01).reshape(y.shape)

    monkeypatch.setattr(Pipeline, "decode", altered)
    res = _run(make_root(tmp_path), "nyx512-abs-fused")
    assert res["correct"] is False
    assert res["checks"]["values outside the bound"]["value"] >= 1


def test_codec_control_is_not_correct(tmp_path):
    prog, ctl = _control(make_root(tmp_path), "nyx512-abs-fused")
    assert [c["value"] for c in prog] == [0, 0]
    assert ctl[0]["value"] > ctl[0]["limit"]


def _engine_fault(monkeypatch, fault):
    from repro.models.engine import DecodeEngine
    step = DecodeEngine.generate_step

    def broken(self):
        before = (self._cache, self._pos, self._tok)
        logits, toks = step(self)
        if fault == "token":
            return logits, (toks + 1) % self.cfg.vocab
        self._cache, self._pos, self._tok = before       # state unchanged
        return logits, toks

    monkeypatch.setattr(DecodeEngine, "generate_step", broken)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_engine_fault_is_not_correct(tmp_path, monkeypatch, fault):
    _engine_fault(monkeypatch, fault)
    res = _run(make_root(tmp_path), "ds67b-chat")
    gap = res["checks"]["widest served-token gap"]
    assert res["correct"] is False and gap["value"] > gap["limit"]


def test_engine_control_readings_run(tmp_path):
    prog, ctl = _control(make_root(tmp_path), "ds67b-chat")
    assert prog[0]["value"] <= prog[0]["limit"]
    assert [c["name"] for c in ctl][:2] == [c["name"] for c in prog]


def test_engine_control_is_not_correct():
    """The fp8 reference in the program's place, over 1000 positions of a
    model whose logits spread as the real cell's do (rms ~0.3): the mean
    gap of the tokens it puts first passes the cell's limit."""
    import json

    import numpy as np

    from bench.reference import llama
    engine = R.import_file(ROOT / "bench" / "drivers" / "engine.py")
    from bench_tiny import tiny_configs
    cfg = dict(tiny_configs()["model"], hidden_size=512,
               intermediate_size=1024, head_dim=128, vocab_size=8192)
    limit = json.loads((ROOT / "bench" / "traffic" / "chat-closed.json")
                       .read_text())["mean_gap_limit"]
    params = engine.make_weights(cfg, SEED)
    seq = np.random.default_rng(SEED).integers(0, 8192, 1000).astype(
        np.int32)
    low = llama.low_argmax(cfg, params, seq, 1000, 1024)
    gaps = llama.token_gaps(cfg, params, seq, low, 1024)
    exact = llama.token_gaps(cfg, params, seq, llama.low_argmax(
        cfg, params, seq, 1000, 1024, fp8=False), 1024)
    assert np.max(exact) == 0.0            # f32 against itself
    assert np.mean(gaps) > limit


FAULTS_SCRIPT = r"""
import json, sys
from pathlib import Path
sys.path.insert(0, {tests!r})
import jax.numpy as jnp
from bench_tiny import CPU_PEAKS
from bench import controls as C, run as R
import repro.compression.grads as G

root = Path({root!r})
line = C.read("ds67b-grad-reduce-4chip", {seed}, 0.5, root=root,
              require_tpu=False, peaks=CPU_PEAKS)
out = {{"program": line["program"][0], "control": line["control"][0]}}
del line

tree, shard_fn = G.compressed_mean_tree, G.compress_shard
def local(grads, residuals, *a, **kw):       # the exchange left out
    return grads, residuals
def altered(*a, **kw):                       # an answer altered
    (m,), r = tree(*a, **kw)
    return (m.at[3].add(1.0),), r
def overflowing(*a, **kw):                   # raw f32 summed instead
    shard, q = shard_fn(*a, **kw)
    shard.enc = shard.enc._replace(overflow=jnp.ones((), bool))
    return shard, q
for name, fn, sfn in (("sound", tree, shard_fn),
                      ("exchange", local, shard_fn),
                      ("answer", altered, shard_fn),
                      ("fallback", tree, overflowing)):
    G.compressed_mean_tree, G.compress_shard = fn, sfn
    res = R.run_cell("ds67b-grad-reduce-4chip", {seed}, 0.5, False,
                     root=root, require_tpu=False, peaks=CPU_PEAKS)
    out[name] = [res["correct"],
                 res["checks"]["worst error over the bound"]["value"],
                 res["metrics"]["ratio"]["value"]]
G.compressed_mean_tree, G.compress_shard = tree, shard_fn
print(json.dumps(out))
"""


def test_reduce_faults_and_control_are_not_correct(tmp_path):
    out = run_four(FAULTS_SCRIPT.format(
        tests=str(ROOT / "tests" / "bench"), root=str(make_root(tmp_path)),
        seed=SEED), timeout=400)
    assert out["program"]["value"] <= out["program"]["limit"]
    assert out["control"]["value"] > out["control"]["limit"]
    for fault in ("exchange", "answer"):
        correct, worst, _ = out[fault]
        assert correct is False and worst > 1.0, (fault, out)
    # `ratio` reads what the timed program shipped: a bucket summed as raw
    # f32 (overflow) or never handed to the transport counts 32 bits a
    # value, though the first's mean is exact and so correct
    assert out["sound"][0] is True and out["sound"][2] > 1.5, out
    assert out["fallback"][0] is True and out["fallback"][1] < 0.01, out
    assert out["fallback"][2] == 1.0 and out["exchange"][2] == 1.0, out


def test_tiny_cells_hold_the_real_limits():
    """The tests above judge the tiny cells by the real cells' limits."""
    import json
    for mix in ("roundtrip", "chat-closed", "grad-reduce"):
        real = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                          .read_text())
        from bench_tiny import tiny_traffic
        tiny = tiny_traffic()[mix]
        for key in ("gap_limit", "mean_gap_limit", "error_bound", "mode",
                    "chain"):
            assert tiny.get(key) == real.get(key)
