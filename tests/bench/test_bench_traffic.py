"""The traffic generators: deterministic per seed, the same set of lengths
for every seed, and lengths that follow the cell's parameters."""
from __future__ import annotations

import json
import statistics

import numpy as np
import pytest

from bench_tiny import ROOT, tiny_configs
from bench import run as R

ENGINE = R.import_file(ROOT / "bench" / "drivers" / "engine.py")
CODEC = R.import_file(ROOT / "bench" / "drivers" / "codec.py")
REDUCE = R.import_file(ROOT / "bench" / "drivers" / "reduce.py")
BIG_SEED = 2 ** 31 + 977


def _traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def _stream(tr, seed, n):
    tg = ENGINE.Traffic(tr, 102400, seed)
    return [tg.next() for _ in range(n)]


@pytest.mark.parametrize("mix", ["chat-closed", "batchgen-closed"])
def test_engine_stream_deterministic_per_seed(mix):
    tr = _traffic(mix)
    a, b = _stream(tr, BIG_SEED, 5), _stream(tr, BIG_SEED, 5)
    assert [(len(r.prompt), r.out_len) for r in a] == [
        (len(r.prompt), r.out_len) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _stream(tr, BIG_SEED + 1, 5)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["chat-closed", "batchgen-closed"])
def test_engine_same_lengths_for_every_seed(mix):
    tr = _traffic(mix)
    n = tr["requests"]
    runs = [[(len(r.prompt), r.out_len) for r in _stream(tr, s, n)]
            for s in (1, 2, BIG_SEED)]
    assert runs[0] == runs[1] == runs[2]
    assert runs[0] != sorted(runs[0])          # shuffled, by order_seed
    # the stream cycles through the set
    two = _stream(tr, 1, 2 * n)
    assert [(len(r.prompt), r.out_len) for r in two[n:]] == runs[0]


@pytest.mark.parametrize("mix", ["chat-closed", "batchgen-closed"])
def test_engine_lengths_follow_the_parameters(mix):
    tr = _traffic(mix)
    reqs = _stream(tr, 3, tr["requests"])
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.out_len for r in reqs])
    ps, os_ = tr["prompt"], tr["output"]
    assert p.min() >= ps["min"] and p.max() <= ps["max"]
    assert o.min() >= os_["min"] and np.all(p + o <= tr["seq"])
    if ps["dist"] == "lognormal":
        assert abs(statistics.median(p) / ps["median"] - 1) < 0.15
    if os_["dist"] == "lognormal":
        assert abs(statistics.median(o) / os_["median"] - 1) < 0.15
    else:                               # uniform up to seq - prompt
        assert o.max() > os_["min"] + 0.8 * (tr["seq"] - ps["max"]
                                             - os_["min"])


def test_lognormal_quantiles_by_hand():
    spec = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 10,
            "max": 500}
    assert ENGINE.length_at(spec, 0.5, 10_000) == 100
    assert ENGINE.length_at(spec, 0.8413447, 10_000) == 272   # e^1 * 100
    assert ENGINE.length_at(spec, 0.999, 10_000) == 500       # clipped
    assert ENGINE.length_at(spec, 0.999, 300) == 300          # seq cap
    uni = {"dist": "uniform", "min": 10, "max": 110}
    assert ENGINE.length_at(uni, 0.25, 10_000) == 35
    assert ENGINE.length_at(uni, 0.5, 60) == 35                # to the cap


def test_codec_field_deterministic_with_specials():
    cfg = tiny_configs()["field"]
    a = np.asarray(CODEC.make_field(BIG_SEED, cfg))
    b = np.asarray(CODEC.make_field(BIG_SEED, cfg))
    assert a.shape == tuple(cfg["shape"]) and a.dtype == np.float32
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))
    bits = a.view(np.uint32).reshape(-1)
    for pat in cfg["special_bits"]:
        assert np.sum(bits == pat) == cfg["n_special"] // len(
            cfg["special_bits"])
    c = np.asarray(CODEC.make_field(BIG_SEED + 1, cfg))
    assert not np.array_equal(a.view(np.uint32), c.view(np.uint32))


def test_reduce_buckets_of_the_real_layer():
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "deepseek-67b-layer-grad.json").read_text())
    sizes = REDUCE.buckets(cfg)
    # 165 buckets of 16 MiB (wq 16, wkv 4, wo 16, w1 w2 w3 43 each) and
    # the two norm scales as one bucket each
    assert len(sizes) == 167
    assert sizes.count(4 * 2 ** 20) == 165
    assert sum(sizes) == sum(REDUCE.leaf_sizes(cfg).values()) == 692_076_544
    assert sorted(set(sizes)) == [8192, 4 * 2 ** 20]
