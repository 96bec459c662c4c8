"""bench/run.py end to end on the CPU at tiny sizes: each driver runs for
one second through the harness's internal API and prints a result line of
the contract's shape; a new cell is data alone; without a TPU, or without
the program, the command prints no result and exits nonzero."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import CPU_PEAKS, ROOT, make_root, run_four, \
    tiny_configs, tiny_traffic
from bench import run as R

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(root, workload, trace=False, seed=2 ** 31 + 5):
    return R.run_cell(workload, seed, 1.0, trace, root=root,
                      require_tpu=False, peaks=CPU_PEAKS)


def _shape(res, names, chips=1):
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    d = res["device"]
    assert d["platform"] == "cpu" and d["count"] == chips
    assert "memory_peak_bytes" in d
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def _e2e(workload):
    cell = R.load_cell(ROOT, workload)
    return [m["name"] for m in cell.e2e]


def test_codec_cell_one_second(tmp_path):
    root = make_root(tmp_path)
    _shape(_run(root, "nyx512-abs-fused"), _e2e("nyx512-abs-fused"))
    res = _run(root, "nyx512-abs-fused", trace=True)
    # no device plane on the CPU: only the host-clock readers report
    assert set(res["metrics"]) == {"encode_ms.codec", "decode_ms.codec"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_engine_cell_one_second(tmp_path):
    root = make_root(tmp_path)
    res = _run(root, "ds67b-chat")
    _shape(res, _e2e("ds67b-chat"))
    assert res["checks"]["compiles in window"]["value"] == 0


REDUCE_SCRIPT = r"""
import json, sys
from pathlib import Path
sys.path.insert(0, {tests!r})
from bench_tiny import CPU_PEAKS
from bench import run as R
res = R.run_cell("ds67b-grad-reduce-4chip", 2**31 + 5, 1.0, False,
                 root=Path({root!r}), require_tpu=False, peaks=CPU_PEAKS)
print(json.dumps(res))
"""


def test_reduce_cell_one_second_on_four_devices(tmp_path):
    root = make_root(tmp_path)
    res = run_four(REDUCE_SCRIPT.format(tests=str(ROOT / "tests" / "bench"),
                                        root=str(root)))
    _shape(res, _e2e("ds67b-grad-reduce-4chip"), chips=4)


def test_a_new_cell_is_data_alone(tmp_path):
    """A cell, its configuration and its traffic mix that no existing file
    names, found and run by name."""
    configs = tiny_configs()
    field = dict(configs["field"], name="my-field", shape=[8, 16, 64])
    mix = {"driver": "codec", "chain": "sci-rel-narrow", "mode": "rel",
           "error_bound": 0.001}
    root = make_root(tmp_path, cells=[("my-new-cell", "my-field",
                                       "my-roundtrip", 1)],
                     configs={"my-field": field},
                     traffic={"my-roundtrip": mix})
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for m in manifest["end_to_end"]:
        if "nyx512-abs-fused" in m.get("workloads", []):
            m["workloads"].append("my-new-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res = _run(root, "my-new-cell")
    _shape(res, ["setup_s", "codec_GBps", "ratio"])


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nyx512-abs-fused",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_without_a_tpu_prints_nothing_and_fails():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_cli_without_the_program_prints_nothing_and_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(R.NoChip):
        R.device_peaks("TPU v9 imaginary", json.loads(
            (ROOT / "bench" / "peaks.json").read_text()))
    assert R.device_peaks("TPU v5 lite", json.loads(
        (ROOT / "bench" / "peaks.json").read_text()))["hbm_bytes_per_s"] > 0


def test_run_has_no_branch_on_names():
    """The harness names no cell, configuration, mix or metric."""
    src = (ROOT / "bench" / "run.py").read_text()
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = ([w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"]
                if m["name"] != "setup_s"]
             + [m["name"] for m in manifest["per_layer"]])
    assert [n for n in names
            if re.search(rf"(?<![\w.-]){re.escape(n)}(?![\w.-])", src)] == []
    assert tiny_traffic()                      # the tiny cells exist
