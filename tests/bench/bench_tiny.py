"""Tiny cells for the benchmark's tests on the CPU: a checkout-like
directory with its own BENCHMARK.json, configuration and traffic files,
and the benchmark's drivers and metric readers linked in unchanged."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}


def _load(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def tiny_configs() -> dict:
    """The real configurations cut to CPU size, every other key kept."""
    field = _load("bench/configs/nyx-512-f32.json")
    field.update(shape=[16, 32, 32], n_special=12)
    model = _load("bench/configs/deepseek-67b-4L.json")
    model.update(name="tiny-llama", hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 num_hidden_layers=2, vocab_size=256)
    grad = _load("bench/configs/deepseek-67b-layer-grad.json")
    grad.update(hidden_size=64, intermediate_size=256, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, bucket_values=4096)
    return {"field": field, "model": model, "grad": grad}


def tiny_traffic() -> dict:
    chat = _load("bench/traffic/chat-closed.json")
    chat.update(slots=3, seq=512, requests=6, check_tokens=40)
    chat["prompt"].update(median=20, min=8, max=200)
    chat["output"].update(median=8, min=4, max=24)
    return {"roundtrip": _load("bench/traffic/roundtrip.json"),
            "grad-reduce": _load("bench/traffic/grad-reduce.json"),
            "chat-closed": chat}


CELLS = [("nyx512-abs-fused", "field", "roundtrip", 1),
         ("ds67b-chat", "model", "chat-closed", 1),
         ("ds67b-grad-reduce-4chip", "grad", "grad-reduce", 4)]


def make_root(tmp: Path, cells=CELLS, configs=None, traffic=None) -> Path:
    """A directory laid out as a checkout: BENCHMARK.json naming `cells`
    (name, config, traffic, chips), their data files, and links to the
    benchmark's drivers and metrics."""
    configs = tiny_configs() if configs is None else configs
    traffic = tiny_traffic() if traffic is None else traffic
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True, exist_ok=True)
    (bench / "traffic").mkdir(parents=True, exist_ok=True)
    for sub in ("drivers", "metrics"):
        if not (bench / sub).exists():
            os.symlink(ROOT / "bench" / sub, bench / sub)
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, tr in traffic.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(tr))
    manifest = _load("BENCHMARK.json")
    manifest["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in configs]
    manifest["workloads"] = [
        {"name": w, "config": c, "traffic": t, "chips": k, "why": "test"}
        for w, c, t, k in cells]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def run_four(script: str, timeout=240) -> dict:
    """Run `script` in a child process with four CPU devices; returns the
    JSON object its last line prints."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
