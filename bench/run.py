#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in BENCHMARK.json.  Its configuration file and
its traffic file (`bench/traffic/<traffic>.json`) are data; the traffic
file names the driver (`bench/drivers/<driver>.py`) that makes the inputs
from the seed, drives the program and checks what it produced against a
plain reference.  Each per-layer metric is read by `bench/metrics/<name>.py`.
Nothing here depends on which cell, configuration or metric it runs.

The run builds its inputs and weights on the device, warms up every shape
the window uses (that is `setup_s`), measures for `--seconds`, reads the
peak device memory, frees the program's state, runs the check, and prints
the numbers compared beside their limits on stderr, then one JSON line on
stdout.  With `--trace 1` the window runs under the profiler and the
line carries the per-layer metrics, the device's busy time and a
breakdown.  Without a TPU, or with fewer chips than the cell asks for, it
exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace as T  # noqa: E402

OUT_DIR = ".bench_out"          # traces, inside the checkout (gitignored)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Spans:
    """Host spans around calls into the program: each is timed on the host
    clock and, while the profiler runs, written into its trace as a
    `TraceAnnotation` of the same name."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.items if n == name]

    def names(self) -> list[str]:
        return sorted({n for n, _, _ in self.items})


def import_file(path: Path):
    """Import a module from a file path (metric files carry dots in their
    names, so they are not importable by module name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> SimpleNamespace:
    """Everything BENCHMARK.json and the data files say about one cell."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in "
                         f"{root / 'BENCHMARK.json'}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    bench = root / "bench"
    traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return SimpleNamespace(
        chips=int(cell["chips"]), config=config,
        traffic=traffic, e2e=e2e, per_layer=per_layer,
        driver=import_file(bench / "drivers" / f"{traffic['driver']}.py"),
        metrics={m["name"]: import_file(bench / "metrics" / f"{m['name']}.py")
                 for m in per_layer})


def chip_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def device_peaks(kind: str, table: dict) -> dict:
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json;"
                     f" have {sorted(k for k in table if k != 'source')}")
    return table[kind]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileCounter:
    """Counts backend compilations while `on` is set (none belongs in the
    window)."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if self.on and event.endswith("backend_compile_duration"):
            self.count += 1


def drive(workload: str, seed: int, seconds: float, trace: bool, *,
          root: Path = ROOT, require_tpu: bool = True,
          peaks: dict | None = None) -> SimpleNamespace:
    """Set up, warm up, measure, free and check one cell: the sequence that
    every run and every control reading (`bench/controls.py`) goes
    through.  Returns the cell, its devices and peaks, the driver's state,
    the window's counts, the trace's summary and the checks."""
    import jax

    from repro.launch.cache import use_compile_cache

    cell = load_cell(Path(root), workload)
    devices = chip_devices(cell.chips, require_tpu)
    kind = devices[0].device_kind
    if peaks is None:
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    peak = device_peaks(kind, peaks)
    if require_tpu:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()

    spans = Spans()
    ctx = SimpleNamespace(config=cell.config, traffic=cell.traffic,
                          seed=int(seed), devices=devices, spans=spans)
    state = cell.driver.setup(ctx)
    setup_s = time.perf_counter() - T_START

    trace_dir = Path(root) / OUT_DIR / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    spans.items.clear()
    compiles.on = True
    try:
        with spans("window"):
            win = cell.driver.window(state, float(seconds), spans)
    finally:
        compiles.on = False
        if trace:
            jax.profiler.stop_trace()
    mem = memory_peak(devices)

    summary = None
    if trace:
        summary = T.Summary(T.load(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    cell.driver.release(state)
    return SimpleNamespace(
        cell=cell, devices=devices, kind=kind, peak=peak, state=state,
        spans=spans, setup_s=setup_s, win=win, mem=mem, summary=summary,
        compiles=compiles.count, checks=cell.driver.check(state))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             peaks: dict | None = None) -> dict:
    """One run of one cell; returns the result object (see module doc).
    `require_tpu=False` and `peaks` are for the tests on the CPU."""
    r = drive(workload, seed, seconds, trace, root=root,
              require_tpu=require_tpu, peaks=peaks)
    cell, win, summary, checks = r.cell, r.win, r.summary, r.checks
    values = dict(win["metrics"], setup_s=r.setup_s)

    device = {"platform": r.devices[0].platform, "kind": r.kind,
              "count": len(r.devices), "memory_peak_bytes": r.mem}
    if trace:
        run = SimpleNamespace(spans=r.spans, counters=win["counters"],
                              trace=summary, peaks=r.peak,
                              config=cell.config, window_s=win["window_s"],
                              chips=len(r.devices))
        metrics = {}
        for m in cell.per_layer:
            v = cell.metrics[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        missing = [m["name"] for m in cell.e2e if m["name"] not in values]
        if missing:
            raise RuntimeError(f"driver {cell.traffic['driver']!r} gives no "
                               f"{missing} for {workload}")
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.e2e}
    result = {"correct": all(c["value"] <= c["limit"] for c in checks)
              and r.compiles == 0,
              "attempted": int(win["attempted"]),
              "failed": int(win["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary.breakdown(r.spans.names())
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    result["checks"]["compiles in window"] = {"value": r.compiles,
                                              "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's own logs go inside the checkout, not to a fixed /tmp
    logs = ROOT / OUT_DIR / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
