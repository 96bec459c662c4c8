"""The on-chip benchmark: one harness (`run.py`), the drivers that make and
serve each kind of traffic (`drivers/`), the plain references they are
checked against (`reference/`), and one reader per per-layer metric
(`metrics/`).  What belongs to one configuration or traffic mix is a data
file under `configs/` or `traffic/`, found by the name in BENCHMARK.json.

A driver module has five functions:

- `setup(ctx)`: make the inputs and weights from `ctx.seed` on
  `ctx.devices`, build the program, warm up every shape the window uses;
  returns the run's state;
- `window(state, seconds, spans)`: drive the program for `seconds`;
  returns `window_s`, `attempted`, `failed`, the end-to-end `metrics` and
  the `counters` the per-layer readers use;
- `release(state)`: free the program's state, keeping what the check
  reads;
- `check(state)`: the numbers compared, each `{name, value, limit}`;
  correct means every value is at most its limit;
- `control(state)`: the same numbers for the control (`controls.py`).

A metric reader has `read(run)`: from the run's host `spans`, `counters`,
reduced `trace` (`trace.Summary`, or None), device `peaks`, `config`,
`window_s` and `chips`, the value, or None where it finds nothing to read.
"""
