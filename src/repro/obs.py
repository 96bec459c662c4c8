"""The program's own marks on the profiler's clock (DESIGN.md §14).

Two primitives, one naming rule (`<layer>.<part>`, e.g. `codec.encode`,
`engine.prefill.pack`, `serve.attn`):

- `span(name, *inputs, **ids)`: a host span, `jax.profiler.TraceAnnotation`.
  It is recorded only while a profiler trace runs (`jax.profiler.trace`),
  on the same clock as the device's ops; otherwise it costs about a
  microsecond.  Host spans go only in eager host code: where a function
  can also run under tracing (inside `jit` or `shard_map`), pass its array
  inputs and the span is skipped when one of them is a `Tracer`.  `ids`
  ride along as the event's arguments (`request=`, `slot=`, `n=`); ids
  that are None are left out.
- `scope(name)`: `jax.named_scope`, used only inside jitted bodies.  It
  puts `name` into the op-name metadata of every op traced under it, so
  each device op in a trace carries its layer.  It costs nothing at run
  time.

There is no switch: to see the spans, run the work under the profiler.
"""
from __future__ import annotations

import contextlib

import jax

_NO_SPAN = contextlib.nullcontext()


def span(name: str, *inputs, **ids):
    """Host span `name` over the `with` block (see the module note)."""
    if any(isinstance(x, jax.core.Tracer) for x in inputs):
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in ids.items() if v is not None})


def scope(name: str):
    """Device scope `name` for the ops traced in the `with` block."""
    return jax.named_scope(name)
