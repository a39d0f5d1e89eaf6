"""Host spans of the program, in the profiler's own trace.

Every span the program opens goes through :func:`span` (or
:func:`step_span` for the training step), a thin wrapper over
``jax.profiler.TraceAnnotation``.  Spans land in the trace that
``jax.profiler.start_trace`` writes (``--profile-dir`` /
:class:`~repro.run.hooks.ProfilerHook`), on the host clock that the
profiler also places the device's programs against, and cost ≈ 1.5 µs
when no trace runs.

Rules:

* names are constant strings under ``repro.``;
* counts and ids go in as the span's arguments, never into the name
  (``span("repro.serve.prefill", rid=7, tokens=190)``); an argument known
  only at the end goes in with ``set_metadata`` on the entered span;
* no span per token or per layer: the host cannot afford them, and the
  device's share of a layer is read from the programs' named scopes
  (``jax.named_scope``), which the profiler reports per device op.
"""
from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``args`` (ints, floats, strings)."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(name: str, step: int, **args) -> jax.profiler.TraceAnnotation:
    """A span that marks one training step (``step_num``) for the
    profiler's step views."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step, **args)
