"""Profiler-annotation helpers: name our ops in device profiles.

Two flavours, matching how JAX attributes time:

* :func:`trace_scope` — ``jax.named_scope``: a *trace-time* context that
  prefixes the HLO op names staged under it.  Zero runtime cost (it only
  exists while tracing), so the model blocks and kernel ``ops`` wrappers
  use it unconditionally — ``jax.profiler`` device traces then attribute
  kernel time to ``repro/ssa_attention`` etc. instead of anonymous
  fusions.
* :func:`annotate` — ``jax.profiler.TraceAnnotation``: a *host-side*
  span that shows up on the profiler's Python track.  The serving engine
  opens them (tick phases, admission, token fetch, program launches) only
  when a tracer is attached, keeping the untraced tick free of per-tick
  instrumentation.
"""
from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

__all__ = ["annotate", "trace_scope"]

trace_scope = jax.named_scope
annotate = TraceAnnotation
