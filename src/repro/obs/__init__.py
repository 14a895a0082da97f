"""repro.obs — telemetry for the sparse stack (docs/observability.md).

Two pieces, one import site:

* :mod:`repro.obs.registry` — process-global :class:`MetricsRegistry` of
  counters / gauges / timers / series, exported in the benchmark record
  schema; tracer-safe and a strict no-op when disabled.
* :mod:`repro.obs.trace` — :func:`annotate` / :func:`annotated` profiler
  scopes (``jax.named_scope`` + ``jax.profiler.TraceAnnotation``).

Instrumentation contract: observing never changes a computed value
(``tests/test_obs.py`` pins kernel and solver outputs bit-for-bit with
telemetry on vs off).
"""
from repro.obs.registry import (
    MetricsRegistry,
    SERIES_CAP,
    concrete,
    disable,
    enable,
    enabled,
    get_registry,
    set_registry,
    using_registry,
)
from repro.obs.trace import annotate, annotated

__all__ = [
    "MetricsRegistry",
    "SERIES_CAP",
    "annotate",
    "annotated",
    "concrete",
    "disable",
    "enable",
    "enabled",
    "get_registry",
    "set_registry",
    "using_registry",
]
