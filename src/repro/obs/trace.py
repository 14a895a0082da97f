"""Trace annotations: name regions of the sparse stack for profilers.

:func:`annotate` is the single spelling every layer uses for a region of a
call; a registry timer (:meth:`MetricsRegistry.timer`) is the spelling for a
set-up phase, and opens the same host span.  ``annotate`` stacks two
complementary scopes:

* ``jax.named_scope`` — tags the *traced* HLO: under ``jit`` every device
  operation carries the scope path in its op_name, so a profile can charge
  it to the region that emitted it;
* ``jax.profiler.TraceAnnotation`` — tags the *host* timeline, so eager
  launches and host work show under the region's name in a
  ``jax.profiler.trace()`` capture next to the device stream.

A region name never holds a Pallas kernel's name (``spmv_csrk``,
``spmv_sellcs``, ``spmv_segsum``, ``spmv_dia``) as a whole word: a trace
reader that finds kernels by name would count the region's glue as kernel
time.  docs/observability.md lists every region with the metric that reads
it.

Neither scope changes any computed value; when telemetry is disabled the
function returns one shared null context and touches nothing.
"""
from __future__ import annotations

import contextlib
import functools

import jax

from repro.obs.registry import _NULL_CTX, get_registry

# Under ``jit`` a region's name lives only in the compiled program's op_name
# metadata.  JAX's persistent compilation cache leaves metadata out of its
# key, so it would hand back a program compiled with other region names (the
# same computation from another version of this library): key it with the
# metadata as well.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def annotate(name: str):
    """Context manager naming a region in both host and HLO traces.

    Usage::

        with annotate("repro.permute_in"):
            x_new = x_old[perm]

    Returns a shared null context when telemetry is disabled (no-op).
    """
    if not get_registry().enabled:
        return _NULL_CTX
    ctx = contextlib.ExitStack()
    ctx.enter_context(jax.profiler.TraceAnnotation(name))
    ctx.enter_context(jax.named_scope(name))
    return ctx


def annotated(name: str):
    """Decorator form of :func:`annotate`: the whole call is the region."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco
