"""Process-level XLA / platform configuration (must run *before* jax init).

XLA reads ``XLA_FLAGS`` exactly once, when the backend initialises, so every
flag here has to be in the environment before the first ``import jax`` runs
any device code.  This module is deliberately **stdlib-only** — importing it
never touches jax — so scripts can do::

    from repro.util.platform import configure_xla
    configure_xla(host_device_count=4, latency_hiding=True)
    import jax   # first init sees the flags

It also owns the one place a compilation-cache path is chosen
(:func:`configure_compile_cache`).

Two flag groups are managed:

* ``--xla_force_host_platform_device_count=N`` — present the host CPU as N
  devices (how every multi-device test and benchmark in this repo gets a
  mesh without hardware).
* The latency-hiding scheduler flags.  These are what let XLA actually run
  a ``ppermute`` concurrently with independent compute — the hardware half
  of the staged halo-overlap plan in :mod:`repro.core.distributed` (the
  graph half is the plan's phase structure: the exchange has no data
  dependence on the interior launch).  The ``--xla_gpu_*`` spelling is
  registered on every backend build (CPU included), so appending them
  off-GPU is harmless; TPU enables its latency-hiding scheduler by default.
  (``--xla_gpu_enable_async_collectives`` is *not* in the set: current XLA
  runs collectives asynchronously by default and aborts on the removed
  flag.)

Flags are *appended*: XLA honours the last occurrence of a repeated flag, so
a pre-existing ``XLA_FLAGS`` (debug / memory flags) is never clobbered, and
our value wins only for the flags we set.
"""
from __future__ import annotations

import os
import sys
from typing import Iterable, Optional

#: Latency-hiding scheduler flags: let the scheduler move independent
#: compute into the shadow of (default-async) collectives, and give the
#: collective stream priority so the exchange actually leads the launch.
LATENCY_HIDING_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def host_device_count_flag(n: int) -> str:
    """The flag that presents the host CPU as ``n`` XLA devices."""
    return f"--xla_force_host_platform_device_count={int(n)}"


def build_xla_flags(
    existing: Optional[str] = None,
    *,
    host_device_count: Optional[int] = None,
    latency_hiding: bool = False,
    extra: Iterable[str] = (),
) -> str:
    """Compose an ``XLA_FLAGS`` value (pure function; nothing is applied).

    Args:
      existing: current ``XLA_FLAGS`` content to preserve (our flags are
        appended after it, so they win for repeated flags).
      host_device_count: if given, append ``host_device_count_flag(n)``.
      latency_hiding: append :data:`LATENCY_HIDING_FLAGS`.
      extra: any further literal flags to append, in order.

    Returns:
      The space-joined flag string (may be empty).
    """
    parts = [existing.strip()] if existing and existing.strip() else []
    if host_device_count is not None:
        parts.append(host_device_count_flag(host_device_count))
    if latency_hiding:
        parts.extend(LATENCY_HIDING_FLAGS)
    parts.extend(extra)
    return " ".join(parts)


#: The checkout root (``src/repro/util/`` → three levels up).
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives in ``<checkout>/.jax_cache`` (git-ignored), a fixed path so a
    later run finds what an earlier one compiled.  Call before jax
    compiles anything; if jax is already imported its config is updated
    too.  Nothing else in the repository sets a cache path.

    Returns:
      The cache directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def configure_xla(
    *,
    host_device_count: Optional[int] = None,
    latency_hiding: bool = False,
    extra: Iterable[str] = (),
    env: Optional[dict] = None,
) -> str:
    """Merge the requested flags into ``XLA_FLAGS`` (call before jax init).

    Args:
      host_device_count / latency_hiding / extra: see :func:`build_xla_flags`.
      env: environment mapping to mutate (defaults to ``os.environ``; tests
        pass their own dict).

    Returns:
      The final ``XLA_FLAGS`` value that was written.
    """
    if env is None:
        env = os.environ
    flags = build_xla_flags(
        env.get("XLA_FLAGS"),
        host_device_count=host_device_count,
        latency_hiding=latency_hiding,
        extra=extra,
    )
    env["XLA_FLAGS"] = flags
    return flags
