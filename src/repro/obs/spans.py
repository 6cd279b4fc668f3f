"""Host spans of the REMD driver: the host side of a device trace.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation("repex." +
name, **args)``.  Under ``jax.profiler.trace`` it records a host event on
the profile's ``/host:CPU`` plane, on the same clock as the device
planes, with ``args`` as its stats; with no profiler running it costs
about a microsecond.  There is no switch: the spans are always in the
code, and a profile shows them whenever one is recorded.

The spans ``run_fused`` / ``run_sharded`` open (docs/OBSERVABILITY.md):

  repex.start      the scan carry (a resumed one, or a fresh failure
                   key), ``run_sharded``'s ``device_put``, the start
                   cycle fetch
  repex.chunk      one chunk; args ``chunk`` (index over the driver's
                   lifetime) and ``cycles`` (K).  It holds:
    repex.dispatch   fetching the compiled chunk function and calling
                     it; arg ``first_call`` (its first run, so a compile
                     shows under this name)
    repex.wait       ``block_until_ready`` on the chunk
    repex.fetch      the one stats fetch of the chunk (Eq. (1) T_data)
    repex.bookkeep   history, acceptance, the telemetry fold
    repex.ckpt       a checkpoint save, when one is due
  repex.report     ``build_report``
"""
from __future__ import annotations

import jax

PREFIX = "repex."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``repex.<name>``; ``args`` become its stats (use
    ``.set_metadata(**more)`` inside the block for values known later)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
