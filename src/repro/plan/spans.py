"""Profiler spans on the flush path: the ``dx.*`` trace annotations.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler runs it
lands in the same trace as the device's operations, on one clock, and
keyword arguments become the event's stats; with no profiler running it
costs about a microsecond and records nothing. There is no other switch.

Names (the benchmark's readers key on them; DESIGN.md lists what each
covers):

  dx.submit                 Scheduler.submit / submit_gather / submit_rmw
  dx.flush                  Scheduler.flush_async
    dx.flush.lower          lowering the window (plan cache, passes)
      dx.pass.<slot>        one per pipeline slot (normalize ... batch)
      dx.cost.measure       CostModel.measure_factor (``outcome=``)
      dx.flush.hazard_scan  the window's hazard scan
    dx.flush.emit           plan_emit.execute
      dx.prefetch.<kind>    one per route-stage prefetch
      dx.emit.<kind>.<be>   one per root node
    dx.flush.report         the FlushReport and the plan's strip
  dx.sync.<site>            a device->host read (``to_host``), ``bytes=``
  dx.h2d.<site>             a host->device upload (``to_device``, or a
                            call under ``uploading``), ``bytes=``

Every device->host read on the flush path goes through ``to_host`` and
every upload of a host array runs under ``uploading``, so each one is
named and its bytes counted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


def _device_bytes(x) -> int:
    return int(x.nbytes) if isinstance(x, jax.Array) else 0


def to_host(x, site: str):
    """``np.asarray(x)`` under the span ``dx.sync.<site>``, which carries
    the bytes read from the device (0 for an array already on the host).
    A list or tuple of arrays is read under one span and comes back as a
    list."""
    many = isinstance(x, (list, tuple))
    xs = x if many else (x,)
    with TraceAnnotation(f"dx.sync.{site}",
                         bytes=sum(_device_bytes(a) for a in xs)):
        out = [np.asarray(a) for a in xs]
    return out if many else out[0]


def _host_bytes(x) -> int:
    """Bytes that uploading the NumPy array ``x`` moves, in the dtype JAX
    gives it; 0 for anything else (a device array, None)."""
    if not isinstance(x, np.ndarray):
        return 0
    return x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize


def uploading(site: str, *xs) -> TraceAnnotation:
    """The span ``dx.h2d.<site>`` around a call that uploads the NumPy
    arrays among ``xs``; it carries their bytes."""
    return TraceAnnotation(f"dx.h2d.{site}",
                           bytes=sum(_host_bytes(x) for x in xs))


def to_device(x, site: str):
    """``jnp.asarray(x)``; a host input's upload runs under
    ``uploading(site, ...)``."""
    if isinstance(x, jax.Array):
        return jnp.asarray(x)
    a = x if isinstance(x, np.ndarray) else np.asarray(x)
    with uploading(site, a):
        return jnp.asarray(a)
