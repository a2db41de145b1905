"""Host spans and copy counts of the serve path.

:func:`span` writes a ``jax.profiler.TraceAnnotation`` into the
profiler's own trace, on the same clock as the device's ``XLA Ops`` and
``XLA Modules`` lines, so that each idle gap of the device can be put
down to what the host was doing.  Starting a profiler session
(``jax.profiler.start_trace``) turns the spans on; with none active,
:func:`span` returns one shared no-op context and builds no metadata.

:func:`to_device` and :func:`to_host` issue the executors'
host-to-device and device-to-host copies.  Inside :func:`counting`, they
add the bytes copied to the given object's integer ``h2d_bytes`` and
``d2h_bytes`` (the serve cycle passes its
:class:`repro.runtime.scheduler.SchedulerStats`), on that thread only.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

# -- span names (docs/SERVING.md "Observability") ----------------------------
# On the serve worker's thread; flat siblings, so a span's self time is its
# duration.
IDLE = "sched.idle"            # waiting for work
WINDOW = "sched.window"        # pending work, waiting for a fuller batch
GROUP = "sched.group"          # grouping, quarantine/breaker/promotion lookups
PROMOTE = "sched.promote"      # the fused-tier compile of a promotion
DISPATCH = "sched.dispatch"    # stacking and padding, upload, enqueue
SYNC = "sched.sync"            # waiting for a dispatch's outputs
FETCH = "sched.fetch"          # device-to-host copies of the outputs
RESOLVE = "sched.resolve"      # building ServeResults, resolving tickets
# On the thread that calls compile_program, nested.
LOWER = "engine.lower"         # a program-cache miss: compile walk + lowering
TABLES = "vm.tables"           # the VM's table lowering, padding and upload
# On a client thread of the decode-step runner (repro.nn.qwen2), nested.
NN_STEP = "nn.step"            # one session's decode step
NN_LAYER = "nn.layer"          # one layer of it (``index``)
NN_STAGE = "nn.stage"          # one stage: submit its tiles, wait for all

_OFF = contextlib.nullcontext()
_TRANSFERS = contextvars.ContextVar("transfers", default=None)


def span(name: str, **meta):
    """A context that marks ``name`` (with ``meta`` as its arguments) in
    the profiler's trace while a session is active, else a shared no-op."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **meta)


@contextlib.contextmanager
def counting(transfers):
    """Count the copies this thread issues into ``transfers``."""
    token = _TRANSFERS.set(transfers)
    try:
        yield
    finally:
        _TRANSFERS.reset(token)


def to_device(x) -> jax.Array:
    """A fresh device buffer holding ``x`` (the executables donate their
    memory operand, so it must be jax-owned); counts the bytes uploaded
    when ``x`` is on the host."""
    out = jnp.array(x, copy=True)
    transfers = _TRANSFERS.get()
    if transfers is not None and not isinstance(x, jax.Array):
        transfers.h2d_bytes += out.nbytes
    return out


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; counts the bytes copied when ``x`` is a device
    array."""
    transfers = _TRANSFERS.get()
    if transfers is not None and isinstance(x, jax.Array):
        transfers.d2h_bytes += x.nbytes
    return np.asarray(x)
