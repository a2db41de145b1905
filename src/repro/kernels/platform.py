"""Where the Pallas kernels run compiled and where they are interpreted.

The one place this package decides interpret mode: a kernel lowered for a
TPU runs through Mosaic, on every other platform through the Pallas
interpreter.  The choice follows the platform the call is lowered for —
where its inputs live — via :func:`jax.lax.platform_dependent`, so it
holds inside an enclosing ``jit`` and for a compile against a described
(unattached) TPU topology alike.
"""
from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)``, compiled on TPU and
    interpreted elsewhere; returns the callable over the kernel inputs."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return lax.platform_dependent(*args, tpu=compiled,
                                      default=interpreted)

    return call
