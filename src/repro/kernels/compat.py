"""The interpret-mode switch shared by every Pallas kernel of the repo.

``pallas_interpret()`` is the one place that decides whether Pallas
kernels (the compute kernels and the device-side ``PallasTransport``
lowering) run under the Pallas interpreter or through the Mosaic TPU
compiler: the interpreter exactly when no TPU backs the default backend
(the CPU test suite, ``JAX_PLATFORMS=cpu``).  No variable overrides it,
so on a TPU every kernel is compiled.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True iff the default backend is not a TPU."""
    return jax.default_backend() != "tpu"
