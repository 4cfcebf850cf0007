"""Backend detection for Pallas kernel dispatch.

Lives in its own module (not ``ops``) so the kernel modules can resolve
their ``interpret`` default without importing ``ops`` back (cycle).

Kernels compile natively on TPU backends and run in interpret mode
everywhere else; the backend alone decides.
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode by default."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret):
    """Kernel-entry helper: explicit argument wins, else backend default.

    Besides a bool, the argument may be ``pltpu.InterpretParams``: the
    TPU interpreter, which models DMAs and semaphores, fills fresh VMEM
    with NaN and raises on an out-of-bounds read."""
    if interpret is None:
        return default_interpret()
    return bool(interpret) if isinstance(interpret, int) else interpret
