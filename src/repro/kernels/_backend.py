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


def resolve_interpret(interpret: bool | None) -> bool:
    """Kernel-entry helper: explicit argument wins, else backend default."""
    return default_interpret() if interpret is None else bool(interpret)
