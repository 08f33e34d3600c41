"""Pallas TPU kernels.

Every kernel entry point takes ``interpret=None`` and resolves it here,
by backend: the CPU runs the Pallas interpreter (the tests and the
rehearsals), a TPU compiles the kernel for the chip, and any other
backend is refused.  An explicit ``interpret=False`` compiles for a TPU
from anywhere (the chip-compile tests do that for a described topology);
an explicit ``interpret=True`` on a TPU is refused, so a call on the
chip never runs the emulator.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a Pallas call on the default backend."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels here target the TPU (compiled) "
                           f"or the CPU (interpreted), not {backend!r}")
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise RuntimeError("interpret=True on a TPU backend would run the "
                           "Pallas emulator instead of the chip")
    return bool(interpret)
