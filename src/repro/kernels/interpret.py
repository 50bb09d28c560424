"""Interpret-mode policy for the Pallas kernels.

Every kernel signature defaults ``interpret=None``; :func:`resolve_interpret`
maps ``None`` to the backend default — interpret off-TPU (the kernel body
executes in Python for validation), compiled Mosaic on TPU.  Forcing
interpret mode on a TPU backend is refused: the emulated kernel would stand
in for the compiled one and hide its real cost, or its failure to lower.

This module is a leaf (no intra-package imports) so the kernels can use it
without creating an import cycle with :mod:`repro.kernels.ops`.
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True off-TPU (Python emulation), False on TPU (compiled Mosaic)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument (``None`` → backend default).

    Raises ``ValueError`` when interpret mode is forced on a TPU backend.
    """
    if interpret is None:
        return default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas kernel would run as a "
            "Python emulation instead of compiled Mosaic; pass interpret=None "
            "or False"
        )
    return bool(interpret)
