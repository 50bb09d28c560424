"""Public jit'd wrappers for the Pallas kernels.

Every kernel signature defaults ``interpret=None`` → :func:`default_interpret`
(interpret off-TPU, compiled Mosaic on TPU).  Callers may force compiled
Mosaic anywhere; forcing interpret mode on a TPU raises
(``kernels/interpret.py``).
"""

from __future__ import annotations

from repro.kernels.bloom import bloom_query, pack_bits  # noqa: F401
from repro.kernels.diff_lookup import diff_lookup  # noqa: F401
from repro.kernels.ell_spmv import ell_spmv  # noqa: F401
from repro.kernels.flash_attn import flash_attention  # noqa: F401
from repro.kernels.fused_sweep import FusedOut, fused_sweep  # noqa: F401
from repro.kernels.interpret import (  # noqa: F401
    default_interpret,
    resolve_interpret,
)


def spmv(states, nbr, w, carry, *, semiring="min_plus", **kw):
    return ell_spmv(states, nbr, w, carry, semiring=semiring, **kw)


def lookup(iters, vals, qi, **kw):
    return diff_lookup(iters, vals, qi, **kw)


def bloom(words, v, i, salt, **kw):
    return bloom_query(words, v, i, salt, **kw)


def attention(q, k, v, *, causal=True, **kw):
    return flash_attention(q, k, v, causal=causal, **kw)


def sweep(*args, **kw):
    """The fused maintenance megakernel (one dispatch per sweep iteration)."""
    return fused_sweep(*args, **kw)
