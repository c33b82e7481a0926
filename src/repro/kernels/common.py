"""Shared kernel utilities: process-wide execution-mode selection.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling).  On this CPU
container they are validated with interpret=True, which executes the
kernel body in Python — far too slow to ever be a silent benchmark path.
`kernel_mode()` therefore picks the mode ONCE per process (the old
`default_interpret()` re-read the backend on every call, so a mid-process
backend change could split one run across modes):

  * "pallas"     compiled Pallas kernels (backend is TPU)
  * "ref"        the jnp references in each kernel package's ref.py —
                 the CPU fast path AND the oracle the equivalence tests
                 pin the kernels against
  * "interpret"  interpret=True Pallas everywhere — debugging only,
                 opt-in via REPRO_KERNEL_MODE=interpret

REPRO_KERNEL_MODE (read at import, like REPRO_NO_PACK/REPRO_NO_DONATE)
overrides the automatic choice with any of the three names.  Benchmarks
record `kernel_mode()` in their JSON, so an interpret-mode number can
never masquerade as a real measurement.
"""
from __future__ import annotations

import functools
import os

import jax

_MODES = ("pallas", "ref", "interpret")
_FORCE = os.environ.get("REPRO_KERNEL_MODE", "")


@functools.lru_cache(maxsize=None)
def kernel_mode() -> str:
    """Process-wide kernel execution mode ("pallas" / "ref" / "interpret"),
    chosen once on first use and cached for the life of the process."""
    if _FORCE:
        if _FORCE not in _MODES:
            raise ValueError(f"REPRO_KERNEL_MODE={_FORCE!r}; "
                             f"valid: {_MODES}")
        return _FORCE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def use_pallas() -> bool:
    """Default kernel-vs-ref dispatch: Pallas kernels unless mode is
    "ref" (interpret mode still routes through pallas_call)."""
    return kernel_mode() != "ref"


def interpret() -> bool:
    """Default interpret flag for pallas_call when `use_pallas()`."""
    return kernel_mode() == "interpret"


def default_interpret() -> bool:
    """Interpret flag for callers that force the Pallas path (kernel
    equivalence tests): interpret everywhere except a real TPU.  Kept
    for back-compat; now derived from the cached process-wide mode."""
    return kernel_mode() != "pallas"


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
