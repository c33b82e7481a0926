"""Jit'd public wrappers for selective flush / apply.

`selective_flush` dispatches to the Pallas gather kernel (TPU, or
interpret=True during CPU validation); `selective_apply` is the scatter
inverse, left to XLA's native scatter (no Pallas win on TPU — see
DESIGN.md kernel notes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import default_interpret
from repro.kernels.selective_flush.kernel import (drain_writeback_pallas,
                                                  selective_flush_pallas)
from repro.kernels.selective_flush import ref


def selective_flush(bank: jnp.ndarray, indices: jnp.ndarray,
                    *, use_pallas: bool | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Compact bank rows named by `indices` (-1 padded) into a dense buffer."""
    if use_pallas is None:
        use_pallas = True
    if not use_pallas:
        return ref.selective_flush_ref(bank, indices)
    if interpret is None:
        interpret = default_interpret()
    return selective_flush_pallas(bank, indices, interpret=interpret)


@jax.jit
def selective_apply(bank: jnp.ndarray, updates: jnp.ndarray,
                    indices: jnp.ndarray) -> jnp.ndarray:
    """Scatter compacted updates back into the bank (the remote 'acquire'
    side applying a flushed delta)."""
    return ref.selective_apply_ref(bank, updates, indices)


def drain_writeback(l2: jnp.ndarray, rows: jnp.ndarray, dirty: jnp.ndarray,
                    indices: jnp.ndarray, *, use_pallas: bool | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Merge drained L1 blocks into the L2 bank under a per-word dirty mask
    (the protocol engine's drain/writeback scatter — see protocol.b_drain).

    `dirty` is either boolean [m, W] or packed uint32 word-bitmask rows
    [m, ceil(W/32)] (DESIGN.md §8) — the packed form is what the packed
    metadata engine passes straight from its wdirty plane; both kernel and
    reference expand it themselves, so no caller ever unpacks.

    Dispatches to the Pallas scatter kernel on TPU; on CPU the jnp
    reference is both the validation oracle and the fast path (XLA fuses
    the scatter-max/gather pair), so interpret-mode Pallas is reserved for
    the kernel equivalence tests.  The mode is chosen once per process
    (`kernels.common.kernel_mode()`), never re-derived mid-run."""
    if use_pallas is None:
        use_pallas = common.use_pallas()
    # the benchmark's `breakdown` labels read this scope from the
    # compiled program's op names
    with jax.named_scope("kernels.drain_writeback"):
        if not use_pallas:
            return ref.drain_writeback_ref(l2, rows, dirty, indices)
        if interpret is None:
            interpret = default_interpret()
        return drain_writeback_pallas(l2, rows, dirty, indices,
                                      interpret=interpret)
