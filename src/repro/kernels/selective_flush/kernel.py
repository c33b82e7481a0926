"""Pallas TPU kernel: selective flush = gather-compact of dirty blocks.

This is the TPU-native realization of the paper's selective-flush (§4.2):
instead of a GPU L1 walking its sFIFO and writing blocks back one by one,
the TPU owner gathers exactly the dirty parameter/state blocks named by the
sFIFO into a contiguous staging buffer — which then feeds one small
collective (the "writeback to global scope").

TPU-idiomatic pattern: the dirty-block index list is *scalar-prefetched*
(PrefetchScalarGridSpec) so the BlockSpec index_map can select a dynamic HBM
block per grid step — dynamic gather without scatter/gather instructions,
driven entirely by the DMA engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flush_kernel(idx_ref, bank_ref, out_ref):
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0

    @pl.when(valid)
    def _copy():
        out_ref[...] = bank_ref[...]

    @pl.when(jnp.logical_not(valid))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_flush_pallas(bank: jnp.ndarray, indices: jnp.ndarray,
                           *, interpret: bool = False) -> jnp.ndarray:
    """bank [n_blocks, block_size], indices [max_dirty] int32 (-1 pad)
    -> [max_dirty, block_size]."""
    n_blocks, block_size = bank.shape
    max_dirty = indices.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(max_dirty,),
        in_specs=[
            # clamp pad entries (-1) in the index_map; the kernel zeroes them
            pl.BlockSpec((1, block_size),
                         lambda i, idx: (jnp.maximum(idx[i], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, block_size), lambda i, idx: (i, 0)),
    )
    return pl.pallas_call(
        _flush_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((max_dirty, block_size), bank.dtype),
        interpret=interpret,
    )(indices, bank)


def _writeback_kernel(idx_ref, l2_ref, rows_ref, dirty_ref, out_ref, *,
                      packed):
    """Sequential masked merge of every list entry into the resident L2
    bank.  The whole bank, the drained rows and their dirty masks sit in
    VMEM; entry i's destination block is a dynamic sublane offset read
    from the SMEM index list.  Walking the list in order IS the
    reference's last-writer-wins priority, so duplicate destinations
    need no sort.  Packed masks (uint32 word-bitmask lanes carried as
    int32) expand in-register: word w is bit w & 31 of lane w >> 5."""
    nb, w = out_ref.shape
    out_ref[...] = l2_ref[...]
    words = lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def body(i, carry):
        b = idx_ref[0, i]

        @pl.when((b >= 0) & (b < nb))
        def _merge():
            d = dirty_ref[pl.ds(i, 1), :]
            if packed:
                sel = jnp.zeros((1, w), jnp.int32)
                for lane in range(d.shape[-1]):
                    bits = (d[:, lane:lane + 1] >> (words & 31)) & 1
                    sel = jnp.where((words >> 5) == lane, bits, sel)
            else:
                sel = d
            cur = out_ref[pl.ds(b, 1), :]
            out_ref[pl.ds(b, 1), :] = jnp.where(
                sel != 0, rows_ref[pl.ds(i, 1), :], cur)

        return carry

    lax.fori_loop(0, idx_ref.shape[1], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def drain_writeback_pallas(l2: jnp.ndarray, rows: jnp.ndarray,
                           dirty: jnp.ndarray, indices: jnp.ndarray,
                           *, interpret: bool = False) -> jnp.ndarray:
    """Masked scatter-merge of drained blocks into the L2 bank (the sFIFO
    drain writeback, §2.2/§4.2): out = l2 with rows[i] merged into block
    indices[i] under the per-word dirty mask.

    One kernel invocation, no grid: the bank, rows and masks are whole
    VMEM blocks (full-array blocks satisfy the TPU tiling rule at any
    W), the index list is an SMEM block, and the merge is an in-kernel
    loop over the list (`_writeback_kernel`).  The L2 bank is
    input/output-aliased.  Under `jax.vmap` (the `run_*_many` replica
    path) the batch becomes a one-axis grid over the same blocks.

    l2 [n_blocks, W] int32; rows [m, W]; dirty [m, W] bool OR
    [m, ceil(W/32)] packed uint32 word-bitmask rows (DESIGN.md §8);
    indices [m] int32 (entries outside [0, n_blocks) write nothing).
    Returns the merged [n_blocks, W] bank, bitwise
    `ref.drain_writeback_ref`."""
    packed = dirty.dtype != jnp.bool_
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_writeback_kernel, packed=packed),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(l2.shape, l2.dtype),
        input_output_aliases={1: 0},   # l2 bank updated in place
        interpret=interpret,
    )(jnp.asarray(indices, jnp.int32).reshape(1, -1), l2, rows,
      lax.bitcast_convert_type(dirty, jnp.int32) if packed
      else dirty.astype(jnp.int32))
