"""gather_count — tier-aware row gather with memory-side access counters.

This is the paper's HMU adapted to the TPU memory system: the per-block
access counters are updated *inside the same kernel pass* that moves the rows
(HBM -> VMEM), so telemetry has full coverage and costs the host nothing —
the TPU analogue of counting CXL.mem packets inside the memory module.

Design (TPU):
  * ``storage`` lives in HBM (``memory_space=ANY``); rows are fetched with
    explicit per-row async copies driven by **scalar-prefetched indices**
    (the standard TPU dynamic-gather pattern: the index vector must be known
    to the core before the DMA can be issued).
  * the grid walks index tiles of ``tile_m`` rows; output tiles are VMEM.
  * ``counts`` (one int32 per block of ``block_rows`` rows) is carried in
    VMEM and aliased input->output, emulating the HMU counter SRAM.  The TPU
    grid is sequential on a core, so read-modify-write is race-free.

The Pallas kernel targets TPU; tests validate it with ``interpret=True``
against ``ref.py`` (CPU containers cannot execute compiled TPU kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_M = 128


def _kernel(
    # scalar-prefetch operands
    idx_ref,            # (M,) int32 row ids, SMEM (scalar prefetch)
    # array operands
    storage_ref,        # (N, D) in ANY/HBM
    counts_in_ref,      # (n_blocks_padded, COUNT_LANES) int32, VMEM (aliased)
    out_ref,            # (tile_m, D) VMEM
    counts_out_ref,     # aliased with counts_in_ref
    scratch_ref,        # (tile_m, D) VMEM staging for DMA
    sem,                # DMA semaphores, one per row in flight
    *,
    tile_m: int,
    block_rows: int,
):
    step = pl.program_id(0)
    base = step * tile_m

    # ---- issue all row DMAs for this tile (HBM -> VMEM scratch)
    def issue(i, _):
        row = idx_ref[base + i]
        cp = pltpu.make_async_copy(
            storage_ref.at[pl.ds(row, 1), :],
            scratch_ref.at[pl.ds(i, 1), :],
            sem.at[i],
        )
        cp.start()
        return ()

    jax.lax.fori_loop(0, tile_m, issue, (), unroll=False)

    # ---- memory-side telemetry: bump the block counter per fetched row.
    # One int32 cell per block; lane 0 of a (pad, 128) layout keeps the
    # scatter vectorizable on the VPU.
    def bump(i, _):
        row = idx_ref[base + i]
        blk = row // block_rows
        cur = counts_out_ref[blk, 0]
        counts_out_ref[blk, 0] = cur + 1
        return ()

    jax.lax.fori_loop(0, tile_m, bump, (), unroll=False)

    # ---- wait for DMAs and publish the tile
    def wait(i, _):
        pltpu.make_async_copy(
            storage_ref.at[pl.ds(idx_ref[base + i], 1), :],
            scratch_ref.at[pl.ds(i, 1), :],
            sem.at[i],
        ).wait()
        return ()

    jax.lax.fori_loop(0, tile_m, wait, (), unroll=False)
    out_ref[...] = scratch_ref[...]


def gather_count_pallas(
    storage: jax.Array,     # (N, D)
    indices: jax.Array,     # (M,) int32
    counts: jax.Array,      # (n_blocks,) int32
    *,
    block_rows: int,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = False,
):
    m = indices.shape[0]
    if m % tile_m:
        raise ValueError(f"M={m} must be a multiple of tile_m={tile_m}")
    n_blocks = counts.shape[0]
    d = storage.shape[1]

    counts2d = counts.reshape(n_blocks, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // tile_m,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # storage stays in HBM
            pl.BlockSpec((n_blocks, 1), lambda i, idx: (0, 0)),  # counts in VMEM
        ],
        out_specs=[
            pl.BlockSpec((tile_m, d), lambda i, idx: (i, 0)),
            pl.BlockSpec((n_blocks, 1), lambda i, idx: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_m, d), storage.dtype),
            pltpu.SemaphoreType.DMA((tile_m,)),
        ],
    )

    out, counts_new = pl.pallas_call(
        functools.partial(_kernel, tile_m=tile_m, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, d), storage.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        input_output_aliases={2: 1},   # counts2d (arg 2 incl. prefetch) -> out 1
        interpret=interpret,
    )(indices.astype(jnp.int32), storage, counts2d)
    return out, counts_new.reshape(n_blocks)
