"""embedding_bag — FBGEMM-style batched embedding-bag with HMU telemetry.

The core DLRM inference op (paper §III.B: "batched embedding bag operations
are the core computational kernels in large-scale personalized
recommendation systems").  For each output sample, ``bag_len`` rows are
gathered from the (possibly tiered) table and sum/weighted-sum pooled.

TPU design:
  * one grid step per bag; the bag's rows are fetched HBM->VMEM with
    ``bag_len`` concurrent async copies driven by scalar-prefetched indices;
  * pooling is a (1, L) x (L, D) matmul against the per-bag weights — the
    reduction runs on the MXU while the next bag's DMAs are in flight
    (sequential grid: Pallas overlaps via the implicit pipeline);
  * per-block HMU counters are bumped in the same pass (aliased VMEM
    buffer), giving exact, host-free access telemetry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    idx_ref,          # (B, L) int32, scalar-prefetched
    storage_ref,      # (N, D) ANY/HBM
    weights_ref,      # (1, L) per-bag pooling weights, VMEM
    counts_in_ref,    # (n_blocks, 1) int32 VMEM (aliased)
    out_ref,          # (1, D) VMEM
    counts_out_ref,   # aliased
    rows_ref,         # (L, D) VMEM scratch
    sem,              # (L,) DMA semaphores
    *,
    bag_len: int,
    block_rows: int,
):
    b = pl.program_id(0)

    def issue(i, _):
        row = idx_ref[b, i]
        pltpu.make_async_copy(
            storage_ref.at[pl.ds(row, 1), :], rows_ref.at[pl.ds(i, 1), :], sem.at[i]
        ).start()
        return ()

    jax.lax.fori_loop(0, bag_len, issue, (), unroll=False)

    # memory-side telemetry (while DMAs fly)
    def bump(i, _):
        blk = idx_ref[b, i] // block_rows
        counts_out_ref[blk, 0] = counts_out_ref[blk, 0] + 1
        return ()

    jax.lax.fori_loop(0, bag_len, bump, (), unroll=False)

    def wait(i, _):
        pltpu.make_async_copy(
            storage_ref.at[pl.ds(idx_ref[b, i], 1), :], rows_ref.at[pl.ds(i, 1), :],
            sem.at[i],
        ).wait()
        return ()

    jax.lax.fori_loop(0, bag_len, wait, (), unroll=False)

    # (1, L) @ (L, D) weighted pool on the MXU, accumulate in f32
    out_ref[...] = jnp.dot(
        weights_ref[...], rows_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def embedding_bag_pallas(
    storage: jax.Array,    # (N, D)
    indices: jax.Array,    # (B, L) int32
    weights: jax.Array,    # (B, L) pooling weights
    counts: jax.Array,     # (n_blocks,) int32
    *,
    block_rows: int,
    interpret: bool = False,
):
    b, l = indices.shape
    n, d = storage.shape
    n_blocks = counts.shape[0]
    counts2d = counts.reshape(n_blocks, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                # storage in HBM
            pl.BlockSpec((1, l), lambda i, idx: (i, 0)),         # weights row
            pl.BlockSpec((n_blocks, 1), lambda i, idx: (0, 0)),  # counts
        ],
        out_specs=[
            pl.BlockSpec((1, d), lambda i, idx: (i, 0)),
            pl.BlockSpec((n_blocks, 1), lambda i, idx: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((l, d), storage.dtype),
            pltpu.SemaphoreType.DMA((l,)),
        ],
    )

    out, counts_new = pl.pallas_call(
        functools.partial(_kernel, bag_len=l, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, d), storage.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        input_output_aliases={3: 1},
        interpret=interpret,
    )(indices.astype(jnp.int32), storage, weights.astype(jnp.float32), counts2d)
    return out, counts_new.reshape(n_blocks)
