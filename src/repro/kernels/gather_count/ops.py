"""jit'd public wrapper for gather_count.

``use_pallas=True`` runs the Pallas kernel through the interpreter — the
only mode it has: Mosaic refuses its per-index counter bump ("Cannot store
scalars to VMEM"), so it does not compile for TPU and a request for the
compiled kernel raises.  ``use_pallas=False`` (the default) runs the
pure-jnp reference on every platform.  The wrapper pads the index vector to
the tile size so callers can pass arbitrary M.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_TILE_M, gather_count_pallas
from .ref import gather_count_ref


@partial(jax.jit, static_argnames=("block_rows", "tile_m", "use_pallas", "interpret"))
def gather_count(
    storage: jax.Array,
    indices: jax.Array,
    counts: jax.Array,
    *,
    block_rows: int,
    tile_m: int = DEFAULT_TILE_M,
    use_pallas: bool = False,
    interpret: bool = True,
):
    """Tier-aware gather + HMU counter update.  Returns (rows, new_counts)."""
    if not use_pallas:
        return gather_count_ref(storage, indices, counts, block_rows=block_rows)
    if not interpret:
        raise ValueError("gather_count does not compile for TPU (Mosaic "
                         "cannot store scalars to VMEM); it runs in interpret "
                         "mode only")

    m = indices.shape[0]
    pad = (-m) % tile_m
    if pad:
        # pad with row 0 and subtract the phantom counts afterwards
        indices_p = jnp.concatenate([indices, jnp.zeros((pad,), indices.dtype)])
    else:
        indices_p = indices
    out, new_counts = gather_count_pallas(
        storage, indices_p, counts,
        block_rows=block_rows, tile_m=tile_m, interpret=interpret,
    )
    if pad:
        new_counts = new_counts.at[0].add(-pad)
        out = out[:m]
    return out, new_counts
