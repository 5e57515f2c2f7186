"""jit'd public wrapper for observe_scatter.

``use_pallas=True`` runs the Pallas kernel through the interpreter — the
only mode it has: Mosaic refuses its per-id VMEM read-modify-write
("Cannot store scalars to VMEM"), so it does not compile for TPU and a
request for the compiled kernel raises.  ``use_pallas=False`` runs the
pure-jnp reference (the XLA scatter every platform uses).  Pads the id
stream to the tile size with ``n_blocks`` — out of range for both paths
(negative ids WRAP once, NumPy-style, so they cannot pad) — so callers
pass arbitrary batch sizes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_TILE_M, observe_scatter_pallas
from .ref import observe_scatter_ref

@partial(jax.jit,
         static_argnames=("n_blocks", "period", "tile_m", "use_pallas",
                          "interpret"))
def observe_scatter(
    ids: jax.Array,                # (M,) int32 block ids
    cursor: jax.Array,             # () int32 PEBS position mod period
    *,
    n_blocks: int,
    period: int,
    keep: jax.Array | None = None,  # (M,) bool fault-model survival mask
    tile_m: int = DEFAULT_TILE_M,
    use_pallas: bool = False,
    interpret: bool = True,
):
    """Fused epoch-batch telemetry scatter -> (hist, pebs_hist)."""
    if not use_pallas:
        return observe_scatter_ref(ids, cursor, n_blocks=n_blocks,
                                   period=period, keep=keep)
    if not interpret:
        raise ValueError("observe_scatter does not compile for TPU (Mosaic "
                         "cannot store scalars to VMEM); it runs in "
                         "interpret mode only")
    m = ids.shape[0]
    tile = min(tile_m, -(-m // 128) * 128)
    pad = (-m) % tile
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((pad,), n_blocks, jnp.int32)])
        if keep is not None:
            keep = jnp.concatenate(
                [keep, jnp.zeros((pad,), keep.dtype)])
    return observe_scatter_pallas(ids, cursor, n_blocks=n_blocks,
                                  period=period, keep=keep, tile_m=tile,
                                  interpret=interpret)
