"""jit'd public wrapper for embedding_bag.

``use_pallas=True`` runs the Pallas kernel through the interpreter — the
only mode it has: Mosaic refuses its ``(1, L)`` weights block over a
``(B, L)`` array (the (8, 128) tiling rule), so it does not compile for TPU
and a request for the compiled kernel raises.  ``use_pallas=False`` (the
default) runs the pure-jnp reference on every platform.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import embedding_bag_pallas
from .ref import embedding_bag_ref


@partial(jax.jit, static_argnames=("block_rows", "use_pallas", "interpret"))
def embedding_bag(
    storage: jax.Array,
    indices: jax.Array,
    counts: jax.Array,
    weights: jax.Array | None = None,
    *,
    block_rows: int,
    use_pallas: bool = False,
    interpret: bool = True,
):
    """Batched (weighted) embedding-bag with fused HMU counters.

    Returns (pooled (B, D), new_counts)."""
    if weights is None:
        weights = jnp.ones(indices.shape, jnp.float32)
    if not use_pallas:
        return embedding_bag_ref(storage, indices, weights, counts, block_rows=block_rows)
    if not interpret:
        raise ValueError("embedding_bag does not compile for TPU (its weights "
                         "block breaks the (8, 128) tiling rule); it runs in "
                         "interpret mode only")
    return embedding_bag_pallas(
        storage, indices, weights, counts, block_rows=block_rows, interpret=interpret
    )
