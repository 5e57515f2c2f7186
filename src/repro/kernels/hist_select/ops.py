"""jit'd public wrapper for hist_select.

``kth_key_u`` is the backend primitive ``selectk`` plugs in: per batch row
and per static segment, the k-th largest uint32 key.  ``use_pallas=True``
runs the Pallas radix-histogram kernel (compiled on TPU, or with
``interpret=True`` for CPU parity runs); ``False`` runs the pure-jnp sort
oracle.  The wrapper pads the key axis to the tile size with segment id -1,
which matches no segment's one-hot row — padding never enters any
histogram.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_TILE_N, kth_key_u_pallas
from .ref import kth_key_u_ref

# f32 histogram accumulation (tile matmuls) is exact for integer counts
# below 2**24; ``dispatch.resolve_backend`` picks the 32-round XLA search
# for larger key rows, and the kernel refuses them.
MAX_N = 1 << 23

@partial(jax.jit, static_argnames=("ks", "tile_n", "use_pallas", "interpret"))
def kth_key_u(
    u: jax.Array,                  # (B, n) uint32 keys (selectk's _to_u image)
    seg_ids: jax.Array,            # (n,) int32 segment of each element
    ks: tuple,                     # static per-segment selection widths
    *,
    tile_n: int = DEFAULT_TILE_N,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:                    # (B, S) uint32 thresholds
    """Per-(row, segment) k-th largest key.  ``0 <= ks[s] <= |segment s|``."""
    n = u.shape[-1]
    if n > MAX_N:
        raise ValueError(f"n={n} exceeds hist_select's exact-count bound "
                         f"MAX_N={MAX_N}; use the selectk XLA path")
    if not use_pallas:
        return kth_key_u_ref(u, seg_ids, ks)

    tile = min(tile_n, -(-n // 128) * 128)    # lane-aligned, never > tile_n
    pad = (-n) % tile
    if pad:
        u = jnp.concatenate([u, jnp.zeros(u.shape[:-1] + (pad,), u.dtype)],
                            axis=-1)
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), -1, jnp.int32)])
    return kth_key_u_pallas(u, seg_ids, jnp.asarray(ks, jnp.int32),
                            tile_n=tile, interpret=interpret)
