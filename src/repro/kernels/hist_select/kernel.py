"""hist_select — Pallas TPU radix-histogram threshold select.

The paper's HMU must rank-select on-module in a bounded number of passes
over its counter SRAM; ``selectk``'s bitwise search emulates that with 32
full compare+reduce passes (one per bit).  This kernel descends the same
threshold in 4 byte levels: per level it streams the key tiles once,
accumulating a per-(row, segment) 256-bin histogram of the current byte
(restricted to keys matching the already-resolved high-byte prefix) in
VMEM, then — on the level's last tile — sums the histogram from the top to
find the bin holding the k-th largest key, folds that byte into the prefix,
and rebases k to the bin-local rank.  After level 3 the prefix IS the k-th
largest key: the exact value ``selectk._kth_largest`` returns, in 4 grid
passes over the data instead of 32.

Layout per grid step ``(level, tile)`` (the grid is sequential on a TPU
core, so the VMEM scratch carries state across steps race-free):

  * keys tile ``(B, tile_n)`` int32 — the bit pattern of the
    order-isomorphic uint32 ``_to_u`` image; every batch row rides in one
    block, so the block's second-minor dim equals the array's (the TPU
    (8, 128) tiling rule), and the body loops over the rows;
  * segment-id tile ``(1, tile_n)`` int32 (-1 = padding, matches no segment);
  * histogram scratch ``(B, S, 256)`` f32, accumulated per row by a
    segment-one-hot ``(S, tile_n)`` × byte-one-hot ``(256, tile_n)``ᵀ
    matmul (MXU-shaped; 0/1 operands and f32 accumulation are exact below
    2**24 counts, ``ops.MAX_N``);
  * prefix / k-remaining scratch ``(B, S, 1)`` int32, reset at
    ``(level, tile) == (0, 0)``.

``S`` is padded to a multiple of 8 by the wrapper (padding segments have
width 0 and no members).  Level and byte shift are static inside
``pl.when`` branches, so the body has no dynamic shifts.  The suffix sum
``count(byte >= j)`` is a matmul with an upper-triangular ones matrix, run
once per byte of the count so every operand is an integer below 256 —
exact whatever precision the MXU uses for f32 operands (Mosaic has no
``cumsum`` lowering).

Per-segment caps ride in as an ``(S, 1)`` VMEM input — the "segment caps
become per-tenant histogram offsets" form of ``segment_top_k_mask``: one
kernel invocation resolves every tenant's threshold instead of one
dispatch per tenant slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_N = 2048
_LEVELS = 4                       # 32-bit keys, one byte per level
_BINS = 256


def _shift(level: int) -> int:
    return 8 * (_LEVELS - 1 - level)


def _accumulate(keys_ref, seg_ref, hist_ref, prefix_ref, *, level: int,
                n_rows: int, n_segments: int, tile_n: int):
    """Add this tile's per-(row, segment) histogram of ``level``'s byte."""
    seg_oh = seg_ref[...] == jax.lax.broadcasted_iota(
        jnp.int32, (n_segments, tile_n), 0)                 # (S, tile_n)
    bins = jax.lax.broadcasted_iota(jnp.int32, (_BINS, tile_n), 0)
    # keys still in the running match the resolved prefix on every byte
    # above this level (level 0: everything matches)
    hi_mask = ~((1 << (32 - 8 * level)) - 1) if level else 0

    def row(b, carry):
        u = keys_ref[pl.ds(b, 1), :]                        # (1, tile_n)
        byte = jax.lax.shift_right_logical(u, jnp.int32(_shift(level))) & 0xFF
        member = seg_oh
        if level:
            member = member & ((u & jnp.int32(hi_mask)) == prefix_ref[b])
        contrib = jnp.where(member, 1.0, 0.0)               # (S, tile_n)
        byte_oh = jnp.where(bins == byte, 1.0, 0.0)         # (256, tile_n)
        hist_ref[b] += jax.lax.dot_general(
            contrib, byte_oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (S, 256)
        return carry

    jax.lax.fori_loop(0, n_rows, row, 0)


def _resolve(hist_ref, prefix_ref, krem_ref, *, level: int, n_rows: int,
             n_segments: int):
    """Level boundary: localize the k-th key's bin, refine prefix and k."""
    upper = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (_BINS, _BINS), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (_BINS, _BINS), 1), 1.0, 0.0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n_segments, _BINS), 1)

    def row(b, carry):
        hist = hist_ref[b].astype(jnp.int32)                # (S, 256)
        from_top = jnp.zeros_like(hist)                     # count(byte >= j)
        for part in range(3):                               # counts < 2**24
            piece = ((hist >> (8 * part)) & 0xFF).astype(jnp.float32)
            summed = jnp.dot(piece, upper, preferred_element_type=jnp.float32)
            from_top = from_top + (summed.astype(jnp.int32) << (8 * part))
        krem = krem_ref[b]                                  # (S, 1)
        # from_top is non-increasing in j: the chosen bin is the largest j
        # with from_top[j] >= k, i.e. (number of qualifying bins) - 1.
        # k == 0 qualifies every bin -> bin 255 -> prefix byte 0xFF, exactly
        # the all-ones threshold the bitwise search degenerates to.
        n_ge = jnp.sum((from_top >= krem).astype(jnp.int32), axis=1,
                       keepdims=True)
        b_idx = jnp.maximum(n_ge - 1, 0)                    # (S, 1)
        above = jnp.sum(jnp.where(lanes == b_idx, from_top - hist, 0),
                        axis=1, keepdims=True)
        krem_ref[b] = krem - above
        prefix_ref[b] = prefix_ref[b] | (b_idx << _shift(level))
        return carry

    jax.lax.fori_loop(0, n_rows, row, 0)


def _kernel(keys_ref, seg_ref, ks_ref, out_ref, hist_ref, prefix_ref,
            krem_ref, *, n_rows: int, n_segments: int, n_tiles: int,
            tile_n: int):
    level = pl.program_id(0)
    tile = pl.program_id(1)
    last = tile == n_tiles - 1

    @pl.when((level == 0) & (tile == 0))
    def _init():
        prefix_ref[...] = jnp.zeros_like(prefix_ref)
        krem_ref[...] = jnp.broadcast_to(ks_ref[...][None], krem_ref.shape)

    @pl.when(tile == 0)
    def _zero_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    for lvl in range(_LEVELS):
        pl.when(level == lvl)(functools.partial(
            _accumulate, keys_ref, seg_ref, hist_ref, prefix_ref, level=lvl,
            n_rows=n_rows, n_segments=n_segments, tile_n=tile_n))
        pl.when((level == lvl) & last)(functools.partial(
            _resolve, hist_ref, prefix_ref, krem_ref, level=lvl,
            n_rows=n_rows, n_segments=n_segments))

    @pl.when((level == _LEVELS - 1) & last)
    def _emit():
        out_ref[...] = prefix_ref[...]


def kth_key_u_pallas(
    u: jax.Array,          # (B, n) uint32 keys, n % tile_n == 0
    seg_ids: jax.Array,    # (n,) int32, -1 = padding
    ks: jax.Array,         # (S,) int32 per-segment widths
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:            # (B, S) uint32 thresholds
    b, n = u.shape
    if n % tile_n:
        raise ValueError(f"n={n} must be a multiple of tile_n={tile_n}")
    s = ks.shape[0]
    s_pad = -(-s // 8) * 8
    ks = jnp.zeros((s_pad, 1), jnp.int32).at[:s, 0].set(ks.astype(jnp.int32))
    n_tiles = n // tile_n

    out = pl.pallas_call(
        functools.partial(_kernel, n_rows=b, n_segments=s_pad,
                          n_tiles=n_tiles, tile_n=tile_n),
        grid=(_LEVELS, n_tiles),
        in_specs=[
            pl.BlockSpec((b, tile_n), lambda l, t: (0, t)),
            pl.BlockSpec((1, tile_n), lambda l, t: (0, t)),
            pl.BlockSpec((s_pad, 1), lambda l, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b, s_pad, 1), lambda l, t: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((b, s_pad, _BINS), jnp.float32),
            pltpu.VMEM((b, s_pad, 1), jnp.int32),
            pltpu.VMEM((b, s_pad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="hist_select",
    )(jax.lax.bitcast_convert_type(u, jnp.int32), seg_ids.reshape(1, n), ks)
    return jax.lax.bitcast_convert_type(out[:, :s, 0], jnp.uint32)
