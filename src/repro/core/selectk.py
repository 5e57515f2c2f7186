"""Exact top-k selection without full-length sorts — the fused runtime's core.

``jax.lax.top_k`` and ``jnp.argsort`` on the XLA CPU backend cost hundreds of
milliseconds per million elements (a full O(n log n) sort each), which is why
the per-lane epoch loop cannot reach paper scale: five policy lanes issue
five ``top_k``s plus two ``argsort``s per epoch.  The fused ``epoch_step``
replaces them with O(n) primitives built from compare+reduce passes (~5 ms
per million on the same backend):

* :func:`select_top_k` — bit-identical replacement for ``lax.top_k(key, k)``
  (values descending, ties broken lowest-index-first): a 32-step bitwise
  binary search finds the k-th largest key, :func:`compact` gathers the
  selected indices from the mask's prefix count, and only the k survivors
  are sorted.
* :func:`compact` — the first k selected indices of a prefix count: one
  scatter pass over n (each selected index written to slot ``count - 1``)
  where ``k * ceil(log2(n + 1)) >= n / SCATTER_C``, a ``searchsorted`` of
  the k targets below that (:func:`compact_impl`, static shapes only).
* :func:`top_k_mask` — membership mask of the same selection, for consumers
  that need set intersections (epoch-hot scoring) rather than order.
* :func:`stable_rank_sparse` — ``argsort(argsort(x))`` for non-negative
  arrays with a static bound on the number of positives (PEBS epoch deltas:
  at most one positive block per sample), again sorting only the positives.

All keys are int32.  Non-negative float32 scores participate via
:func:`sortable_key` (IEEE-754 bit patterns of non-negative floats are
order-isomorphic to their int32 interpretation), so float and integer lanes
share one selection kernel.  Every function is shape-polymorphic over leading
batch (lane) axes and safe under ``vmap``/``jit``/SPMD partitioning.

All selection entry points take an optional ``backend`` (a
``repro.kernels.dispatch.PallasBackend``): when its ``select`` site is
``"hist_select"`` and ``k`` is static, the 32-round threshold search is
replaced by the ``kernels.hist_select`` Pallas radix-histogram kernel (4
grid passes instead of 32), bit-identical by the same
largest-``t``-with-``count(u >= t) >= k`` definition.  ``None`` (the
default) keeps the pure-XLA path.  The size rule lives in
``dispatch.resolve_backend``; a row past ``hist_select.MAX_N`` raises
here instead of quietly taking the XLA search.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import hist_select
from ..obs import metrics as obs_metrics
from ..obs.trace import named_scope

__all__ = [
    "sortable_key", "select_top_k", "top_k_mask", "stable_rank_sparse",
    "compact", "compact_impl", "segment_top_k_mask",
]

_SIGN = jnp.uint32(0x80000000)

# Eager-input contract checking for sortable_key (skipped under tracing —
# the fused epoch step cannot afford a host round-trip); set False to
# silence in long host-loop runs.
CHECK_SORTABLE_KEYS = True


def sortable_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 key with the same ordering.

    Contract: every value must be either **non-negative** or equal to **one
    shared negative sentinel** (e.g. the hinted lane's score sentinel -1).
    IEEE-754 bit patterns of non-negative floats are order-isomorphic to
    their int32 interpretation, and any negative float's pattern compares
    below all non-negative ones — but order *among distinct* negatives is
    REVERSED, so two different negative values would rank backwards.
    Concrete (non-traced) inputs are checked; traced inputs are the
    caller's responsibility (the fused runtime's keys are non-negative by
    construction)."""
    x32 = x.astype(jnp.float32)
    if CHECK_SORTABLE_KEYS and not isinstance(x32, jax.core.Tracer):
        neg = np.asarray(x32)
        neg = neg[neg < 0]
        if neg.size and np.unique(neg).size > 1:
            raise ValueError(
                "sortable_key: negative inputs must all equal one shared "
                f"sentinel; got distinct negatives {np.unique(neg)[:4]} — "
                "their relative order would be reversed")
    return jax.lax.bitcast_convert_type(x32, jnp.int32)


def _to_u(key: jax.Array) -> jax.Array:
    """int32 -> uint32, order-preserving (flip the sign bit)."""
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ _SIGN


def prefix_sum(x: jax.Array, chunk: int = 256) -> jax.Array:
    """Inclusive int32 prefix sum along the last axis.  XLA's cumsum on CPU
    runs log(n) full passes; chunking to (m, chunk) caps the scanned width,
    cutting ~1/3 of the wall time at 1M elements.  Non-dividing lengths are
    zero-padded up to the next chunk multiple (padding past the end never
    feeds back into the first n prefixes), so the chunked path is taken for
    EVERY length — it used to fall back to a full ``jnp.cumsum`` whenever
    ``n % chunk != 0``, silently costing the log(n) passes on exactly the
    ragged sizes real segment slices produce."""
    xi = x.astype(jnp.int32)
    n = xi.shape[-1]
    if n == 0:
        return xi
    pad = (-n) % chunk
    if pad:
        xi = jnp.pad(xi, [(0, 0)] * (xi.ndim - 1) + [(0, pad)])
    xr = xi.reshape(xi.shape[:-1] + (xi.shape[-1] // chunk, chunk))
    within = jnp.cumsum(xr, axis=-1)
    tot = within[..., -1]
    offs = jnp.cumsum(tot, axis=-1) - tot
    out = (within + offs[..., None]).reshape(xi.shape)
    return out[..., :n] if pad else out


def _kth_largest(u: jax.Array, k) -> jax.Array:
    """Largest threshold ``t`` with ``count(u >= t) >= k`` per leading batch
    element, without a sort: a bitwise binary search — 32 rounds, each one
    compare+sum pass over the data (XLA fuses compare and reduce; resolving
    more bits per round costs a full extra pass, so one bit per round wins).
    ``k`` may be a static int or a per-batch traced array (dynamic sizes)."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (31 - i))
        n_ge = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(n_ge >= k, cand, t)

    return jax.lax.fori_loop(0, 32, body, jnp.zeros(u.shape[:-1], jnp.uint32))


def _uses_kernel(backend) -> bool:
    return backend is not None and backend.uses_select_kernel


def _kth_dispatch(u: jax.Array, k, backend) -> jax.Array:
    """k-th-largest threshold: the hist_select radix kernel when the
    backend selects it and ``k`` is static (4 grid passes), the 32-round
    bitwise search otherwise.  Identical thresholds either way: both
    compute the largest ``t`` with ``count(u >= t) >= k``."""
    if not _uses_kernel(backend) or not isinstance(k, int):
        return _kth_largest(u, k)
    n = u.shape[-1]
    t = hist_select.kth_key_u(
        u.reshape((-1, n)), jnp.zeros((n,), jnp.int32), (k,),
        tile_n=backend.select_tile_n, use_pallas=True,
        interpret=backend.interpret)
    return t.reshape(u.shape[:-1])


def _selection_mask(u: jax.Array, k, backend=None):
    """Boolean mask of the k largest (ties resolved lowest-index-first) and
    its inclusive prefix count.  ``k``: static int or per-batch array."""
    k_b = k[..., None] if isinstance(k, jax.Array) else k
    with named_scope("selectk.threshold"):
        t = _kth_dispatch(u, k, backend)[..., None]
    with named_scope("selectk.mask"):
        gt = u > t
        eq = u == t
        n_gt = jnp.sum(gt.astype(jnp.int32), axis=-1, keepdims=True)
        eq_rank = prefix_sum(eq) - 1
        sel = gt | (eq & (eq_rank < (k_b - n_gt)))
        return sel, prefix_sum(sel)


def top_k_mask(key: jax.Array, k: int, *, backend=None) -> jax.Array:
    """(..., n) bool: membership in ``lax.top_k(key, k)``'s selection."""
    return _selection_mask(_to_u(key), min(k, key.shape[-1]), backend)[0]


def bottom_k_mask(key: jax.Array, counts) -> jax.Array:
    """(..., n) bool: the per-batch ``counts`` smallest keys, ties resolved
    lowest-index-first — the first ``counts`` entries of a stable ascending
    argsort, as a mask.  ``counts`` may be traced (clipped to [0, n])."""
    n = key.shape[-1]
    counts = jnp.clip(counts, 0, n)
    return _selection_mask(~_to_u(key), counts)[0]


# The rule's constant: a v5e gathers one element of one binary-search round
# in 13-19 ns where the scatter below writes one update in 4.8-6.3 ns, so
# the scatter wins once k * ceil(log2(n + 1)) reaches about n / 3
# (benchmarks/compact_crossover.py: the two meet at n / 3.0 for
# n = 5,000,000 and at n / 3.8 for n = 2,621,440).
SCATTER_C = 3

# Trace-time counter: which algorithm each named compaction site compiled
# to, one tick per trace (the epoch loop traces once).
COMPACT_IMPL = obs_metrics.REGISTRY.counter(
    "repro_compact_impl_total",
    help="Traces of a compaction site, by the algorithm its shape chose")


def compact_impl(n: int, k: int) -> str:
    """``"scatter"`` (n updates per row) when ``k * ceil(log2(n + 1))``
    binary-search gathers reach ``n / SCATTER_C``, else ``"search"``.
    Static shapes only."""
    return ("scatter" if SCATTER_C * k * math.ceil(math.log2(n + 1)) >= n
            else "search")


def compact(csel: jax.Array, k: int, *, site: Optional[str] = None
            ) -> jax.Array:
    """Indices of the first k selected elements in ascending order, given the
    inclusive prefix count of a selection mask along the last axis (fewer
    than k true entries fill with n).  Shared by :func:`select_top_k`,
    ``placement.apply_plan``'s free-slot assignment and
    :func:`stable_rank_sparse`.

    The j-th selected element is the position i where the count steps up to
    ``csel[i] == j + 1``, so one pass that writes each such i to slot
    ``csel[i] - 1`` answers in n updates per row; a binary search of
    ``csel`` for each target 1..k takes ``k * ceil(log2(n + 1))`` gathers.
    :func:`compact_impl` picks by shape; both give the same indices.
    ``site`` names the call site in ``repro_compact_impl_total``."""
    impl = compact_impl(csel.shape[-1], k)
    if site is not None:
        COMPACT_IMPL.labels(site=site, impl=impl).inc()
    if impl == "scatter":
        return _compact_scatter(csel, k)
    return _compact_search(csel, k)


def _compact_search(csel: jax.Array, k: int) -> jax.Array:
    targets = jnp.arange(1, k + 1, dtype=csel.dtype)

    def pick(cs):
        return jnp.searchsorted(cs, targets, side="left").astype(jnp.int32)

    for _ in range(csel.ndim - 1):
        pick = jax.vmap(pick)
    return pick(csel)


def _compact_scatter(csel: jax.Array, k: int) -> jax.Array:
    n = csel.shape[-1]
    iota = jnp.arange(n, dtype=jnp.int32)

    def row(cs):
        prev = jnp.concatenate([jnp.zeros((1,), cs.dtype), cs[:-1]])
        dest = jnp.where((cs > prev) & (cs <= k), cs - 1, k)   # k: dropped
        return jnp.full((k,), n, jnp.int32).at[dest].set(iota, mode="drop")

    # One row at a time, the mask recovered from the count inside the row:
    # compiled for a v5e at both benchmark cells' sizes this adds no
    # temporary to the epoch step, where a batched scatter's (rows, n, 2)
    # index tuples take 480 MB at the DLRM select's shape, and a mask passed
    # in from outside the loop raises the step's peak by 46 MB at
    # mmap-bench's.
    out = jax.lax.map(row, csel.reshape((-1, n)))
    return out.reshape(csel.shape[:-1] + (k,))


def select_top_k(key: jax.Array, k: int, return_mask: bool = False,
                 *, backend=None):
    """Drop-in ``lax.top_k(key, k)`` on int32 keys: ``(values, indices)``,
    values descending, ties lowest-index-first — in O(n) passes plus one
    O(k log k) sort of the survivors.  ``return_mask=True`` also returns the
    (..., n) membership mask (an intermediate, free to expose)."""
    n = key.shape[-1]
    k = min(k, n)
    u = _to_u(key)
    sel, csel = _selection_mask(u, k, backend)
    with named_scope("selectk.compact"):
        ids = compact(csel, k, site="select")     # ascending index order

    def order(us, i):
        # ascending ~u == descending u; stable keeps ascending-index ties
        return jax.lax.sort_key_val(~us, i, is_stable=True)[1]

    for _ in range(key.ndim - 1):
        order = jax.vmap(order)
    with named_scope("selectk.order"):
        u_sel = jnp.take_along_axis(u, ids, axis=-1)
        ids_sorted = order(u_sel, ids)
        vals = jnp.take_along_axis(key, ids_sorted, axis=-1)
    if return_mask:
        return vals, ids_sorted, sel
    return vals, ids_sorted


def segment_top_k_mask(key: jax.Array, bounds, caps, *,
                       backend=None) -> jax.Array:
    """Per-segment top-k membership over static contiguous segments.

    ``key`` (..., n) int32 selection keys; ``bounds`` a static length-(S+1)
    cumulative offset tuple partitioning the last axis into S segments
    (``bounds[s]:bounds[s+1]``); ``caps`` a static per-segment selection
    width.  Returns the (..., n) bool mask marking, within every segment
    independently, that segment's ``min(caps[s], len)`` largest keys (ties
    lowest-index-first, exactly :func:`top_k_mask`'s tie-break).

    This is the fused runtime's multi-tenant quota primitive: masking a
    lane's selection key to ``int32.min`` outside this mask turns the global
    top-k select into a *segment-capped* select — every tenant keeps its own
    ``caps[t]`` best candidates in the running no matter how loud a
    neighbouring tenant's counters are, at the cost of one O(n_t)
    threshold-select per segment (no sorts).

    With a Pallas ``backend`` the per-segment thresholds all come out of ONE
    ``hist_select`` invocation (the caps become per-tenant rows of the radix
    histogram) and the per-segment tie-break ranks are recovered from global
    prefix sums rebased at the static segment starts — bit-identical to the
    per-slice path, without its S separate selects.
    """
    if not _uses_kernel(backend):
        parts = [
            top_k_mask(jax.lax.slice_in_dim(key, int(a), int(b), axis=-1),
                       min(int(cap), int(b) - int(a)))
            for a, b, cap in zip(bounds, bounds[1:], caps)
        ]
        return jnp.concatenate(parts, axis=-1)

    n = key.shape[-1]
    edges = [int(b) for b in bounds]
    lens = np.diff(np.asarray(edges))
    ks = tuple(min(int(c), int(l)) for c, l in zip(caps, lens))
    seg = np.repeat(np.arange(len(ks), dtype=np.int32), lens)
    u = _to_u(key).reshape((-1, n))
    with named_scope("selectk.threshold"):
        t = hist_select.kth_key_u(
            u, jnp.asarray(seg), ks, tile_n=backend.select_tile_n,
            use_pallas=True, interpret=backend.interpret)   # (B, S) uint32

    def widen(per_seg):             # (B, S) -> (B, n), constant per segment
        # one broadcast per static segment: no index arrays, no gather
        return jnp.concatenate(
            [jnp.broadcast_to(per_seg[:, s:s + 1], (per_seg.shape[0], int(l)))
             for s, l in enumerate(lens)], axis=-1)

    t_elem = widen(t)
    gt = u > t_elem
    eq = u == t_elem
    # per-segment prefix ranks = global inclusive prefix sums rebased at the
    # (static) segment starts; exclusive-at-start values read via a 0-column
    zero = jnp.zeros(u.shape[:-1] + (1,), jnp.int32)
    cgt = jnp.concatenate([zero, prefix_sum(gt)], axis=-1)
    ceq = jnp.concatenate([zero, prefix_sum(eq)], axis=-1)
    n_gt = cgt[..., edges[1:]] - cgt[..., edges[:-1]]       # (B, S)
    allow_eq = jnp.asarray(ks, jnp.int32)[None, :] - n_gt
    eq_rank = ceq[..., 1:] - widen(ceq[..., edges[:-1]]) - 1
    sel = gt | (eq & (eq_rank < widen(allow_eq)))
    return sel.reshape(key.shape)


def stable_rank_sparse(x: jax.Array, max_positive: int) -> jax.Array:
    """``jnp.argsort(jnp.argsort(x))`` for a 1-D non-negative int32 array with
    at most ``max_positive`` positive entries (a *static* bound).

    A stable ascending argsort of such an array ranks the zeros first in
    index order, then the positives by (value, index) — so the full-length
    double sort reduces to a cumsum over the zeros plus a sort of just the
    positives.  Exact whenever the bound holds (the fused runtime derives it
    from the epoch's access count and the PEBS period).
    """
    n = x.shape[0]
    s = min(max_positive, n)
    pos = x > 0
    n_zero = n - jnp.sum(pos.astype(jnp.int32))
    rank = prefix_sum(~pos) - 1                          # zero ranks
    ids = compact(prefix_sum(pos), s)                    # fill -> n
    vals = jnp.where(ids < n, x[jnp.minimum(ids, n - 1)], jnp.iinfo(jnp.int32).max)
    _, ids_sorted = jax.lax.sort_key_val(_to_u(vals), ids, is_stable=True)
    return rank.at[jnp.where(ids_sorted < n, ids_sorted, n)].set(
        n_zero + jnp.arange(s, dtype=jnp.int32), mode="drop")
