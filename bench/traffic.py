"""The one stream generator: a deployment's key skew plus a traffic mix ->
a seeded pool of epochs.

A configuration file states the block space and how its accesses are
spread over it (``popularity``); a traffic file states how accesses arrive
(batch size, batches per epoch, hot-set rotations).  Both are data, so a
later cell needs a JSON file and no code.

Popularity kinds:

* ``zipf`` -- Zipf(``alpha``) over popularity ranks, with ranks laid on
  blocks by a seeded permutation (ids carry no popularity order).  A
  rotation by ``rotate_by`` moves rank ``r`` onto the block of rank
  ``(r + phase * rotate_by) % n``: the same skew on a different hot head.
* ``regions`` -- contiguous block ranges, each taking a share of the
  accesses, uniform within the range (the paper's mmap-bench).

Every seed gives the same sizes and the same arrival pattern; only which
blocks are drawn changes.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

__all__ = ["Pool", "make_pool"]


class Pool:
    """Epochs to replay cyclically, plus the layout a compiler would know."""

    def __init__(self, epochs: List[np.ndarray],
                 rank_to_page: Optional[np.ndarray]):
        self.epochs = epochs
        self.rank_to_page = rank_to_page

    def epoch(self, i: int) -> np.ndarray:
        return self.epochs[i % len(self.epochs)]

    def upcoming(self, i: int, depth: int) -> tuple:
        """The ``depth`` epochs queued behind epoch ``i``."""
        return tuple(self.epoch(i + 1 + d) for d in range(depth))


def _zipf_sampler(pop: dict, n: int, rng: np.random.Generator):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** (-float(pop["alpha"])))
    cdf /= cdf[-1]
    rank_to_page = rng.permutation(n).astype(np.int32)

    def draw(size: int, shift: int) -> np.ndarray:
        rank = np.searchsorted(cdf, rng.random(size))
        if shift:
            rank = (rank + shift) % n
        return rank_to_page[rank]

    return draw, rank_to_page


def _regions_sampler(pop: dict, n: int, rng: np.random.Generator):
    regions = pop["regions"]
    shares = np.asarray([r["share"] for r in regions], np.float64)
    if not np.isclose(shares.sum(), 1.0):
        raise ValueError(f"region shares sum to {shares.sum()}, not 1")
    bounds = [(int(r["start"]), int(r["end"])) for r in regions]
    if any(not 0 <= a < b <= n for a, b in bounds):
        raise ValueError(f"regions {bounds} do not lie in [0, {n})")
    cum = np.cumsum(shares)

    def draw(size: int, shift: int) -> np.ndarray:
        which = np.searchsorted(cum, rng.random(size), side="right")
        which = np.minimum(which, len(bounds) - 1)
        out = np.empty(size, np.int32)
        for j, (a, b) in enumerate(bounds):
            sel = which == j
            out[sel] = rng.integers(a, b, int(sel.sum()))
        if shift:
            out = ((out.astype(np.int64) + shift) % n).astype(np.int32)
        return out

    return draw, None


_KINDS = {"zipf": _zipf_sampler, "regions": _regions_sampler}


def make_pool(config: dict, traffic: dict, seed: int) -> Pool:
    """``config['stream_pool_epochs']`` epochs of shape
    ``(batches_per_epoch, batch)``, int32 block ids.  Epoch ``e`` belongs
    to phase ``(e // epochs_per_phase) % phases``; phase ``p`` shifts the
    popularity by ``p * (n_blocks // rotate_divisor)`` blocks."""
    n = int(config["n_blocks"])
    n_epochs = int(config["stream_pool_epochs"])
    per_phase = int(traffic.get("epochs_per_phase", n_epochs))
    phases = int(traffic.get("phases", 1))
    rotate_by = n // int(traffic.get("rotate_divisor", 1)) if phases > 1 else 0
    shape = (int(traffic["batches_per_epoch"]), int(traffic["batch"]))
    pop = config["popularity"]
    rng = np.random.default_rng(int(seed))
    draw, rank_to_page = _KINDS[pop["kind"]](pop, n, rng)
    epochs = []
    for e in range(n_epochs):
        phase = (e // per_phase) % phases
        ids = draw(shape[0] * shape[1], phase * rotate_by)
        epochs.append(np.ascontiguousarray(ids.reshape(shape), np.int32))
    return Pool(epochs, rank_to_page)
