"""Placement — the one bounded-fast-tier indirection substrate.

A placement is the pair of mutually-inverse maps every tiered object in this
repo carries:

  ``slot_to_block``  (..., n_slots)   block id in each fast slot, -1 = free
  ``block_to_slot``  (..., n_blocks)  fast slot of each block,   -1 = slow-only

plus the *bounded-promotion invariant* around ``policy.plan_eviction``: a
promotion plan fills free slots first in priority order; when slots run out,
the epoch-coldest residents are demoted — never a block the plan still wants
ahead of an empty slot.

Before this module the sequence was duplicated three ways (EpochRuntime's
per-lane numpy maps, TieredEmbedding.rebalance, TieredStore's
demote-on-overwrite); now it lives here once:

* :func:`apply_plan` — pure ``jnp`` promote+evict, usable inside ``jit`` and
  ``vmap``-stacked over policy lanes ((L, n_slots)/(L, n_blocks) leading
  axes).  This is what the fused ``epoch_step`` runs.
* :func:`demote_idle` — watermark demotion (free residents an epoch never
  touched), same pure form.
* :func:`plan_promotion` — the host-side variant for stores that must *move
  payload bytes* along with the maps: returns the victims to demote so the
  caller can drive ``TieredStore.migrate`` (TieredEmbedding's control plane).

Everything is functional; ``Placement`` is a pytree and can be sharded,
donated, and carried through ``lax``-land like any other state.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import policy, selectk
from ..obs.trace import named_scope

__all__ = ["Placement", "apply_plan", "demote_idle", "plan_promotion"]

# Free fast slots sort at this heat in eviction order: after every finite
# resident (so cold residents are demoted first) but before +inf-guarded
# still-wanted residents — exactly policy.coldest_victims' convention.
_FREE_HEAT = float(np.iinfo(np.int32).max)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Placement:
    """Bounded fast-tier indirection maps (optionally lane-stacked)."""

    slot_to_block: jax.Array     # (..., n_slots) int32, -1 = free
    block_to_slot: jax.Array     # (..., n_blocks) int32, -1 = slow-only

    @staticmethod
    def create(n_blocks: int, n_slots: int, lanes: Optional[int] = None,
               ) -> "Placement":
        """Everything slow-resident (the paper's profiling phase).  With
        ``lanes`` the maps get a leading lane axis (one placement per policy
        lane, vmapped together by the fused runtime)."""
        lead = () if lanes is None else (int(lanes),)
        return Placement(
            slot_to_block=jnp.full(lead + (int(n_slots),), -1, jnp.int32),
            block_to_slot=jnp.full(lead + (int(n_blocks),), -1, jnp.int32),
        )

    @property
    def n_slots(self) -> int:
        return self.slot_to_block.shape[-1]

    @property
    def n_blocks(self) -> int:
        return self.block_to_slot.shape[-1]

    @property
    def fast_mask(self) -> jax.Array:
        return self.block_to_slot >= 0

    def resident(self) -> jax.Array:
        """Occupied-slot count (per lane, if stacked)."""
        return jnp.sum((self.slot_to_block >= 0).astype(jnp.int32), axis=-1)


def _scatter_ids(arr: jax.Array, idx: jax.Array, valid: jax.Array,
                 val: jax.Array) -> jax.Array:
    """Batched last-axis ``arr[..., idx] = val`` where ``valid``; invalid
    entries are routed out of bounds and dropped (no undefined duplicate
    writes at a clamped index)."""
    oob = jnp.asarray(arr.shape[-1], idx.dtype)
    return jnp.put_along_axis(arr, jnp.where(valid, idx, oob),
                              val.astype(arr.dtype), axis=-1,
                              inplace=False, mode="drop")


def demote_idle(p: Placement, est: jax.Array, enable) -> Tuple[Placement, jax.Array]:
    """Watermark demotion: free every resident block whose epoch estimate is
    zero (else a reactive tier fills once and freezes).  ``enable`` gates the
    whole operation (scalar or per-lane bool).  Returns (placement, count)."""
    idle = p.fast_mask & (est == 0) & enable
    b2s = jnp.where(idle, -1, p.block_to_slot)
    occ = p.slot_to_block >= 0
    blk = jnp.maximum(p.slot_to_block, 0)
    slot_idle = occ & jnp.take_along_axis(idle, blk, axis=-1)
    s2b = jnp.where(slot_idle, -1, p.slot_to_block)
    return (Placement(slot_to_block=s2b, block_to_slot=b2s),
            jnp.sum(idle.astype(jnp.int32), axis=-1))


def apply_plan(p: Placement, want: jax.Array, est: jax.Array,
               ) -> Tuple[Placement, jax.Array, jax.Array]:
    """Promote ``want`` (priority-ordered unique block ids, -1 padding) into
    the bounded fast tier; when free slots run short, demote the coldest
    residents by ``est`` with plan-guarded victims (``policy.plan_eviction``'s
    invariant).  Pure jnp over the trailing axis — works per lane and
    lane-stacked.  Returns (placement, promoted, demoted) counts.
    """
    n, k = p.n_blocks, p.n_slots
    s2b, b2s = p.slot_to_block, p.block_to_slot

    valid = want >= 0
    safe_want = jnp.maximum(want, 0)
    wanted = _scatter_ids(jnp.zeros(b2s.shape, jnp.bool_), want, valid,
                          jnp.ones(want.shape, jnp.bool_))
    new = valid & (jnp.take_along_axis(b2s, safe_want, axis=-1) < 0)
    n_new = jnp.sum(new.astype(jnp.int32), axis=-1, keepdims=True)
    n_free = jnp.sum((s2b < 0).astype(jnp.int32), axis=-1, keepdims=True)
    need = n_new - n_free

    # eviction order: finite-heat residents coldest-first, then free slots,
    # then +inf-guarded wanted residents (identical to policy.plan_eviction);
    # the `need` coldest slots come from an O(n_slots) threshold selection
    # with stable (lowest-slot-first) tie-break — no sort.
    occ = s2b >= 0
    blk = jnp.maximum(s2b, 0)
    heat = jnp.where(
        occ,
        jnp.where(jnp.take_along_axis(wanted, blk, axis=-1), jnp.inf,
                  jnp.take_along_axis(est.astype(jnp.float32), blk, axis=-1)),
        _FREE_HEAT)
    victim = occ & selectk.bottom_k_mask(selectk.sortable_key(heat),
                                         jnp.squeeze(need, -1))
    demoted = jnp.sum(victim.astype(jnp.int32), axis=-1)

    b2s = _scatter_ids(b2s, s2b, victim, jnp.full(s2b.shape, -1, jnp.int32))
    s2b = jnp.where(victim, -1, s2b)

    # fill free slots (ascending slot index) with new blocks in plan order:
    # the j-th new block lands in the j-th free slot, located by prefix count
    free = s2b < 0
    cfree = jnp.cumsum(free.astype(jnp.int32), axis=-1)
    n_free = cfree[..., -1:]
    new_rank = jnp.cumsum(new.astype(jnp.int32), axis=-1) - 1
    assign = new & (new_rank < n_free)
    with named_scope("placement.free_slots"):
        # (..., k), fill -> k
        free_slot = selectk.compact(cfree, k, site="free_slots")
    slot_for = jnp.take_along_axis(
        free_slot, jnp.clip(new_rank, 0, k - 1), axis=-1)
    s2b = _scatter_ids(s2b, slot_for, assign, want)
    b2s = _scatter_ids(b2s, want, assign, slot_for)
    promoted = jnp.sum(assign.astype(jnp.int32), axis=-1)
    return Placement(slot_to_block=s2b, block_to_slot=b2s), promoted, demoted


def plan_promotion(p: Placement, want, est) -> Tuple[np.ndarray, Optional[jax.Array]]:
    """Host-side control-plane variant for payload-carrying stores: given a
    plan's ids and the epoch estimate, return ``(want_ids, victims)`` where
    ``victims`` (or None) are the demotions that make the promotions fit —
    exactly the sequence ``TieredStore.migrate`` expects.  The eviction
    choice is the same ``policy.plan_eviction`` the device path applies."""
    want = np.asarray(want).reshape(-1)
    want = want[want >= 0]
    b2s = np.asarray(p.block_to_slot)
    n_new = int(np.sum(b2s[want] < 0)) if want.size else 0
    free = p.n_slots - int(np.sum(np.asarray(p.slot_to_block) >= 0))
    need = n_new - free
    victims = None
    if need > 0:
        victims = policy.plan_eviction(
            jnp.asarray(np.asarray(est, np.float32)), jnp.asarray(want),
            p.slot_to_block, int(need))
    return want, victims
