"""Epoch-driven tiering runtime — observe -> decide -> migrate -> account.

The paper's headline numbers come from a one-shot profile->promote->replay
methodology; its §VI vision (reactive placement, proactive movement, compiler
hints from a programmable HMU) is inherently *online*.  This module is that
online regime: a loop over epochs in which

  1. **observe**  — the whole epoch's access stream is fed to all three
     collectors (HMU / PEBS / NB) and the ground-truth counter in ONE jit
     dispatch (``telemetry.observe_all``'s ``lax.scan``),
  2. **decide**   — every policy lane (five of them, one per §VI strategy)
     turns its collector's *epoch-local* estimate into a migration plan,
  3. **migrate**  — promotions are applied against a bounded fast tier;
     when slots run out the lane demotes plan-guarded coldest victims first,
  4. **account**  — the epoch is charged: modeled access time under the
     placement that actually *served* it (decided from data up to the
     previous epoch — no time travel), plus the collector's host tax and the
     epoch's migration traffic; accuracy/coverage are scored against the
     epoch's own true top-K.

**Dispatch accounting.**  Steps 2-4 are one jit'd ``_epoch_step`` that keeps
every lane's placement state — a lane-stacked :class:`~repro.core.placement.
Placement` plus the EWMA predictor — resident on device and ``vmap``s the
policy/migration kernels over the lane axis, so a whole epoch is exactly
**two dispatches** (``observe_all`` + ``epoch_step``; counted in
:data:`DISPATCH_COUNTS`, traced-once proven via :data:`TRACE_COUNTS`) and
only the :class:`EpochRecord` fields cross the device boundary.
Per-lane branching is a lane-config tuple (estimate source, selection
threshold, move cap, hint weight) baked into the trace; top-k selection uses
:mod:`~repro.core.selectk`'s O(n) kernels instead of full-length sorts.  The
pre-refactor per-lane host loop (five policy lanes x several small jits +
four full-array pulls per epoch) is preserved as ``fused=False`` — the
bit-identity reference and the benchmark baseline.

**Pipelined record sync.**  The record fields themselves are accumulated on
device: ``_epoch_step`` writes each epoch's scalars, per-lane counters, and
per-tenant rows into row ``out_row`` of a stacked ``(sync_every,)`` buffer
pytree (``_FusedState.out_buf`` — ``out_row`` is a traced scalar, so K
boundaries never retrace), and the host pulls the whole buffer in ONE
``jax.device_get`` every ``sync_every`` epochs (counted in
``DISPATCH_COUNTS["record_sync"]``; partial tail flushed on loop exit).
With ``sync_every=1`` (default) the loop is the historical synchronous one;
with K>1 the flush happens *after* the next epoch's ``observe_all`` is
dispatched, so the host assembles :class:`EpochRecord`\\ s — cumulative
host-tax deltas and the prefetch lane's pending-migration chain replayed in
dispatch order, hence bit-identical for every K — while the device streams
ahead.  Both jits donate their state operand (``donate_argnums=0``), so the
loop also never copies the collector/placement buffers; the telemetry that
"observes without interfering" finally stops interfering with itself.
Donation bounds the pipeline depth: a donated operand must be *ready*
before its dispatch returns, so the host runs at most one epoch ahead of
the device — enough to overlap all its per-epoch work (hint refresh,
record assembly) with the in-flight step.  That overlap is real freed time
wherever host and device are separate resources (accelerator backends, a
multi-core host); on a single-core CPU host the two share the core and the
loop is throughput-neutral — which is why the benchmark gates below are
*structural* (sync count, dispatch count, bit-identity), not a wall-clock
ratio.

Policy lanes and their telemetry sources:

=================  =========================  ===============================
lane               estimate                   host tax per epoch
=================  =========================  ===============================
hmu_oracle         HMU epoch-delta counts     log drain (~ns/record)
nb_two_touch       NB cumulative faults       hint faults (~2 us each)
reactive_watermark HMU epoch-delta counts     log drain
proactive_ewma     EWMA of HMU epoch deltas   log drain
hinted             PEBS epoch-delta estimate  PEBS samples (~1.5 us each)
                   blended with static hints
prefetch           lookahead window over the  none (compiler hints are free
                   queued next-epoch batches  at run time)
=================  =========================  ===============================

**Hints.**  The ``hinted`` and ``prefetch`` lanes' rank arrays come from a
:class:`~repro.hints.HintPipeline` (``hints=`` at construction): per epoch
the pipeline's providers (static table analysis, bounded lookahead over the
batch queue, EWMA phase-change re-weighting) produce fresh ``hint_rank`` /
``prefetch_rank`` arrays which replace state leaves before the epoch step —
a host-to-device transfer counted in ``DISPATCH_COUNTS["hint_refresh"]``,
*not* a third dispatch.  The ``prefetch`` lane promotes blocks the lookahead
says the next epoch will touch, before the accesses land; its boundary
migration therefore streams concurrently with the epoch it serves, charged
component-wise in ``_record`` (access + migration - hidden overlap) —
equivalent to ``MemSystem.overlapped_epoch_time_s``, parity-tested in
``test_core_tiering`` — with the migration issued at the *previous* boundary
charged against the epoch it overlapped and its hidden share recorded in
``EpochRecord.hidden_s``.

**Multi-tenancy.**  A :class:`Tenancy` (built by ``repro.fleet``) declares
how one shared block space splits into per-tenant id ranges, each tenant's
true-hot-set size, and optional per-tenant quotas.  With quotas, every
lane's top-k select becomes *segment-capped* (``selectk.segment_top_k_mask``
masks each key row to each tenant's own top-``caps[t]`` before the global
select), so a noisy tenant cannot crowd a quiet one out of any lane's
candidate list — and because ``apply_plan`` never evicts a still-wanted
resident while ``sum(caps) <= k_hot``, a tenant's capped want is *admitted*
unconditionally: quotas are isolation guarantees.  Per-tenant accounting
(sums over each tenant's static id range plus each tenant's own
top-``hot_k[t]`` hot set) rides in the same single
device->host sync as the scalar record fields, one (L, T) row set per
epoch in ``EpochRuntime.tenant_records``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
from collections import deque
from functools import partial
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import metrics, policy, selectk
from . import telemetry as tel
from ..faults.model import (CARRY_BASE, COLLECTORS, LANE_COLLECTOR,
                            FaultModel, Hardening)
from ..kernels.dispatch import PallasBackend, resolve_backend
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..obs.trace import named_scope
from .costmodel import CXL_SYSTEM, MemSystem, split_accesses_by_tier
from .placement import Placement, apply_plan, demote_idle

__all__ = [
    "ALL_POLICIES", "DISPATCH_COUNTS", "TRACE_COUNTS",
    "Counters", "counting",
    "EpochRecord", "EpochRuntime", "Tenancy", "Trajectory",
]

ALL_POLICIES = (
    "hmu_oracle", "nb_two_touch", "reactive_watermark", "proactive_ewma",
    "hinted", "prefetch",
)

# Host-side cost per telemetry event (see dlrm.tracesim for the NB/PEBS
# calibration; HMU pays only bulk log processing — the paper's 'process the
# trace immediately', which NMC would shrink further).
NB_FAULT_COST_S = 2e-6
PEBS_SAMPLE_COST_S = 1.5e-6
HMU_DRAIN_COST_S = 2e-9

# Python-side counters.  TRACE_COUNTS ticks once per (shape, config) trace of
# the fused step — tests prove the epoch loop compiles once.  DISPATCH_COUNTS
# ticks per *call*: a fused epoch is exactly observe_all + epoch_step; the
# reference path's count grows with every policy-lane jit/eager op and
# full-array pull it issues.  "hint_refresh" counts HintPipeline refreshes —
# host-to-device transfers of the rank arrays, not dispatches — so the
# 2-dispatch/epoch claim stays auditable with hints enabled.  "record_sync"
# counts device->host record pulls (one batched ``jax.device_get`` of the
# stacked ``(sync_every,)`` record buffer): the synchronous loop pays one
# per epoch, ``sync_every=K`` exactly ceil(n_epochs / K) — the benchmark
# gate that keeps a reintroduced per-epoch host sync from landing.
#
# Since the repro.obs PR both dicts are CounterDict views over the process
# metrics registry (repro_trace_total / repro_dispatch_total, labelled by
# kind) so the same counts are scrapeable; the dict API and the never-zeroed
# reentrancy contract below are unchanged.
TRACE_COUNTS = obs_metrics.CounterDict(
    obs_metrics.REGISTRY.counter(
        "repro_trace_total",
        help="XLA (re)traces of the fused epoch step / observe_all"),
    "kind", keys=("epoch_step",))
DISPATCH_COUNTS = obs_metrics.CounterDict(
    obs_metrics.REGISTRY.counter(
        "repro_dispatch_total",
        help="Host->device dispatches and transfers by kind"),
    "kind", keys=("observe_all", "epoch_step", "reference",
                  "hint_refresh", "record_sync"))


class _CounterView:
    """Read-only scope-relative view of one live counter dict: each key reads
    as (current total) - (total at scope entry).  The live dict is never
    mutated, so any number of views — nested, overlapping, or read while an
    inner scope is open — stay correct simultaneously."""

    def __init__(self, live: Dict[str, int]):
        self._live = live
        self._base = dict(live)

    def __getitem__(self, key: str) -> int:
        if key not in self._live:       # fail fast like the dicts it wraps:
            raise KeyError(key)         # a typo'd gate must not read as 0
        return self._live[key] - self._base.get(key, 0)

    def get(self, key: str, default: int = 0) -> int:
        return self[key] if key in self._live else default

    def __contains__(self, key: str) -> bool:
        return key in self._live

    def __iter__(self):
        return iter(self._live)

    def keys(self):
        return self._live.keys()

    def items(self):
        return [(k, self[k]) for k in self._live]

    def __eq__(self, other) -> bool:
        if isinstance(other, _CounterView):
            other = dict(other.items())
        return dict(self.items()) == other

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"_CounterView({dict(self.items())!r})"


class Counters(NamedTuple):
    """The scope-relative counter views a :func:`counting` block observes
    (zero-based at entry): per-call dispatches, epoch_step traces, and the
    telemetry module's observe_all traces."""
    dispatch: _CounterView
    trace: _CounterView
    observe_trace: _CounterView


@contextlib.contextmanager
def counting():
    """Scoped view of the dispatch/trace counters.

    ``DISPATCH_COUNTS``, ``TRACE_COUNTS`` and ``telemetry.TRACE_COUNTS`` are
    module-level mutable dicts, so raw reads leak activity across tests and
    benchmark runs.  ``with counting() as c:`` snapshots all three at entry
    and hands back views that read each counter relative to that snapshot —
    ``c.dispatch`` etc. show exactly the activity since the block started.

    The live dicts are never zeroed or restored, which makes the scope
    safely **nestable**: an earlier implementation zeroed the dicts in
    place, so re-entering ``counting()`` (as :func:`repro.fleet.run_fleet`
    does around its per-tenant solo sub-runs) blanked the outer scope's
    accrual while the inner scope was open.  Now an outer view keeps
    reading correctly at any point — before, during, and after any number
    of inner scopes — and inner activity accrues outward, so enclosing
    accounting stays monotonic.
    """
    yield Counters(_CounterView(DISPATCH_COUNTS), _CounterView(TRACE_COUNTS),
                   _CounterView(tel.TRACE_COUNTS))


@dataclasses.dataclass
class EpochRecord:
    """One lane's accounting for one epoch."""
    epoch: int
    lane: str
    time_s: float            # access + host tax + migration
    access_s: float
    host_tax_s: float
    migration_s: float
    accuracy: float          # placement that served the epoch vs epoch top-K
    coverage: float
    resident: int            # fast blocks during the epoch
    promoted: int            # migrations applied at epoch end
    demoted: int
    host_events: float       # telemetry events charged this epoch
    hidden_s: float = 0.0    # migration time overlapped away (prefetch lane)
    quality: float = 1.0     # smoothed quality of the lane's primary
                             # collector (1.0 without hardening / for the
                             # collector-free prefetch lane)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Trajectory:
    """Per-epoch time series for every lane (the runtime's output)."""
    n_blocks: int
    k_hot: int
    records: Dict[str, List[EpochRecord]]

    def lane(self, name: str) -> List[EpochRecord]:
        return self.records[name]

    def times(self, name: str) -> np.ndarray:
        return np.array([r.time_s for r in self.records[name]])

    def to_json(self, **meta) -> str:
        return json.dumps({
            "n_blocks": self.n_blocks,
            "k_hot": self.k_hot,
            **meta,
            "lanes": {name: [r.to_dict() for r in recs]
                      for name, recs in self.records.items()},
        }, indent=1)


@dataclasses.dataclass
class _Lane:
    """Per-policy placement state of the *reference* path (host numpy maps;
    the fused path holds the same state lane-stacked in a Placement)."""
    name: str
    slot_to_block: np.ndarray            # (k,) int32, -1 = free
    block_to_slot: np.ndarray            # (n_blocks,) int32, -1 = slow-only
    pred: Optional[np.ndarray] = None    # EWMA state (proactive lane)

    @property
    def fast_mask(self) -> np.ndarray:
        return self.block_to_slot >= 0

    def resident_ids(self) -> np.ndarray:
        s = self.slot_to_block
        return s[s >= 0]


def _unique_in_order(ids: np.ndarray, k: int) -> np.ndarray:
    """Valid plan ids, de-duplicated preserving priority order, capped at k."""
    ids = np.asarray(ids).reshape(-1)
    ids = ids[ids >= 0]
    _, first = np.unique(ids, return_index=True)
    return ids[np.sort(first)][:k]


class Tenancy(NamedTuple):
    """Static multi-tenant layout of one shared block space (``repro.fleet``).

    ``offsets`` are the cumulative block offsets of the per-tenant id ranges
    (length T+1, ``offsets[0] == 0``, ``offsets[-1] == n_blocks``); tenant
    ``t`` owns global ids ``[offsets[t], offsets[t+1])``.  ``hot_k`` is each
    tenant's true-hot-set size — the denominator of its per-tenant coverage,
    i.e. the fast-tier target the tenant would run solo — and ``caps`` are
    per-tenant admission quotas applied to every lane's migration plan each
    epoch (``None`` = shared pool, no quota enforcement).  A tenant whose
    plan is quota-capped still gets its first ``caps[t]`` wanted blocks
    admitted *unconditionally* whenever ``sum(caps) <= k_hot``, because
    ``placement.apply_plan`` never evicts a still-wanted resident ahead of a
    free slot — admission quotas are therefore isolation guarantees, not
    just rate limits.  Hashable: baked into the fused trace like the rest
    of ``_FusedCfg``."""
    offsets: Tuple[int, ...]
    hot_k: Tuple[int, ...]
    caps: Optional[Tuple[int, ...]] = None

    @property
    def n_tenants(self) -> int:
        return len(self.offsets) - 1

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(b - a for a, b in zip(self.offsets, self.offsets[1:]))

    def validate(self, n_blocks: int, k_hot: int) -> None:
        offs = self.offsets
        if len(offs) < 2 or offs[0] != 0 or offs[-1] != n_blocks or any(
                b <= a for a, b in zip(offs, offs[1:])):
            raise ValueError(f"tenancy offsets must be strictly increasing "
                             f"from 0 to n_blocks={n_blocks}, got {offs}")
        if len(self.hot_k) != self.n_tenants or any(
                not 0 < h <= s for h, s in zip(self.hot_k, self.sizes)):
            raise ValueError(f"hot_k must give every tenant a size in "
                             f"(0, n_tenant_blocks], got {self.hot_k}")
        if self.caps is not None:
            if len(self.caps) != self.n_tenants or any(
                    c < 0 for c in self.caps):
                raise ValueError(f"caps must be one non-negative quota per "
                                 f"tenant, got {self.caps}")
            if sum(self.caps) > k_hot:
                raise ValueError(f"tenant caps sum to {sum(self.caps)} > "
                                 f"k_hot={k_hot}; quotas must fit the fast "
                                 f"tier for admission to be guaranteed")


# ======================================================  fused device step
class _FusedCfg(NamedTuple):
    """Hashable static config baked into the epoch_step trace."""
    lanes: Tuple[str, ...]
    n_blocks: int
    k_hot: int
    ewma_alpha: float
    hint_weight: float
    nb_rate_limit: Optional[int]
    reactive_hot_threshold: Optional[int]
    tenancy: Optional[Tenancy] = None
    hardening: Optional[Hardening] = None
    pallas: Optional[PallasBackend] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _FusedState:
    """Everything the epoch loop mutates, resident on device between epochs."""
    bundle: tel.TelemetryBundle
    placement: Placement         # lane-stacked: (L, k_hot) / (L, n_blocks)
    pred: jax.Array              # (n_blocks,) f32 EWMA (the proactive lane's)
    hint_rank: jax.Array         # (n_blocks,) f32 static priorities
    prefetch_rank: jax.Array     # (n_blocks,) f32 lookahead priorities
    prev_hmu: jax.Array          # (n_blocks,) i32 epoch-delta baselines
    prev_pebs: jax.Array
    out_buf: Dict[str, jax.Array]
                                 # stacked (sync_every,)-leading record
                                 # fields: scalars (K,), per-lane (K, L),
                                 # per-tenant (K, L, T) — the batched-sync
                                 # accumulator, donated like everything else
    # --- robustness leaves (None = subsystem off; presence keys the trace,
    #     so a fault-free runtime compiles exactly the seed program) -------
    prev_true: Optional[jax.Array] = None
                                 # (n_blocks,) i32 ground-truth baseline;
                                 # only with faults (d_hmu is no longer the
                                 # truth, so accounting keeps its own delta)
    stale: Optional[jax.Array] = None
                                 # (stale_epochs+1, 3, n_blocks) i32 delay
                                 # ring of [d_hmu, d_pebs, nb] estimates
    stale_ptr: Optional[jax.Array] = None      # () i32 ring write position
    quality: Optional[jax.Array] = None
                                 # (3,) f32 smoothed per-collector quality
                                 # (COLLECTORS order; hardening only)
    prev_nb: Optional[jax.Array] = None
                                 # (n_blocks,) i32 last served NB faults —
                                 # the NB quality signal's epoch baseline
    nb_ewma: Optional[jax.Array] = None
                                 # () f32 EWMA of NB epoch fault mass (the
                                 # "expected" NB signal quality divides by)
    cold_streak: Optional[jax.Array] = None
                                 # (L, n_blocks) i32 consecutive-cold-epoch
                                 # counters (demote hysteresis H > 1 only)


def _out_buf_init(sync_every: int, n_lanes: int,
                  tenancy: Optional[Tenancy],
                  hardening: Optional[Hardening] = None):
    """Zeroed device accumulator for ``sync_every`` epochs of record fields.
    Dtypes mirror what ``_epoch_step`` computes (hi/lo i32 collector
    scalars, i32 lane counts) so the buffered write is a pure row store —
    pulling row j yields bit-identical values to the per-epoch sync it
    replaces."""
    K, L = int(sync_every), int(n_lanes)

    def scal():
        return jnp.zeros((K,), jnp.int32)

    def lane():
        return jnp.zeros((K, L), jnp.int32)

    buf = {
        # collector event scalars ride as exact hi/lo int32 pairs (the
        # device carries them as faults.Counter64; the host recombines in
        # float64, exact to 2**53)
        "drained_hi": scal(), "drained_lo": scal(),
        "pebs_host_hi": scal(), "pebs_host_lo": scal(),
        "nb_host_hi": scal(), "nb_host_lo": scal(),
        "n_fast": lane(), "n_slow": lane(),
        "inter": lane(), "resident": lane(),
        "promoted": lane(), "demoted": lane(),
    }
    if hardening is not None:
        buf["quality"] = jnp.zeros((K, 3), jnp.float32)
    if tenancy is not None:
        T = tenancy.n_tenants
        buf["tenant"] = {
            key: jnp.zeros((K, L, T), jnp.int32)
            for key in ("n_fast", "n_slow", "inter", "resident",
                        "promoted", "demoted")
        }
    return buf


@partial(jax.jit, static_argnames=("cfg", "s_max"), donate_argnums=0)
def _epoch_step(state: _FusedState, epoch_accesses: jax.Array,
                out_row: jax.Array, *, cfg: _FusedCfg, s_max: int):
    """decide + migrate + account for every lane in ONE dispatch.

    ``epoch_accesses`` is traced and ``s_max`` (the static PEBS-positives
    bound) is quantized by the caller, so ragged epoch sizes share traces
    instead of recompiling the five-lane program per unique size.  The
    per-lane integer/scalar outputs the host needs to assemble
    :class:`EpochRecord`s are written into row ``row`` (traced, so neither
    the row position nor a ``sync_every`` boundary retraces) of the
    donated ``state.out_buf`` accumulator and ride back inside the state —
    nothing leaves the device until the runtime's batched record sync
    pulls the stacked buffer, and nothing (n_blocks,)-sized ever does.
    """
    TRACE_COUNTS["epoch_step"] += 1
    lanes, n, k = cfg.lanes, cfg.n_blocks, cfg.k_hot
    har = cfg.hardening
    b = state.bundle
    faulty = b.faults is not None

    # -- drain the HMU log (host tax charged below from the drained count)
    drained = b.hmu.log_used
    bundle = dataclasses.replace(b, hmu=tel.hmu_drain_cost(b.hmu))

    # -- epoch-local estimates (deltas against the previous epoch's totals).
    #    Without faults the HMU counter is exact, so d_hmu *is* the epoch's
    #    ground truth (bit-identical to d_true) — the oracle lane's selection
    #    doubles as the epoch-hot set and true_counts never needs its own
    #    ranking.  With faults the runtime carries its own prev_true
    #    baseline: accounting stays ground-truth while the lanes see only
    #    what their (degraded) collectors deliver.
    true_now = b.true_counts
    hmu_now = b.hmu.counts
    pebs_now = b.pebs.sampled * b.pebs.period
    d_hmu = hmu_now - state.prev_hmu
    d_pebs = pebs_now - state.prev_pebs
    nb_faults = b.nb.faults
    d_true = (true_now - state.prev_true if state.prev_true is not None
              else d_hmu)

    # -- staleness: the policies read estimates from a delay ring this
    #    epoch's deltas are only written into — served values are
    #    stale_epochs old (zeros while the ring warms up).  Accounting
    #    (d_true) is never delayed: the workload really happened now.
    if state.stale is not None:
        depth = state.stale.shape[0]
        stale_new = state.stale.at[state.stale_ptr].set(
            jnp.stack([d_hmu, d_pebs, nb_faults]))
        serve_at = (state.stale_ptr + 1) % depth
        served = stale_new[serve_at]
        d_hmu, d_pebs, nb_faults = served[0], served[1], served[2]
        stale_ptr_new = serve_at
    else:
        stale_new = stale_ptr_new = None
    if faulty:
        # reset events shrink cumulative collector state, so a delta can go
        # negative — "no information this epoch", never negative hotness
        d_hmu = jnp.maximum(d_hmu, 0)
        d_pebs = jnp.maximum(d_pebs, 0)
    d_hmu_f = d_hmu.astype(jnp.float32)

    # -- per-collector quality: observed epoch mass vs expected (hardening).
    #    HMU and period-scaled PEBS should both report ~the epoch's access
    #    mass; NB's expectation is its own smoothed fault-mass history.
    #    Saturation, drops, resets and stalls all shrink observed mass, so
    #    one EWMA-smoothed scalar per collector covers every fault lane.
    if har is not None:
        exp_mass = jnp.maximum(epoch_accesses.astype(jnp.float32), 1.0)
        obs_hmu = jnp.sum(d_hmu).astype(jnp.float32)
        obs_pebs = jnp.sum(d_pebs).astype(jnp.float32)
        d_nb = jnp.maximum(nb_faults - state.prev_nb, 0)
        obs_nb = jnp.sum(d_nb).astype(jnp.float32)
        q_raw = jnp.stack([
            policy.quality_estimate(obs_hmu, exp_mass),
            policy.quality_estimate(obs_pebs, exp_mass),
            jnp.where(state.nb_ewma > 0.0,
                      policy.quality_estimate(obs_nb, state.nb_ewma), 1.0),
        ])
        quality_new = policy.quality_smooth(state.quality, q_raw,
                                            har.quality_beta)
        nb_ewma_new = policy.quality_smooth(state.nb_ewma, obs_nb,
                                            har.quality_beta)
        prev_nb_new = nb_faults
    else:
        quality_new = nb_ewma_new = prev_nb_new = None

    thr = (cfg.reactive_hot_threshold
           if cfg.reactive_hot_threshold is not None
           else jnp.maximum(2, epoch_accesses // (8 * max(k, 1))))

    # -- per-lane selection keys (int32; floats via order-isomorphic bitcast),
    #    eviction estimates, and selection gates: the lane-config arrays that
    #    replace the per-lane Python branching.  Lanes that rank the same
    #    signal (oracle + reactive + the epoch-hot set all rank d_hmu) share
    #    one selection row.
    rows: Dict[str, Tuple[jax.Array, jax.Array]] = {}

    def row(rkey: str, key: jax.Array, est: jax.Array) -> int:
        if rkey not in rows:
            rows[rkey] = (key, est)
        return list(rows).index(rkey)

    hmu_row = row("hmu", d_hmu, d_hmu_f)
    # -- collector fallback (hardening): when a lane's primary collector's
    #    smoothed quality is below the floor, the lane's selection key AND
    #    eviction estimate are swapped — branchlessly, one jnp.where on the
    #    quality scalar — to the named healthy collector's served delta.
    fb_map = dict(har.fallback) if har is not None else {}
    col_key = {"hmu": d_hmu, "pebs": d_pebs, "nb": nb_faults}

    def fall_back(name: str, key: jax.Array, est: jax.Array):
        alt = col_key[fb_map[name]]
        ok = quality_new[COLLECTORS.index(LANE_COLLECTOR[name])] \
            >= har.quality_floor
        return ok, jnp.where(ok, key, alt), \
            jnp.where(ok, est, alt.astype(jnp.float32))

    pred_new = state.pred
    lane_row, min_keys, caps, is_reactive, healthy = [], [], [], [], []
    for name in lanes:
        if name == "hmu_oracle":
            r, min_key, cap = hmu_row, 1, k
            key, est = d_hmu, d_hmu_f
        elif name == "nb_two_touch":
            cap = k if cfg.nb_rate_limit is None else min(k, cfg.nb_rate_limit)
            min_key = 2
            r = row("nb", nb_faults, nb_faults.astype(jnp.float32))
            key, est = nb_faults, nb_faults.astype(jnp.float32)
        elif name == "reactive_watermark":
            r, min_key, cap = hmu_row, 0, k      # 0 = thr placeholder (traced)
            key, est = d_hmu, d_hmu_f
        elif name == "proactive_ewma":
            pred_new = (cfg.ewma_alpha * d_hmu_f
                        + (1.0 - cfg.ewma_alpha) * state.pred)
            key, est = selectk.sortable_key(pred_new), pred_new
            r = row("pred", key, est)
            min_key, cap = 1, k
        elif name == "hinted":
            # exact argsort(argsort(d_pebs)): positives are bounded by this
            # epoch's PEBS samples, so rank the sparse support only
            t_rank = selectk.stable_rank_sparse(d_pebs, s_max)
            score = policy.hinted_score(d_pebs, t_rank, state.hint_rank,
                                        cfg.hint_weight)
            key, est = selectk.sortable_key(score), d_pebs.astype(jnp.float32)
            r = row("score", key, est)
            min_key, cap = 0, k
        elif name == "prefetch":
            # lookahead rank in [0,1]; min_key 1 gates rank > 0 (int32 bits of
            # any positive float are >= 1), matching policy.prefetch's gate
            r = row("la", selectk.sortable_key(state.prefetch_rank),
                    state.prefetch_rank)
            min_key, cap = 1, k
        else:  # pragma: no cover - guarded in __init__
            raise ValueError(name)
        if name in fb_map:
            ok, key, est = fall_back(name, key, est)
            r = row(f"fb:{name}", key, est)
            healthy.append(ok)
        else:
            healthy.append(None)
        lane_row.append(r)
        min_keys.append(min_key)
        caps.append(cap)
        is_reactive.append(name == "reactive_watermark")

    key_rows = jnp.stack([kv[0] for kv in rows.values()])   # (U, n) int32
    est_rows = jnp.stack([kv[1] for kv in rows.values()])   # (U, n) f32
    lane_row = np.asarray(lane_row)
    est_lanes = est_rows[lane_row]                          # (L, n) f32
    reactive_arr = jnp.asarray(is_reactive)
    min_key_arr = jnp.where(reactive_arr, thr,
                            jnp.asarray(min_keys, jnp.int32))
    if fb_map:
        # a fallen-back lane keys on a raw collector delta whatever its
        # normal key space was; gate at >= max(min_key, 1) so zero-signal
        # blocks are never promoted just to fill k
        healthy_arr = jnp.stack([jnp.asarray(True) if h is None else h
                                 for h in healthy])
        min_key_arr = jnp.where(healthy_arr, min_key_arr,
                                jnp.maximum(min_key_arr, 1))
    min_key_arr = min_key_arr[:, None]
    cap_arr = jnp.asarray(caps, jnp.int32)

    # -- multi-tenant quotas: a segment-capped select replaces the global
    #    one.  Every unique key row is masked to int32.min outside each
    #    tenant's own top-caps[t] (selectk.segment_top_k_mask over the
    #    static tenant bounds), so a lane's top-k candidate list always
    #    carries every tenant's best blocks BY THAT LANE'S KEY — a noisy
    #    neighbour can no longer crowd a quieter tenant out of selection.
    #    Masked entries fail every lane's value gate (all min_keys >= 0).
    #    The epoch's true hot set stays unmasked: it is workload truth,
    #    not policy.
    ten = cfg.tenancy
    quotas = ten is not None and ten.caps is not None
    if quotas:
        with named_scope("tenancy.select"):
            protected = selectk.segment_top_k_mask(
                key_rows, ten.offsets, ten.caps, backend=cfg.pallas)
            key_rows = jnp.where(protected, key_rows,
                                 jnp.iinfo(jnp.int32).min)

    # -- one O(n) selection per unique signal, fanned out to lanes
    vals_u, ids_u, sel_u = selectk.select_top_k(key_rows, k, return_mask=True,
                                                backend=cfg.pallas)
    vals, ids = vals_u[lane_row], ids_u[lane_row]           # (L, k)

    # -- account the epoch under the placement that served it
    #    (pre-migration).  The hot set is workload truth: with faults or
    #    staleness the hmu selection row no longer ranks the truth, so it
    #    gets its own exact top-K; otherwise the oracle row doubles as it.
    if quotas:
        with named_scope("tenancy.hot_set"):
            hot = selectk.top_k_mask(d_true, k, backend=cfg.pallas)
    elif faulty or state.stale is not None:
        hot = selectk.top_k_mask(d_true, k, backend=cfg.pallas)
    else:
        hot = sel_u[hmu_row]                       # epoch's true top-K set
    fast0 = state.placement.fast_mask              # (L, n)
    n_fast = jnp.sum(jnp.where(fast0, d_true, 0), axis=-1)
    n_slow = jnp.sum(d_true) - n_fast
    inter = jnp.sum((fast0 & hot).astype(jnp.int32), axis=-1)
    resident0 = state.placement.resident()

    # -- decide: ordered top-k ids per lane, gated per lane config.  With
    #    demote hysteresis a resident block must have looked cold for H
    #    consecutive epochs before the watermark lane frees its slot.
    demote_enable = reactive_arr[:, None]
    if state.cold_streak is not None:
        cold_streak_new = policy.cold_streak(state.cold_streak, est_lanes,
                                             fast0)
        demote_enable = demote_enable & (
            cold_streak_new >= har.demote_hysteresis)
    else:
        cold_streak_new = None
    pl, pre_demoted = demote_idle(state.placement, est_lanes, demote_enable)
    free_slots = jnp.sum((pl.slot_to_block < 0).astype(jnp.int32), axis=-1)
    cap_eff = jnp.where(reactive_arr, jnp.minimum(cap_arr, free_slots),
                        cap_arr)
    ok = (vals >= min_key_arr) & (jnp.arange(k, dtype=jnp.int32)[None, :]
                                  < cap_eff[:, None])
    want = jnp.where(ok, ids, -1)

    # -- migrate: bounded promotion with plan-guarded coldest-victim eviction
    pl, promoted, demoted = apply_plan(pl, want, est_lanes)

    out = {
        "drained_hi": drained.hi, "drained_lo": drained.lo,
        "pebs_host_hi": bundle.pebs.host_events.hi,
        "pebs_host_lo": bundle.pebs.host_events.lo,
        "nb_host_hi": bundle.nb.host_events.hi,
        "nb_host_lo": bundle.nb.host_events.lo,
        "n_fast": n_fast, "n_slow": n_slow,
        "inter": inter, "resident": resident0,
        "promoted": promoted, "demoted": demoted + pre_demoted,
    }
    if har is not None:
        out["quality"] = quality_new
    if ten is not None:
        # Per-tenant accounting: tenant-segment reductions of the same masks
        # the global record sums, plus each tenant's own true-hot set (top
        # hot_k[t] of its id range — the coverage target it would have solo).
        # All outputs are (L, T) scalars-per-tenant; nothing (n,)-sized
        # leaves the device.
        tsum = partial(_per_tenant_sum, offsets=ten.offsets)
        with named_scope("tenancy.hot_set"):
            t_hot = jnp.concatenate([
                selectk.top_k_mask(
                    jax.lax.slice_in_dim(d_true, ten.offsets[t],
                                         ten.offsets[t + 1]),
                    ten.hot_k[t], backend=cfg.pallas)
                for t in range(ten.n_tenants)
            ])
        fast1 = pl.fast_mask
        with named_scope("tenancy.account"):
            out["tenant"] = {
                "n_fast": tsum(jnp.where(fast0, d_true, 0)),
                "n_slow": tsum(jnp.where(fast0, 0, d_true)),
                "inter": tsum(fast0 & t_hot),
                "resident": tsum(fast0),
                "promoted": tsum(fast1 & ~fast0),
                "demoted": tsum(fast0 & ~fast1),
            }
    # -- append this epoch's record row to the device-side accumulator
    #    (same pytree structure as out; dtypes fixed by _out_buf_init)
    out_buf = jax.tree_util.tree_map(
        lambda buf, v: buf.at[out_row].set(v.astype(buf.dtype)),
        state.out_buf, out)
    updates = dict(
        bundle=bundle, placement=pl, pred=pred_new,
        prev_hmu=hmu_now, prev_pebs=pebs_now, out_buf=out_buf,
    )
    if state.prev_true is not None:
        updates["prev_true"] = true_now
    if state.stale is not None:
        updates.update(stale=stale_new, stale_ptr=stale_ptr_new)
    if har is not None:
        updates.update(quality=quality_new, nb_ewma=nb_ewma_new,
                       prev_nb=prev_nb_new)
    if state.cold_streak is not None:
        updates["cold_streak"] = cold_streak_new
    return dataclasses.replace(state, **updates)


def _per_tenant_sum(x: jax.Array, offsets: Tuple[int, ...]) -> jax.Array:
    """(..., n_blocks) -> (..., T) int32: one sum per tenant over its static
    contiguous id range ``offsets[t]:offsets[t+1]`` -- reductions, where a
    segment sum over a per-block tenant id would scatter every element onto
    T addresses."""
    x = x.astype(jnp.int32)
    return jnp.stack([jnp.sum(x[..., a:b], axis=-1)
                      for a, b in zip(offsets, offsets[1:])], axis=-1)


class EpochRuntime:
    """Runs all policy lanes over one shared telemetry stream, epoch by epoch.

    One collector set observes the stream once per epoch (fused); each lane
    owns only its placement.  ``step`` consumes one epoch of equal-size
    batches ``(n_batches, batch_size)`` and returns that epoch's records;
    ``run`` drives a whole workload and returns the :class:`Trajectory`.

    ``fused=True`` (default) keeps all lane state on device and executes
    decide+migrate+account as the single ``_epoch_step`` dispatch;
    ``fused=False`` is the pre-refactor per-lane host loop kept as the
    bit-identity reference and benchmark baseline.  ``mesh`` (with a
    ``NamedSharding`` axis named ``axis``) shards every (n_blocks,)-sized
    array — collector histograms and lane placements — across devices for
    paper-scale (5.24 M page) runs; see ``launch.mesh.make_telemetry_mesh``.

    ``hints`` (a :class:`repro.hints.HintPipeline`) refreshes the hinted
    lane's ``hint_rank`` and the prefetch lane's ``prefetch_rank`` every
    epoch from the pipeline's providers; ``run`` buffers the epoch stream by
    the pipeline's lookahead depth so ``step`` sees the queued next epochs.
    ``prefetch_overlap`` in [0,1] is how much of the prefetch lane's boundary
    migration streams concurrently with the epoch it serves (0 = the same
    stop-the-world charging every other lane pays).

    ``sync_every=K`` (fused only; default 1) batches the record sync: K
    epochs of record fields accumulate on device and cross the host
    boundary in one ``device_get`` — ``step`` then returns the epochs it
    flushed (a dict of record *lists*, empty until a buffer fills) instead
    of the K=1 per-epoch record dict, ``run``/``trajectory`` flush the
    partial tail automatically, and :meth:`flush` drains it on demand after
    manual stepping.  Trajectories are bit-identical for every K.
    """

    def __init__(
        self,
        n_blocks: int,
        k_hot: int,
        policies: Sequence[str] = ALL_POLICIES,
        system: MemSystem = CXL_SYSTEM,
        bytes_per_access: float = 256.0,
        block_bytes: float = 4096.0,
        pebs_period: int = 10007,
        nb_scan_rate: Optional[int] = None,
        hmu_log_capacity: int = 1 << 33,
        ewma_alpha: float = 0.5,
        hint_rank: Optional[np.ndarray] = None,
        hint_weight: float = 0.25,
        reactive_hot_threshold: Optional[int] = None,
        nb_rate_limit: Optional[int] = None,
        hints=None,
        prefetch_overlap: float = 1.0,
        fused: bool = True,
        mesh=None,
        mesh_axis: str = "blocks",
        tenancy: Optional[Tenancy] = None,
        sync_every: int = 1,
        faults: Optional[FaultModel] = None,
        hardening: Optional[Hardening] = None,
        export=None,
        use_pallas: Optional[bool] = None,
    ):
        unknown = set(policies) - set(ALL_POLICIES)
        if unknown:
            raise ValueError(f"unknown policies {sorted(unknown)}; "
                             f"choose from {ALL_POLICIES}")
        if mesh is not None and not fused:
            raise ValueError("mesh sharding requires the fused epoch step "
                             "(the reference path keeps lane state on the "
                             "host); pass fused=True or drop mesh")
        if (faults is not None or hardening is not None) and not fused:
            raise ValueError("fault injection / hardening run inside the "
                             "fused epoch step; the reference path stays "
                             "the fault-free bit-identity oracle — pass "
                             "fused=True or drop faults/hardening")
        if hardening is not None and not isinstance(hardening, Hardening):
            hardening = Hardening.make(**dict(hardening))
        if hardening is not None:
            hardening.validate()
        # The resolved backend records the implementation of each kernel
        # site (``kernels``); an explicit use_pallas=True it cannot honour —
        # past hist_select's size bound, or under a mesh, where the sharded
        # XLA path stays authoritative — raises.
        if use_pallas and not fused:
            raise ValueError("the Pallas kernels run inside the fused epoch "
                             "step; the reference path stays the pure-XLA "
                             "bit-identity oracle — pass fused=True or drop "
                             "use_pallas")
        self._pallas = resolve_backend(
            use_pallas if fused else False, n_blocks=int(n_blocks),
            sharded=mesh is not None)
        self.sync_every = int(sync_every)
        if self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every!r}")
        if self.sync_every > 1 and not fused:
            raise ValueError("sync_every > 1 batches record syncs in the "
                             "fused epoch loop; the reference path stays "
                             "synchronous (it is the bit-identity oracle) — "
                             "pass fused=True or sync_every=1")
        self.n_blocks = int(n_blocks)
        self.k_hot = min(int(k_hot), self.n_blocks)
        self.system = system
        self.bytes_per_access = float(bytes_per_access)
        self.block_bytes = float(block_bytes)
        self.ewma_alpha = float(ewma_alpha)
        self.hint_rank = (np.zeros((n_blocks,), np.float32)
                          if hint_rank is None
                          else np.asarray(hint_rank, np.float32))
        self.prefetch_rank = np.zeros((n_blocks,), np.float32)
        self.hint_weight = float(hint_weight)
        self.reactive_hot_threshold = reactive_hot_threshold
        self.nb_rate_limit = nb_rate_limit
        self.hints = hints                  # Optional[repro.hints.HintPipeline]
        self.prefetch_overlap = float(prefetch_overlap)
        if not 0.0 <= self.prefetch_overlap <= 1.0:
            raise ValueError(f"prefetch_overlap must be in [0, 1], "
                             f"got {prefetch_overlap!r}")
        self._prefetch_pending = 0          # blocks moved at the last boundary
        self._mesh, self._mesh_axis = mesh, mesh_axis
        self.fused = bool(fused)
        self.tenancy = tenancy
        # per-epoch per-tenant raw accounting ((L, T) int64 arrays, lane
        # order = policies); repro.fleet.accounting slices these into
        # TenantRecord rows with the tenants' own cost-model geometry
        self.tenant_records: List[Dict[str, np.ndarray]] = []
        if tenancy is not None:
            tenancy.validate(self.n_blocks, self.k_hot)
        self.faults = faults
        self.hardening = hardening
        # Optional repro.export client (duck-typed: export_epoch_record).
        # Records it sees are the ones _flush_records already assembled for
        # self.records, at the record-sync boundary where they are already
        # host-side — export adds no dispatch and must never raise or block
        # here (the client guarantees both).
        self.export = export
        scan = nb_scan_rate if nb_scan_rate is not None else max(n_blocks // 16, 1)
        bundle = tel.bundle_init(
            n_blocks, pebs_period=pebs_period, nb_scan_rate=scan,
            hmu_log_capacity=hmu_log_capacity, faults=faults,
        )
        self._lane_names = tuple(policies)
        self.epoch = 0
        self.records: Dict[str, List[EpochRecord]] = {n: [] for n in policies}
        self._prev_pebs_host = 0.0
        self._prev_nb_host = 0.0
        self._buffered = 0          # dispatched epochs not yet record-synced
        if self.fused:
            L = len(self._lane_names)
            self._cfg = _FusedCfg(
                lanes=self._lane_names, n_blocks=self.n_blocks,
                k_hot=self.k_hot, ewma_alpha=self.ewma_alpha,
                hint_weight=self.hint_weight,
                nb_rate_limit=self.nb_rate_limit,
                reactive_hot_threshold=self.reactive_hot_threshold,
                tenancy=self.tenancy,
                hardening=self.hardening,
                pallas=self._pallas,
            )
            def zeros_n():
                # distinct buffers (not one shared array) so donation works
                return jnp.zeros((self.n_blocks,), jnp.int32)

            # robustness leaves exist only when their subsystem is on, so a
            # fault-free runtime's state structure — and therefore its
            # compiled epoch program — is exactly the seed one
            extra = {}
            if faults is not None:
                extra["prev_true"] = zeros_n()
                if faults.stale_epochs > 0:
                    extra["stale"] = jnp.zeros(
                        (faults.stale_epochs + 1, 3, self.n_blocks),
                        jnp.int32)
                    extra["stale_ptr"] = jnp.zeros((), jnp.int32)
            if hardening is not None:
                extra["quality"] = jnp.ones((3,), jnp.float32)
                extra["nb_ewma"] = jnp.zeros((), jnp.float32)
                extra["prev_nb"] = zeros_n()
                if hardening.demote_hysteresis > 1:
                    extra["cold_streak"] = jnp.zeros(
                        (L, self.n_blocks), jnp.int32)
            self._state = _FusedState(
                bundle=bundle,
                placement=Placement.create(self.n_blocks, self.k_hot, lanes=L),
                pred=jnp.zeros((self.n_blocks,), jnp.float32),
                hint_rank=jnp.asarray(self.hint_rank),
                prefetch_rank=jnp.asarray(self.prefetch_rank),
                prev_hmu=zeros_n(), prev_pebs=zeros_n(),
                out_buf=_out_buf_init(self.sync_every, L, self.tenancy,
                                      self.hardening),
                **extra,
            )
            if mesh is not None:
                self._state = _shard_state(self._state, mesh, mesh_axis)
        else:
            self.bundle = bundle
            self._ref_lanes = {
                name: _Lane(
                    name=name,
                    slot_to_block=np.full((self.k_hot,), -1, np.int32),
                    block_to_slot=np.full((self.n_blocks,), -1, np.int32),
                    pred=(np.zeros((self.n_blocks,), np.float32)
                          if name == "proactive_ewma" else None),
                )
                for name in policies
            }
            # epoch-delta baselines (host copies, like the PR-1 loop)
            self._prev_true = np.zeros((n_blocks,), np.int64)
            self._prev_hmu = np.zeros((n_blocks,), np.int64)
            self._prev_pebs = np.zeros((n_blocks,), np.int64)

    # ---------------------------------------------------------- constructors
    @classmethod
    def for_scenario(cls, scenario, *, policies: Sequence[str] = ALL_POLICIES,
                     hints=None, prefetch_overlap: float = 1.0,
                     fused: bool = True, mesh=None, mesh_axis: str = "blocks",
                     **overrides) -> "EpochRuntime":
        """Build a runtime from an :class:`repro.scenarios.AccessScenario`'s
        geometry and cost-model parameters — the scenario supplies what the
        DLRM-shaped callers used to hand-wire (block count, hot-set size,
        per-access and per-block byte sizes, collector rates, memory system).
        A scenario that carries a ``tenancy`` attribute (a :class:`Tenancy` —
        ``repro.fleet.FleetScenario`` does) gets its multi-tenant layout and
        quotas installed too.  ``overrides`` replace any constructor kwarg
        (e.g. ``ewma_alpha=``)."""
        kw = dict(
            policies=policies,
            system=scenario.system,
            bytes_per_access=scenario.bytes_per_access,
            block_bytes=scenario.block_bytes,
            pebs_period=scenario.pebs_period,
            nb_scan_rate=scenario.nb_scan_rate,
            hints=hints, prefetch_overlap=prefetch_overlap,
            fused=fused, mesh=mesh, mesh_axis=mesh_axis,
            tenancy=getattr(scenario, "tenancy", None),
        )
        kw.update(overrides)
        return cls(scenario.n_blocks, scenario.k_hot, **kw)

    # ------------------------------------------------------- state accessors
    @property
    def kernels(self) -> Dict[str, str]:
        """Implementation of each kernel site (``select``, ``scatter``) this
        runtime resolved at its size, e.g. ``{"select": "hist_select
        (compiled)", "scatter": "xla"}``."""
        return self._pallas.describe()

    @property
    def lanes(self) -> Dict[str, _Lane]:
        """Per-lane placement view (host copies in fused mode)."""
        if not self.fused:
            return self._ref_lanes
        s2b = np.asarray(self._state.placement.slot_to_block)
        b2s = np.asarray(self._state.placement.block_to_slot)
        pred = np.asarray(self._state.pred)
        return {
            name: _Lane(
                name=name, slot_to_block=s2b[i], block_to_slot=b2s[i],
                pred=pred if name == "proactive_ewma" else None)
            for i, name in enumerate(self._lane_names)
        }

    @property
    def pending_migration_s(self) -> float:
        """Migration time of the prefetch lane's last boundary, not yet
        charged to any record: pending migration overlaps the NEXT epoch's
        stream, so at the end of a finite run the final boundary's cost has
        no epoch to land in.  Surfaced here (and in ``run_online``'s summary)
        so lane-total comparisons can account for it instead of it being
        silently dropped — every other lane charges its final boundary into
        its last record even though that migration serves no epoch either.
        Flushes the batched record sync first: ``_prefetch_pending`` is
        replayed during the flush, so a ``sync_every=K`` partial tail must
        be drained before the value is current."""
        if self.fused:
            self._flush_records()
        return self.system.migration_time_s(self._prefetch_pending,
                                            self.block_bytes)

    # ----------------------------------------------------------- hint refresh
    def set_hint_ranks(self, hint_rank: Optional[np.ndarray] = None,
                       prefetch_rank: Optional[np.ndarray] = None) -> None:
        """Replace the hint arrays the next epoch step reads.  On the fused
        path this swaps state leaves — a host-to-device transfer (sharded
        like the rest of the state under ``mesh``), not a dispatch, so the
        epoch stays at two; counted in ``DISPATCH_COUNTS['hint_refresh']``.
        An array that is the SAME object as the current one is skipped (the
        HintPipeline returns its cached static rank until the phase detector
        moves the scale), so an unchanged n-block hint_rank is not
        re-uploaded every epoch — the counter only ticks when something
        actually changed."""
        updates = {}
        if hint_rank is not None and hint_rank is not self.hint_rank:
            self.hint_rank = np.asarray(hint_rank, np.float32)
            updates["hint_rank"] = self.hint_rank
        if prefetch_rank is not None and prefetch_rank is not self.prefetch_rank:
            self.prefetch_rank = np.asarray(prefetch_rank, np.float32)
            updates["prefetch_rank"] = self.prefetch_rank
        if updates:
            DISPATCH_COUNTS["hint_refresh"] += 1
        if self.fused and updates:
            def put(x: np.ndarray) -> jax.Array:
                if self._mesh is None:
                    return jnp.asarray(x)
                from jax.sharding import NamedSharding, PartitionSpec as P
                return jax.device_put(
                    x, NamedSharding(self._mesh, P(self._mesh_axis)))

            _tr = obs_trace.get_tracer()
            cm = (_tr.span("hint_refresh", epoch=self.epoch,
                           arrays=",".join(sorted(updates)))
                  if _tr.enabled else obs_trace.NOOP_SPAN)
            with cm:
                self._state = dataclasses.replace(
                    self._state, **{k: put(v) for k, v in updates.items()})

    # ------------------------------------------------------------- migrate
    def _apply_plan(self, lane: _Lane, plan: policy.MigrationPlan,
                    est: np.ndarray) -> Tuple[int, int]:
        """Reference path: promote the plan into the lane's bounded fast
        tier; evict plan-guarded coldest victims when no slots are free.
        Returns (promoted, demoted) block counts — each is one block copy of
        migration traffic."""
        want = _unique_in_order(np.asarray(plan.promote), self.k_hot)
        if want.size == 0:
            return 0, 0
        new = want[lane.block_to_slot[want] < 0]
        if new.size == 0:
            return 0, 0
        free = np.nonzero(lane.slot_to_block < 0)[0]
        demoted = 0
        need = new.size - free.size
        if need > 0:
            DISPATCH_COUNTS["reference"] += 1
            vic = np.asarray(policy.plan_eviction(
                jnp.asarray(est, jnp.float32), jnp.asarray(want),
                jnp.asarray(lane.slot_to_block), int(need)))
            vic = vic[vic >= 0]
            if vic.size:
                slots = lane.block_to_slot[vic]
                lane.slot_to_block[slots] = -1
                lane.block_to_slot[vic] = -1
                demoted = int(vic.size)
            free = np.nonzero(lane.slot_to_block < 0)[0]
        take = int(min(new.size, free.size))
        if take:
            sel, slots = new[:take], free[:take]
            lane.slot_to_block[slots] = sel
            lane.block_to_slot[sel] = slots
        return take, demoted

    def _demote_untouched(self, lane: _Lane, est: np.ndarray) -> int:
        """Watermark demotion: free every resident block the epoch never
        touched (est == 0) so reactive promotion has slots."""
        resident = lane.resident_ids()
        idle = resident[est[resident] == 0]
        if idle.size:
            slots = lane.block_to_slot[idle]
            lane.slot_to_block[slots] = -1
            lane.block_to_slot[idle] = -1
        return int(idle.size)

    # -------------------------------------------------------------- decide
    def _plan_quota(self, lane: _Lane, d_hmu: np.ndarray, d_pebs: np.ndarray,
                    nb_faults: np.ndarray, epoch_accesses: int,
                    ) -> Tuple[policy.MigrationPlan, np.ndarray, int]:
        """Reference decide under per-tenant quotas: the lane's selection key
        is protected per tenant (each tenant's top ``caps[t]`` keys survive,
        ties lowest-index-first) and masked to ``int32.min`` elsewhere, then
        the lane's value/positional gates run on the globally-ordered masked
        selection — plain numpy sorts, mirroring the spec of the fused
        segment-capped select (``selectk.segment_top_k_mask``).  Float-keyed
        lanes go through the same float32 bit-pattern keys the device uses,
        computed by the same jnp policy helpers, so near-ties cannot split
        the two paths."""
        ten, k, n = self.tenancy, self.k_hot, self.n_blocks
        pre_demoted = 0
        DISPATCH_COUNTS["reference"] += 1

        def f32_key(x: np.ndarray) -> np.ndarray:
            return np.ascontiguousarray(
                np.asarray(x, np.float32)).view(np.int32)

        min_key: int
        cap = k
        if lane.name == "hmu_oracle":
            est, key, min_key = d_hmu, d_hmu, 1
        elif lane.name == "nb_two_touch":
            est, key, min_key = nb_faults, nb_faults, 2
            if self.nb_rate_limit is not None:
                cap = min(k, self.nb_rate_limit)
        elif lane.name == "reactive_watermark":
            est, key = d_hmu, d_hmu
            pre_demoted = self._demote_untouched(lane, est)
            cap = min(k, int(np.sum(lane.slot_to_block < 0)))
            min_key = (self.reactive_hot_threshold
                       if self.reactive_hot_threshold is not None
                       else max(2, epoch_accesses // (8 * max(k, 1))))
        elif lane.name == "proactive_ewma":
            pred, _ = policy.proactive_ewma(
                jnp.asarray(lane.pred), jnp.asarray(d_hmu, jnp.float32), k,
                alpha=self.ewma_alpha)
            lane.pred = np.asarray(pred)
            est, key, min_key = lane.pred, f32_key(lane.pred), 1
        elif lane.name == "hinted":
            est = d_pebs
            t_rank = jnp.argsort(jnp.argsort(jnp.asarray(est, jnp.int32)))
            score = policy.hinted_score(
                jnp.asarray(est, jnp.int32), t_rank,
                jnp.asarray(self.hint_rank), self.hint_weight)
            key, min_key = f32_key(np.asarray(score)), 0
        elif lane.name == "prefetch":
            est = self.prefetch_rank
            key, min_key = f32_key(est), 1
        else:  # pragma: no cover - guarded in __init__
            raise ValueError(lane.name)

        key = np.asarray(key, np.int64)
        protected = np.zeros((n,), bool)
        for t, tcap in enumerate(ten.caps):
            off, end = ten.offsets[t], ten.offsets[t + 1]
            order = np.argsort(-key[off:end], kind="stable")
            protected[off + order[:tcap]] = True
        masked = np.where(protected, key, np.iinfo(np.int32).min)
        ids = np.argsort(-masked, kind="stable")[:k]
        ok = (masked[ids] >= min_key) & (np.arange(ids.size) < cap)
        return (policy.MigrationPlan(promote=np.where(ok, ids, -1)),
                np.asarray(est), pre_demoted)

    def _plan(self, lane: _Lane, d_hmu: np.ndarray, d_pebs: np.ndarray,
              nb_faults: np.ndarray, epoch_accesses: int,
              ) -> Tuple[policy.MigrationPlan, np.ndarray, int]:
        """Reference path: one lane's decide step -> (plan, estimate,
        pre-demotions)."""
        if self.tenancy is not None and self.tenancy.caps is not None:
            return self._plan_quota(lane, d_hmu, d_pebs, nb_faults,
                                    epoch_accesses)
        k = self.k_hot
        pre_demoted = 0
        DISPATCH_COUNTS["reference"] += 1
        if lane.name == "hmu_oracle":
            est = d_hmu
            plan = policy.oracle_top_k(jnp.asarray(est, jnp.int32), k)
        elif lane.name == "nb_two_touch":
            est = nb_faults
            plan = policy.nb_two_touch(jnp.asarray(est, jnp.int32), k,
                                       self.nb_rate_limit)
        elif lane.name == "reactive_watermark":
            est = d_hmu
            pre_demoted = self._demote_untouched(lane, est)
            free = int(np.sum(lane.slot_to_block < 0))
            thr = (self.reactive_hot_threshold
                   if self.reactive_hot_threshold is not None
                   else max(2, epoch_accesses // (8 * max(k, 1))))
            plan = policy.reactive_watermark(
                jnp.asarray(est, jnp.int32), int(thr),
                jnp.asarray(free), max_moves=k)
        elif lane.name == "proactive_ewma":
            pred, plan = policy.proactive_ewma(
                jnp.asarray(lane.pred), jnp.asarray(d_hmu, jnp.float32), k,
                alpha=self.ewma_alpha)
            lane.pred = np.asarray(pred)
            est = lane.pred
        elif lane.name == "hinted":
            est = d_pebs
            plan = policy.hinted(jnp.asarray(est, jnp.int32),
                                 jnp.asarray(self.hint_rank), k,
                                 hint_weight=self.hint_weight)
        elif lane.name == "prefetch":
            est = self.prefetch_rank
            plan = policy.prefetch(jnp.asarray(est), k)
        else:  # pragma: no cover - guarded in __init__
            raise ValueError(lane.name)
        return plan, np.asarray(est), pre_demoted

    # ---------------------------------------------------------------- step
    def step(self, batches, lookahead: Sequence = ()) -> Dict[str, EpochRecord]:
        """Consume one epoch ``(n_batches, batch_size)``: fused observe, then
        decide/migrate/account every lane.  ``lookahead`` is the queued
        upcoming epochs (the dataloader's prefetch queue) handed to the hint
        pipeline, if any.  Returns this epoch's records."""
        batches = np.ascontiguousarray(np.asarray(batches, np.int32))
        if batches.ndim != 2:
            raise ValueError(f"epoch batches must be 2-D, got {batches.shape}")
        if self.hints is not None:
            _tr = obs_trace.get_tracer()
            cm = (_tr.span("hints", epoch=self.epoch)
                  if _tr.enabled else obs_trace.NOOP_SPAN)
            with cm:
                ranks = self.hints.epoch_ranks(batches, lookahead)
            self.set_hint_ranks(*ranks)
        if self.fused:
            return self._step_fused(batches)
        return self._step_reference(batches)

    def _record(self, name: str, epoch: int, n_fast: float, n_slow: float,
                host_events: float, promoted: int, demoted: int,
                resident: int, inter: int,
                quality: float = 1.0) -> EpochRecord:
        """Shared epoch accounting (host float64 scalar math, both paths).
        ``epoch`` is explicit because the batched sync assembles records
        for epochs that were dispatched several steps ago."""
        access_s = self.system.access_time_s(
            n_fast, n_slow, self.bytes_per_access)
        per_event = (NB_FAULT_COST_S if name == "nb_two_touch" else
                     PEBS_SAMPLE_COST_S if name == "hinted" else
                     0.0 if name == "prefetch" else
                     HMU_DRAIN_COST_S)
        host_tax_s = host_events * per_event
        hidden_s = 0.0
        if name == "prefetch":
            # Lookahead lets the prefetch lane issue its boundary migration
            # ahead of the epoch it serves, so the migration charged here is
            # the one issued at the PREVIOUS boundary — it streamed under
            # THIS epoch's accesses, and the overlapped share is hidden
            # (MemSystem.overlapped_epoch_time_s).  Every other lane pays its
            # boundary migration stop-the-world, same as before.
            moved = self._prefetch_pending
            self._prefetch_pending = promoted + demoted
            migration_s = self.system.migration_time_s(moved, self.block_bytes)
            hidden_s = self.system.migration_overlap_s(
                n_slow, self.bytes_per_access, moved, self.block_bytes,
                self.prefetch_overlap)
        else:
            migration_s = self.system.migration_time_s(
                promoted + demoted, self.block_bytes)
        return EpochRecord(
            epoch=epoch, lane=name,
            time_s=access_s + host_tax_s + migration_s - hidden_s,
            access_s=access_s, host_tax_s=host_tax_s, migration_s=migration_s,
            accuracy=(inter / resident) if resident else 0.0,
            coverage=(inter / self.k_hot) if self.k_hot else 0.0,
            resident=resident, promoted=promoted, demoted=demoted,
            host_events=host_events, hidden_s=hidden_s, quality=quality,
        )

    def _step_fused(self, batches: np.ndarray):
        state = self._state
        # obs spans are attribution only: tracing-off uses the shared no-op
        # context manager (zero allocations), tracing-on wraps the very same
        # dispatch calls — the --obs bench gates bit-identical records and
        # equal DISPATCH_COUNTS either way.
        _tr = obs_trace.get_tracer()
        cm = (_tr.span("id_upload", epoch=self.epoch, bytes=batches.nbytes)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            ids = jax.device_put(batches)
        DISPATCH_COUNTS["observe_all"] += 1
        cm = (_tr.span("observe_all", epoch=self.epoch)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            bundle = tel.observe_all(state.bundle, ids, pallas=self._pallas)
        state = dataclasses.replace(state, bundle=bundle)
        # Pipelining: this epoch's observe_all is already dispatched when a
        # full record buffer forces the previous K epochs' batched sync, so
        # the device never idles against the pull.  (The flush reads
        # self._state.out_buf — untouched by observe_all, not yet donated
        # to this epoch's _epoch_step.)
        flushed: Dict[str, List[EpochRecord]] = {}
        if self._buffered >= self.sync_every:
            flushed = self._flush_records()
        # static PEBS-positives bound, quantized to the next power of two so
        # ragged epoch sizes don't retrace the epoch program
        bound = int(batches.size) // state.bundle.pebs.period + 2
        s_max = min(self.n_blocks, 1 << (bound - 1).bit_length())
        DISPATCH_COUNTS["epoch_step"] += 1
        cm = (_tr.span("epoch_step", epoch=self.epoch)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            self._state = _epoch_step(
                state, jnp.asarray(batches.size, jnp.int32),
                jnp.asarray(self._buffered, jnp.int32),
                cfg=self._cfg, s_max=s_max)
        self.epoch += 1
        self._buffered += 1
        if self.sync_every == 1:
            flushed = self._flush_records()   # synchronous loop: pull now
            return {name: recs[0] for name, recs in flushed.items()}
        return flushed

    def _flush_records(self) -> Dict[str, List[EpochRecord]]:
        """Pull the buffered epochs' record fields in ONE device->host sync
        (``jax.device_get`` of the stacked ``(sync_every,)`` accumulator)
        and assemble their :class:`EpochRecord`s / per-tenant rows in
        dispatch order — bit-identical to the per-epoch sync it batches."""
        n_buf = self._buffered
        if not self.fused or n_buf == 0:
            return {}
        base = self.epoch - n_buf
        DISPATCH_COUNTS["record_sync"] += 1
        _tr = obs_trace.get_tracer()
        cm = (_tr.span("record_sync", epoch_base=base, n_epochs=n_buf)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            # waiting for the device, then the transfer that follows
            out_buf = self._state.out_buf
            cm = (_tr.span("record_wait", epoch_base=base)
                  if _tr.enabled else obs_trace.NOOP_SPAN)
            with cm:
                jax.block_until_ready(out_buf)
            cm = (_tr.span("record_pull", epoch_base=base)
                  if _tr.enabled else obs_trace.NOOP_SPAN)
            with cm:
                host = jax.device_get(out_buf)
        cm = (_tr.span("record_assembly", epoch_base=base, n_epochs=n_buf)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            return self._assemble_records(host, base, n_buf)

    def _assemble_records(self, host: dict, base: int, n_buf: int
                          ) -> Dict[str, List[EpochRecord]]:
        tenant = host.get("tenant")
        qual = host.get("quality")
        flushed: Dict[str, List[EpochRecord]] = {
            name: [] for name in self._lane_names}

        def c64(field: str, j: int) -> float:
            # recombine the exact hi/lo int32 pair in float64 (exact < 2**53)
            return (float(host[field + "_hi"][j]) * CARRY_BASE
                    + float(host[field + "_lo"][j]))

        for j in range(n_buf):                 # rows beyond n_buf are stale
            pebs_host = c64("pebs_host", j)
            nb_host = c64("nb_host", j)
            d_pebs_host = pebs_host - self._prev_pebs_host
            d_nb_host = nb_host - self._prev_nb_host
            self._prev_pebs_host, self._prev_nb_host = pebs_host, nb_host
            drained = c64("drained", j)
            if tenant is not None:
                self.tenant_records.append({
                    key: np.asarray(val[j], np.int64)
                    for key, val in tenant.items()})
            for i, name in enumerate(self._lane_names):
                host_events = (d_nb_host if name == "nb_two_touch" else
                               d_pebs_host if name == "hinted" else
                               0.0 if name == "prefetch" else drained)
                col = LANE_COLLECTOR[name]
                quality = (float(qual[j, COLLECTORS.index(col)])
                           if qual is not None and col is not None else 1.0)
                rec = self._record(
                    name, epoch=base + j, quality=quality,
                    n_fast=float(host["n_fast"][j, i]),
                    n_slow=float(host["n_slow"][j, i]),
                    host_events=host_events,
                    promoted=int(host["promoted"][j, i]),
                    demoted=int(host["demoted"][j, i]),
                    resident=int(host["resident"][j, i]),
                    inter=int(host["inter"][j, i]),
                )
                self.records[name].append(rec)
                flushed[name].append(rec)
                if self.export is not None:
                    self.export.export_epoch_record(rec)
        self._buffered = 0
        return flushed

    def flush(self) -> Dict[str, List[EpochRecord]]:
        """Force the batched record sync for any still-buffered epochs (the
        ``sync_every=K`` partial tail).  ``run`` calls this on loop exit;
        call it after manual ``step``-ing with ``sync_every > 1`` before
        reading ``records``/``tenant_records``.  No-op on the reference
        path and on an empty buffer."""
        return self._flush_records()

    def block_until_ready(self) -> "EpochRuntime":
        """Block until all dispatched device work has finished — the honest
        stopping point for wall-clock timers under async dispatch (records
        may already be flushed while the final epoch's state updates are
        still in flight)."""
        jax.block_until_ready(self._state if self.fused else self.bundle)
        return self

    def _step_reference(self, batches: np.ndarray) -> Dict[str, EpochRecord]:
        _tr = obs_trace.get_tracer()
        cm = (_tr.span("reference_step", epoch=self.epoch)
              if _tr.enabled else obs_trace.NOOP_SPAN)
        with cm:
            return self._step_reference_impl(batches)

    def _step_reference_impl(self, batches: np.ndarray) -> Dict[str, EpochRecord]:
        epoch_accesses = int(batches.size)

        # -- observe (one dispatch) + drain the HMU log
        DISPATCH_COUNTS["observe_all"] += 1
        self.bundle = tel.observe_all(self.bundle, jnp.asarray(batches))
        drained = float(self.bundle.hmu.log_used)
        self.bundle = dataclasses.replace(
            self.bundle, hmu=tel.hmu_drain_cost(self.bundle.hmu))

        # -- epoch-local estimates (four full-array pulls per epoch)
        DISPATCH_COUNTS["reference"] += 4
        true_now = np.asarray(self.bundle.true_counts, np.int64)
        hmu_now = np.asarray(tel.hmu_estimate(self.bundle.hmu), np.int64)
        pebs_now = np.asarray(tel.pebs_estimate(self.bundle.pebs), np.int64)
        d_true = true_now - self._prev_true
        d_hmu = hmu_now - self._prev_hmu
        d_pebs = pebs_now - self._prev_pebs
        nb_faults = np.asarray(tel.nb_estimate(self.bundle.nb), np.int64)
        pebs_host = float(self.bundle.pebs.host_events)
        nb_host = float(self.bundle.nb.host_events)
        d_pebs_host = pebs_host - self._prev_pebs_host
        d_nb_host = nb_host - self._prev_nb_host
        self._prev_true, self._prev_hmu, self._prev_pebs = true_now, hmu_now, pebs_now
        self._prev_pebs_host, self._prev_nb_host = pebs_host, nb_host

        epoch_hot = metrics.true_top_k(d_true, self.k_hot)
        ten = self.tenancy
        if ten is not None:
            # per-tenant true-hot mask: top hot_k[t] of each tenant's range
            # (same stable tie-break as the fused selectk.top_k_mask)
            t_hot_mask = np.zeros((self.n_blocks,), bool)
            for t in range(ten.n_tenants):
                off, end = ten.offsets[t], ten.offsets[t + 1]
                t_hot_mask[off + metrics.true_top_k(d_true[off:end],
                                                    ten.hot_k[t])] = True
            t_rows = {key: [] for key in ("n_fast", "n_slow", "inter",
                                          "resident", "promoted", "demoted")}
        out: Dict[str, EpochRecord] = {}
        for lane in self._ref_lanes.values():
            # -- account the epoch under the placement that served it
            served = lane.resident_ids().copy()
            fast_before = lane.fast_mask.copy()
            n_fast, n_slow = split_accesses_by_tier(d_true, fast_before)
            host_events = (d_nb_host if lane.name == "nb_two_touch" else
                           d_pebs_host if lane.name == "hinted" else
                           0.0 if lane.name == "prefetch" else drained)

            # -- decide + migrate for the NEXT epoch
            plan, est, pre_demoted = self._plan(
                lane, d_hmu, d_pebs, nb_faults, epoch_accesses)
            promoted, demoted = self._apply_plan(lane, plan, est)
            inter = int(np.intersect1d(served, epoch_hot).size)
            if ten is not None:
                fast_after = lane.fast_mask
                lane_masks = {
                    "n_fast": np.where(fast_before, d_true, 0),
                    "n_slow": np.where(fast_before, 0, d_true),
                    "inter": fast_before & t_hot_mask,
                    "resident": fast_before,
                    "promoted": fast_after & ~fast_before,
                    "demoted": fast_before & ~fast_after,
                }
                for key, arr in lane_masks.items():
                    t_rows[key].append(np.array([
                        int(arr[ten.offsets[t]:ten.offsets[t + 1]].sum())
                        for t in range(ten.n_tenants)], np.int64))
            rec = self._record(
                lane.name, epoch=self.epoch, n_fast=n_fast, n_slow=n_slow,
                host_events=host_events, promoted=promoted,
                demoted=demoted + pre_demoted,
                resident=int(served.size), inter=inter,
            )
            self.records[lane.name].append(rec)
            out[lane.name] = rec
            if self.export is not None:
                self.export.export_epoch_record(rec)
        if ten is not None:
            self.tenant_records.append(
                {key: np.stack(rows) for key, rows in t_rows.items()})
        self.epoch += 1
        return out

    # ----------------------------------------------------------------- run
    def run(self, epochs: Iterable) -> Trajectory:
        """Drive a whole epoch stream.  With a hint pipeline attached, the
        stream is buffered by the pipeline's lookahead depth so each ``step``
        sees the queued next epochs — the dataloader's prefetch queue, which
        is what the lookahead provider models.

        Each ``run`` is one workload: the prefetch lane's pending boundary
        migration is cleared on entry, so a runtime reused for a second
        ``run`` does not charge the previous stream's final boundary (already
        surfaced via :attr:`pending_migration_s`) against the new stream's
        first epoch — and the returned :class:`Trajectory` holds only THIS
        stream's records (earlier manual ``step``/``run`` history stays in
        :attr:`records` / :meth:`trajectory`)."""
        self._flush_records()     # manual-step leftovers belong to their own
        self._prefetch_pending = 0                              # stream
        starts = {name: len(recs) for name, recs in self.records.items()}
        depth = self.hints.lookahead_depth if self.hints is not None else 0
        it = iter(epochs)
        buf: deque = deque()                # current epoch + queued lookahead
        try:
            while True:
                if not buf:
                    buf.extend(itertools.islice(it, 1))
                    if not buf:
                        break
                batches = buf.popleft()
                buf.extend(itertools.islice(it, depth - len(buf)))
                self.step(batches, lookahead=tuple(buf))
        finally:
            # sync_every=K partial tail — also on exception, so a run killed
            # mid-stream still lands (and exports) every dispatched epoch
            self._flush_records()
        return Trajectory(n_blocks=self.n_blocks, k_hot=self.k_hot,
                          records={name: recs[starts[name]:]
                                   for name, recs in self.records.items()})

    def trajectory(self) -> Trajectory:
        """Full record history across every ``step``/``run`` on this runtime
        (each ``run`` additionally returns its own stream's slice)."""
        self._flush_records()
        return Trajectory(n_blocks=self.n_blocks, k_hot=self.k_hot,
                          records=self.records)


def _shard_state(state: _FusedState, mesh, axis: str) -> _FusedState:
    """Distribute every (n_blocks,)-sized leaf (collector histograms, lane
    placements, EWMA state) over ``mesh``'s ``axis``; scalars and slot maps
    are replicated.  jit then partitions observe_all and epoch_step via
    GSPMD — the decision loop runs where the counters live."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_blocks = state.bundle.true_counts.shape[0]

    def put(x):
        if getattr(x, "ndim", 0) >= 1 and x.shape[-1] == n_blocks:
            spec = P(*([None] * (x.ndim - 1) + [axis]))
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, state)
