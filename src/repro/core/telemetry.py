"""Telemetry collectors: three observers of one ground-truth access stream.

The paper's central experiment is to feed the *same* workload to three hotness
trackers and compare what each believes the hot set is:

* ``HMU``  — memory-side Hotness Monitoring Unit: sees **every** request the
  memory device services (the CXL Data Logger snoops all CXL.mem packets).
  Exact per-block counters, zero host cost for collection; host cost only to
  drain/process the log.
* ``PEBS`` — CPU-assisted sampling: sees every ``period``-th memory access
  (Intel PEBS semantics).  Full-address precision on sampled events but
  **coverage** is bounded by the sampling period; each sample costs host work.
* ``NB``   — OS-level NUMA-balancing hints: the kernel *unmaps* pages in a
  cyclic scan; the next touch of an unmapped page raises a hint fault.  The OS
  therefore observes **recency, not frequency**: one touch after a scan looks
  identical to ten thousand touches.  Each fault costs host work.

All collectors are functional pytrees; ``observe`` is jit-able and is driven
with batches of row/page indices (the "physical addresses" in the log).  The
access stream itself is produced by the workloads (mmap-bench, DLRM, the LM
embedding / expert / KV layers).

**Fault lanes.**  Real collectors are not perfectly reliable, and the limits
study only holds if the degraded regimes are modeled too.  When the
:class:`TelemetryBundle` carries a :class:`repro.faults.FaultModel`
(``bundle_init(faults=...)``), the fused observe path injects — on device,
inside the same ``lax.scan``, so the epoch stays one dispatch:

* **HMU counter saturation** — per-block counters clamp at the model's
  ``hmu_counter_max`` (``2**w - 1`` for a ``w``-bit hardware counter)
  instead of silently wrapping int32; a saturated block's epoch delta reads
  0, so a narrow counter makes the *hottest* blocks invisible.  With no
  model the clamp still applies at int32 max (wrapping is never correct).
* **PEBS sample drops** — each would-be sample is lost with probability
  ``pebs_drop_p`` (scalar, or per-block for per-tenant profiles) before the
  host sees it; the drop count accrues to ``faults.pebs_dropped``.
* **collector resets** — once per epoch, with per-collector probability
  ``reset_p``, a collector's cumulative signal state (HMU counts / PEBS
  sampled histogram / NB fault counts + PTE state) resets to empty.  This
  models drain races: the epoch deltas the runtime computes against its
  pre-reset baselines are garbage for one epoch — exactly the signal the
  degradation machinery in ``core.runtime`` has to survive.
* **NB scan stalls** — with probability ``nb_stall_p`` per observe call the
  scanner makes no progress (no unmapping, no cursor advance), so hint
  faults stop arriving — ``task_numa_work`` skipping its slice under load.
* **staleness** — ``stale_epochs`` delays the estimates the *policies* see
  through a ring buffer (a runtime state leaf, not a collector change).

All event scalars (``log_used``/``log_dropped``/``host_events``) are exact
:class:`repro.faults.Counter64` hi/lo int32 pairs: the float32 scalars they
replace silently stopped incrementing past 2**24 events, which paper-scale
runs exceed within one run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..faults.model import (
    CARRY_BASE, CARRY_BITS, INT32_MAX, Counter64, FaultModel,
    counter_add, counter_init, counter_scaled_add, counter_zero_like,
)
from ..kernels.observe_scatter import observe_scatter
from ..obs import metrics as obs_metrics
from ..obs.trace import named_scope

__all__ = [
    "HMUState", "PEBSState", "NBState", "TelemetryBundle",
    "hmu_init", "hmu_observe", "hmu_estimate", "hmu_drain_cost",
    "hmu_saturated",
    "pebs_init", "pebs_observe", "pebs_estimate",
    "nb_init", "nb_observe", "nb_estimate",
    "bundle_init", "observe_all", "count_observe",
]


# =====================================================================  HMU
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HMUState:
    """Memory-side exact counters + bounded request-log emulation.

    ``counts`` is what a counter-mode HMU exposes; updates **saturate** at
    the configured counter width (int32 max by default — a real counter
    clamps, it never wraps to negative).  ``log_used``/``log_dropped`` model
    the paper's log-DRAM capacity (256 GB on the FPGA card): in log mode
    every request consumes one record until the log fills; software must
    drain it (``hmu_drain_cost``) or subsequent records are dropped.  Drops
    only affect log mode — counter mode loses events only to saturation.
    """
    counts: jax.Array          # (n_blocks,) int32 saturating access counts
    log_used: Counter64        # records currently in the log (exact)
    log_dropped: Counter64     # records lost to log overflow (exact)
    log_capacity: int = dataclasses.field(metadata=dict(static=True))
    host_events: Counter64     # host work units spent (drain only; exact)


def hmu_init(n_blocks: int, log_capacity: int = 1 << 33) -> HMUState:
    # Scalar accounting uses exact hi/lo int32 pairs (x64 is disabled; these
    # model counters exceed both int32 range AND float32 exactness — a
    # 256 GB log is billions of records, and float32 stops incrementing at
    # 2**24).  Distinct arrays per counter so donation works.
    return HMUState(
        counts=jnp.zeros((n_blocks,), jnp.int32),
        log_used=counter_init(),
        log_dropped=counter_init(),
        log_capacity=int(log_capacity),
        host_events=counter_init(),
    )


def _hmu_observe(state: HMUState, block_ids: jax.Array, weight: int = 1,
                 counter_max: Optional[jax.Array] = None,
                 hist: Optional[jax.Array] = None) -> HMUState:
    """Pure (un-jitted) HMU update — shared by the per-batch jit and the
    fused epoch scan so both paths are the *same traced computation* and
    therefore bit-identical.  ``counter_max`` is the saturation cap from a
    :class:`~repro.faults.FaultModel` (scalar or per-block); without one the
    counters still clamp at int32 max instead of wrapping.  ``hist`` (the
    batch's precomputed (n_blocks,) access histogram, from the fused
    ``observe_scatter`` kernel) replaces the scatter-add with the
    elementwise-identical ``counts + hist * weight``."""
    flat = block_ids.reshape(-1)
    n = flat.shape[0] * weight
    if n >= CARRY_BASE:                      # static shape check
        raise ValueError(
            f"one observe call adds {n} events; split calls below "
            f"{CARRY_BASE} so the hi/lo log counters carry exactly")
    cap = jnp.int32(INT32_MAX) if counter_max is None else counter_max
    summed = (state.counts.at[flat].add(weight, mode="drop")
              if hist is None else state.counts + hist * weight)
    # Saturate instead of wrapping: a wrapped sum reads *less* than the old
    # count (two's complement), so `summed < counts` flags exactly the
    # blocks that crossed int32 max this call (per-call mass << 2**31).
    counts = jnp.where(summed < state.counts, cap, jnp.minimum(summed, cap))
    # Log free space in exact hi/lo arithmetic: when at least 2 hi-words
    # (2**24 records) are free, the whole batch fits; otherwise the exact
    # small remainder decides.  (The unused free_small product may wrap
    # int32 for huge free space — it is masked out in exactly that case.)
    cap_hi = jnp.int32(state.log_capacity >> CARRY_BITS)
    cap_lo = jnp.int32(state.log_capacity & (CARRY_BASE - 1))
    diff_hi = cap_hi - state.log_used.hi
    free_small = diff_hi * CARRY_BASE + (cap_lo - state.log_used.lo)
    n_arr = jnp.int32(n)
    appended = jnp.where(diff_hi >= 2, n_arr, jnp.clip(free_small, 0, n_arr))
    return dataclasses.replace(
        state,
        counts=counts,
        log_used=counter_add(state.log_used, appended),
        log_dropped=counter_add(state.log_dropped, n_arr - appended),
    )


@partial(jax.jit, donate_argnums=0, static_argnums=2)
def hmu_observe(state: HMUState, block_ids: jax.Array, weight: int = 1) -> HMUState:
    """Device-side: every access counted. No host involvement."""
    return _hmu_observe(state, block_ids, weight)


def hmu_estimate(state: HMUState) -> jax.Array:
    return state.counts


def hmu_saturated(state: HMUState,
                  counter_max: Optional[jax.Array] = None) -> jax.Array:
    """Number of blocks pinned at the saturation cap — the blocks whose
    epoch deltas now read 0 even while they are the hottest in the system.
    Pass the :class:`~repro.faults.FaultModel`'s ``hmu_counter_max`` for a
    width-limited counter; the default audits the int32 clamp."""
    cap = jnp.int32(INT32_MAX) if counter_max is None else counter_max
    return jnp.sum((state.counts >= cap).astype(jnp.int32))


def hmu_drain_cost(state: HMUState, per_record_cost: float = 1.0) -> HMUState:
    """Host drains/processes the log (paper: 'process the trace immediately').
    This is the only host cost HMU incurs; NMC (paper §VI) would shrink it.
    ``per_record_cost`` must be a small non-negative integer so the exact
    hi/lo counter math stays exact (scale per-record costs into the
    time-per-event constants instead)."""
    cost = float(per_record_cost)
    if not cost.is_integer() or not 0 <= cost < 64:
        raise ValueError(f"per_record_cost must be a small non-negative "
                         f"integer (exact hi/lo counter math), got "
                         f"{per_record_cost!r}")
    return dataclasses.replace(
        state,
        host_events=counter_scaled_add(state.host_events, state.log_used,
                                       int(cost)),
        log_used=counter_zero_like(state.log_used),
    )


# =====================================================================  PEBS
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PEBSState:
    sampled: jax.Array        # (n_blocks,) number of *sampled* hits per block
    cursor: jax.Array         # scalar int32: global access index mod period
    period: int = dataclasses.field(metadata=dict(static=True))
    host_events: Counter64    # one per PEBS record (interrupt+parse; exact)


def pebs_init(n_blocks: int, period: int = 10007) -> PEBSState:
    return PEBSState(
        sampled=jnp.zeros((n_blocks,), jnp.int32),
        cursor=jnp.zeros((), jnp.int32),
        period=int(period),
        host_events=counter_init(),
    )


def _pebs_sample_mask(state: PEBSState, n: int) -> jax.Array:
    # cursor is an exact int32 carried modulo period: a float32 cursor is only
    # exact for streams < 2^24 accesses, so paper-scale epoch streams would
    # drift the sampling phase.  The modulo keeps it exact forever.
    idx = state.cursor + jnp.arange(n, dtype=jnp.int32)
    return (idx % state.period) == 0


def _pebs_apply(state: PEBSState, flat: jax.Array,
                kept: Optional[jax.Array],
                pebs_hist: Optional[jax.Array] = None,
                n_kept: Optional[jax.Array] = None) -> PEBSState:
    # scatter-add only surviving sampled positions (weight 0/1); the fused
    # kernel path hands the already-scattered histogram and the kept count
    # instead of the per-position mask
    sampled = (state.sampled.at[flat].add(kept.astype(jnp.int32),
                                          mode="drop")
               if pebs_hist is None else state.sampled + pebs_hist)
    if n_kept is None:
        n_kept = jnp.sum(kept).astype(jnp.int32)
    return dataclasses.replace(
        state,
        sampled=sampled,
        cursor=(state.cursor + flat.shape[0]) % state.period,
        host_events=counter_add(state.host_events, n_kept),
    )


def _pebs_observe(state: PEBSState, block_ids: jax.Array) -> PEBSState:
    flat = block_ids.reshape(-1)
    return _pebs_apply(state, flat, _pebs_sample_mask(state, flat.shape[0]))


def _pebs_observe_faulty(state: PEBSState, block_ids: jax.Array,
                         keep: jax.Array) -> Tuple[PEBSState, jax.Array]:
    """Sampling with Bernoulli event loss: ``keep`` is a per-event survival
    mask (drawn by the caller from the fault model's ``pebs_drop_p``).  A
    dropped sample never reaches the host — no histogram update, no host
    event — and is only visible in the returned drop count."""
    flat = block_ids.reshape(-1)
    hit = _pebs_sample_mask(state, flat.shape[0])
    return (_pebs_apply(state, flat, hit & keep),
            jnp.sum(hit & ~keep).astype(jnp.int32))


@partial(jax.jit, donate_argnums=0)
def pebs_observe(state: PEBSState, block_ids: jax.Array) -> PEBSState:
    """CPU-assisted: only every ``period``-th access in program order is seen.

    The access stream order is the order of ``block_ids`` — identical to what
    the HMU sees, so coverage differences are purely due to sampling.
    """
    return _pebs_observe(state, block_ids)


def pebs_estimate(state: PEBSState) -> jax.Array:
    """Scaled estimate: each sample represents ``period`` accesses."""
    return state.sampled * state.period


# =====================================================================  NB
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NBState:
    """Linux NUMA-balancing emulation (task_numa_work-style cyclic scanner).

    The scanner unmaps ``scan_rate`` blocks per observe call starting at
    ``scan_ptr``; a *first* touch of an unmapped block raises a hint fault
    (host_events += 1), re-maps the block and bumps its fault count.  Blocks
    are promotion candidates after >= 2 faults (two-touch rule).  Frequency
    beyond the first touch per scan pass is invisible — that is the accuracy
    limitation the paper measures.
    """
    mapped: jax.Array        # (n_blocks,) bool: PTE present (access invisible)
    faults: jax.Array        # (n_blocks,) hint-fault counts
    scan_ptr: jax.Array      # scalar cyclic scan position
    scan_rate: int = dataclasses.field(metadata=dict(static=True))
    host_events: Counter64   # hint faults serviced (exact)


def nb_init(n_blocks: int, scan_rate: int) -> NBState:
    return NBState(
        mapped=jnp.ones((n_blocks,), jnp.bool_),
        faults=jnp.zeros((n_blocks,), jnp.int32),
        scan_ptr=jnp.zeros((), jnp.int32),
        scan_rate=int(scan_rate),
        host_events=counter_init(),
    )


def _nb_observe(state: NBState, block_ids: jax.Array,
                stalled: Optional[jax.Array] = None,
                touched: Optional[jax.Array] = None) -> NBState:
    """``stalled`` (a traced bool from the fault model) makes the scanner
    tick a no-op — no unmapping, no cursor advance — while the workload's
    touches still re-map pages as usual: faults stop *arriving*, they are
    not merely delayed, which is what starves the NB lane's signal.
    ``touched`` (fused kernel path) is the batch's precomputed touched-set
    mask, replacing the scatter over the id stream."""
    n_blocks = state.mapped.shape[0]
    # 1. scanner tick: unmap the next scan_rate blocks (cyclic)
    scan_idx = (state.scan_ptr + jnp.arange(state.scan_rate, dtype=jnp.int32)) % n_blocks
    advance = state.scan_rate
    if stalled is not None:
        # a stalled tick unmaps nothing: push the indices out of range (the
        # drop-mode scatter ignores them) and freeze the cursor
        scan_idx = jnp.where(stalled, n_blocks, scan_idx)
        advance = jnp.where(stalled, 0, state.scan_rate)
    mapped = state.mapped.at[scan_idx].set(False, mode="drop")
    # 2. workload touches: first touch of an unmapped block faults
    if touched is None:
        flat = block_ids.reshape(-1)
        touched = jnp.zeros((n_blocks,), jnp.bool_).at[flat].set(
            True, mode="drop")
    faulted = touched & ~mapped
    faults = state.faults + faulted.astype(jnp.int32)
    mapped = mapped | touched
    return dataclasses.replace(
        state,
        mapped=mapped,
        faults=faults,
        scan_ptr=(state.scan_ptr + advance) % n_blocks,
        host_events=counter_add(state.host_events,
                                jnp.sum(faulted).astype(jnp.int32)),
    )


@partial(jax.jit, donate_argnums=0)
def nb_observe(state: NBState, block_ids: jax.Array) -> NBState:
    return _nb_observe(state, block_ids)


def nb_estimate(state: NBState) -> jax.Array:
    """NB's 'hotness' signal: hint-fault counts (recency proxy).
    Two-touch gating is applied by the policy layer (candidates = faults >= 2)."""
    return state.faults


# =====================================================  fused bundle (epoch)
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TelemetryBundle:
    """All three collectors plus the device-side ground-truth counter as ONE
    pytree, so a whole epoch of batches is observed with a single jit
    dispatch (``observe_all`` ``lax.scan``s over the batch axis) instead of
    three dispatches + a host ``np.add.at`` per batch.

    ``true_counts`` is the exact access histogram the evaluation compares
    against — it is what an ideal oracle sees, kept on device so the fused
    path never synchronises with the host mid-epoch.

    ``faults`` (an optional :class:`repro.faults.FaultModel`) rides in the
    same pytree, so fault injection happens inside the same scan and its
    mutable counters are donated with everything else.  ``None`` keeps the
    exact fault-free trace — the structure differs, so the two regimes can
    never share (and therefore never contaminate) a compiled program.
    """
    hmu: HMUState
    pebs: PEBSState
    nb: NBState
    true_counts: jax.Array     # (n_blocks,) int32 exact histogram
    faults: Optional[FaultModel] = None


def bundle_init(
    n_blocks: int,
    pebs_period: int = 10007,
    nb_scan_rate: int = 1,
    hmu_log_capacity: int = 1 << 33,
    faults: Optional[FaultModel] = None,
) -> TelemetryBundle:
    if faults is not None:
        for name, leaf in (("pebs_drop_p", faults.pebs_drop_p),
                           ("hmu_counter_max", faults.hmu_counter_max)):
            if leaf.ndim == 1 and leaf.shape[0] != n_blocks:
                raise ValueError(f"FaultModel.{name} is per-block with "
                                 f"{leaf.shape[0]} entries; this bundle has "
                                 f"n_blocks={n_blocks}")
        # private copy: the bundle is donated every epoch, so sharing one
        # model's buffers across runtimes would delete them under the caller
        faults = jax.tree_util.tree_map(jnp.array, faults)
    return TelemetryBundle(
        hmu=hmu_init(n_blocks, log_capacity=hmu_log_capacity),
        pebs=pebs_init(n_blocks, period=pebs_period),
        nb=nb_init(n_blocks, scan_rate=nb_scan_rate),
        true_counts=jnp.zeros((n_blocks,), jnp.int32),
        faults=faults,
    )


def _count_observe(counts: jax.Array, block_ids: jax.Array,
                   hist: Optional[jax.Array] = None) -> jax.Array:
    if hist is not None:
        return counts + hist
    flat = block_ids.reshape(-1)
    return counts.at[flat].add(1, mode="drop")


@partial(jax.jit, donate_argnums=0)
def count_observe(counts: jax.Array, block_ids: jax.Array) -> jax.Array:
    """Ground-truth histogram update (device-side ``np.add.at`` analogue)."""
    return _count_observe(counts, block_ids)


def _fused_scatter(bundle: TelemetryBundle, flat: jax.Array, pallas,
                   keep: Optional[jax.Array] = None):
    """One ``observe_scatter`` kernel pass over the batch's id stream ->
    the access histogram and PEBS-sampled histogram every collector update
    below is an affine function of."""
    return observe_scatter(
        flat, bundle.pebs.cursor,
        n_blocks=bundle.true_counts.shape[0], period=bundle.pebs.period,
        keep=keep, tile_m=pallas.scatter_tile_m, use_pallas=True,
        interpret=pallas.interpret)


def _bundle_observe(bundle: TelemetryBundle, block_ids: jax.Array,
                    pallas=None) -> TelemetryBundle:
    f = bundle.faults
    flat = block_ids.reshape(-1)
    m = flat.shape[0]
    if pallas is not None and not pallas.uses_scatter_kernel:
        pallas = None
    if f is None:
        hist = pebs_hist = n_kept = touched = None
        if pallas is not None:
            hist, pebs_hist = _fused_scatter(bundle, flat, pallas)
            # hits among the m stream positions = multiples of period in
            # [cursor, cursor + m): exact closed form, no per-position mask
            cur, per = bundle.pebs.cursor, bundle.pebs.period
            n_kept = ((cur + m - 1) // per - (cur - 1) // per
                      ).astype(jnp.int32)
            touched = hist > 0
        with named_scope("telemetry.hmu"):
            hmu = _hmu_observe(bundle.hmu, block_ids, hist=hist)
        with named_scope("telemetry.pebs"):
            pebs = (_pebs_apply(bundle.pebs, flat, None, pebs_hist=pebs_hist,
                                n_kept=n_kept)
                    if pallas is not None
                    else _pebs_observe(bundle.pebs, block_ids))
        with named_scope("telemetry.nb"):
            nb = _nb_observe(bundle.nb, block_ids, touched=touched)
        with named_scope("telemetry.true"):
            true_counts = _count_observe(bundle.true_counts, block_ids,
                                         hist=hist)
        return TelemetryBundle(hmu=hmu, pebs=pebs, nb=nb,
                               true_counts=true_counts)
    # fault injection: per-batch Bernoulli draws from the model's traced
    # rates.  Ground truth is never faulted — it is the evaluation's
    # reference, not a collector.
    key, k_drop, k_stall = jax.random.split(f.key, 3)
    drop_p = (f.pebs_drop_p if f.pebs_drop_p.ndim == 0
              else f.pebs_drop_p[flat])
    keep = jax.random.uniform(k_drop, flat.shape) >= drop_p
    stalled = jax.random.bernoulli(k_stall, f.nb_stall_p)
    if pallas is not None:
        hist, pebs_hist = _fused_scatter(bundle, flat, pallas, keep=keep)
        hit = _pebs_sample_mask(bundle.pebs, m)
        with named_scope("telemetry.pebs"):
            pebs = _pebs_apply(bundle.pebs, flat, None, pebs_hist=pebs_hist,
                               n_kept=jnp.sum(hit & keep).astype(jnp.int32))
        n_dropped = jnp.sum(hit & ~keep).astype(jnp.int32)
        touched = hist > 0
    else:
        hist = touched = None
        with named_scope("telemetry.pebs"):
            pebs, n_dropped = _pebs_observe_faulty(bundle.pebs, block_ids,
                                                   keep)
    with named_scope("telemetry.hmu"):
        hmu = _hmu_observe(bundle.hmu, block_ids,
                           counter_max=f.hmu_counter_max, hist=hist)
    with named_scope("telemetry.nb"):
        nb = _nb_observe(bundle.nb, block_ids, stalled=stalled,
                         touched=touched)
    with named_scope("telemetry.true"):
        true_counts = _count_observe(bundle.true_counts, block_ids,
                                     hist=hist)
    return TelemetryBundle(
        hmu=hmu, pebs=pebs, nb=nb, true_counts=true_counts,
        faults=dataclasses.replace(
            f, key=key,
            pebs_dropped=counter_add(f.pebs_dropped, n_dropped),
            nb_stalls=f.nb_stalls + stalled.astype(jnp.int32)),
    )


def _bundle_resets(bundle: TelemetryBundle) -> TelemetryBundle:
    """Per-epoch collector reset events (drain races): with per-collector
    probability ``reset_p`` the collector's cumulative signal state snaps
    back to empty — HMU counts, the PEBS sampled histogram, NB fault counts
    plus its PTE state (a reset scanner's unmaps are re-established).  The
    *consumer's* epoch-delta baselines are not touched, which is the point:
    the next delta the runtime computes is garbage for one epoch, exactly
    like a log drained underneath the reader."""
    f = bundle.faults
    key, kr = jax.random.split(f.key)
    r = jax.random.uniform(kr, (3,)) < f.reset_p       # COLLECTORS order
    hmu = dataclasses.replace(
        bundle.hmu, counts=jnp.where(r[0], 0, bundle.hmu.counts))
    pebs = dataclasses.replace(
        bundle.pebs, sampled=jnp.where(r[1], 0, bundle.pebs.sampled))
    nb = dataclasses.replace(
        bundle.nb, faults=jnp.where(r[2], 0, bundle.nb.faults),
        mapped=bundle.nb.mapped | r[2])
    return dataclasses.replace(
        bundle, hmu=hmu, pebs=pebs, nb=nb,
        faults=dataclasses.replace(f, key=key,
                                   resets=f.resets + r.astype(jnp.int32)))


# Python-side trace counter: observe_all's body runs once per (shape, static)
# combination; tests use this to prove the fused path compiles once and then
# issues exactly one dispatch per epoch.  A CounterDict view over the same
# repro_trace_total registry family core.runtime uses (kind="observe_all"),
# keeping the historical dict API.
TRACE_COUNTS = obs_metrics.CounterDict(
    obs_metrics.REGISTRY.counter(
        "repro_trace_total",
        help="XLA (re)traces of the fused epoch step / observe_all"),
    "kind", keys=("observe_all",))


@partial(jax.jit, donate_argnums=0, static_argnames=("pallas",))
def observe_all(bundle: TelemetryBundle, batches: jax.Array,
                pallas=None) -> TelemetryBundle:
    """Observe a whole epoch in one dispatch.

    ``batches`` is the epoch's access stream as ``(n_batches, batch_size)``
    block ids (equal-size batches; pad with a repeated id if needed — every
    access is still counted, the paper's collectors have no notion of batch
    boundaries).  The scan applies the identical per-batch update the
    unfused path uses, in the same order, so collector states match the
    per-batch path bit-for-bit.

    With a fault model attached, epoch-granularity reset events are drawn
    once before the scan and the per-batch injections (drops, stalls,
    saturation caps) ride inside it — still one dispatch, and a model with
    all rates at zero leaves every collector value bit-identical.

    The bundle operand is donated (``donate_argnums=0``), like every
    observe above: the runtime's epoch loop re-uses the collector buffers
    in place, and — because the call is async-dispatched — the host is
    already free to flush the previous epochs' batched record sync
    (``EpochRuntime`` with ``sync_every=K``) while the scan runs.

    ``pallas`` (a static ``repro.kernels.dispatch.PallasBackend``) whose
    ``scatter`` site is ``"observe_scatter"`` swaps the per-collector
    scatters inside the scan for ONE kernel pass per batch — one read of
    the id stream feeding all four collector updates — still a single
    dispatch, bit-identical states.
    """
    TRACE_COUNTS["observe_all"] += 1
    if bundle.faults is not None:
        bundle = _bundle_resets(bundle)

    def step(b: TelemetryBundle, block_ids: jax.Array):
        return _bundle_observe(b, block_ids, pallas=pallas), None

    out, _ = jax.lax.scan(step, bundle, batches)
    return out
