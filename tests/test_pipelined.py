"""Pipelined async epoch loop: ``sync_every=K`` batches the runtime's
record syncs (device-resident ``(K,)`` accumulator, one ``device_get``
per K epochs, partial tail flushed on loop exit) and must stay
bit-identical to the synchronous per-epoch-sync loop for every K —
records, per-tenant rows, final placements, single-device and sharded —
while the epoch still costs exactly 2 dispatches, one trace, and one
``record_sync`` per K.  Plus the reuse/timing bugfixes that ride along:
``run()`` returns only its own stream's records, donation through
``_epoch_step`` keeps invalidating the previous epoch's buffers, and the
hint identity-skip cache still short-circuits under pipelining."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import runtime as rtmod
from repro.core.runtime import ALL_POLICIES, EpochRuntime
from repro.dlrm import datagen
from repro.scenarios import (DLRMScenario, KVCacheScenario,
                             MmapBenchScenario, MoEExpertScenario,
                             run_scenario)

REPO = Path(__file__).resolve().parent.parent
SUBPROC_ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"),
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")

SMALL_SPEC = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)


def run_py(code: str, timeout=480):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=SUBPROC_ENV,
                          timeout=timeout, cwd=REPO)


def make_runtime(sync_every=1, fused=True, **kw):
    kw.setdefault("policies", ALL_POLICIES)
    kw.setdefault("pebs_period", 101)
    kw.setdefault("nb_scan_rate", 90)
    return EpochRuntime(400, 40, fused=fused, sync_every=sync_every, **kw)


def make_epochs(n_epochs, n_blocks=400, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n_blocks, (3, 2000)).astype(np.int32)
            for _ in range(n_epochs)]


SCENARIO_FACTORIES = {
    "dlrm": lambda: DLRMScenario(spec=SMALL_SPEC, n_epochs=4,
                                 batches_per_epoch=2, shift_at=2),
    "kv_cache": lambda: KVCacheScenario(batch=2, n_epochs=4,
                                        batches_per_epoch=2,
                                        accesses_per_batch=1_024),
    "moe_experts": lambda: MoEExpertScenario(n_epochs=4, batches_per_epoch=2,
                                             shift_at=2, batch=2),
    "mmap_bench": lambda: MmapBenchScenario(n_epochs=4, batches_per_epoch=2,
                                            accesses_per_batch=8_000),
}


# ------------------------------------------------------- raw-runtime parity
@pytest.mark.parametrize("sync_every", [1, 4, 7])
def test_sync_every_bit_identical_to_reference(sync_every):
    """ISSUE acceptance: K=1 (per-epoch sync), K=4 (7 epochs -> one full
    buffer + a 3-epoch partial tail), K=7 (tail-only flush) all reproduce
    the synchronous reference-path oracle bit for bit — every EpochRecord
    field, every lane, and the final placements."""
    epochs = make_epochs(7)
    ref = make_runtime(fused=False)
    t_ref = ref.run(iter(epochs))
    rt = make_runtime(sync_every=sync_every)
    t = rt.run(iter(epochs))
    for lane in ALL_POLICIES:
        assert len(t.lane(lane)) == 7
        for a, b in zip(t_ref.lane(lane), t.lane(lane)):
            assert a.to_dict() == b.to_dict(), (lane, a.epoch)
    lanes_ref, lanes_k = ref.lanes, rt.lanes
    for name in ALL_POLICIES:
        np.testing.assert_array_equal(lanes_ref[name].slot_to_block,
                                      lanes_k[name].slot_to_block)


def test_record_epochs_are_stamped_in_dispatch_order():
    rt = make_runtime(sync_every=3)
    rt.run(iter(make_epochs(5)))
    for recs in rt.records.values():
        assert [r.epoch for r in recs] == [0, 1, 2, 3, 4]


def test_sync_every_validation():
    with pytest.raises(ValueError, match="sync_every"):
        make_runtime(sync_every=0)
    with pytest.raises(ValueError, match="reference"):
        make_runtime(sync_every=2, fused=False)


# ----------------------------------------------- dispatch / trace accounting
def test_pipelined_epoch_still_two_dispatches_one_record_sync_per_k():
    """ISSUE acceptance: sync_every=K keeps the epoch at observe_all +
    epoch_step (2 dispatches), re-uses ONE trace across K boundaries (the
    row index is a traced scalar, the buffer a fixed (K,) shape), and pulls
    records exactly ceil(n_epochs / K) times."""
    rt = make_runtime(sync_every=4)
    rt.step(make_epochs(1, seed=9)[0])               # warm the trace
    rt.flush()
    with rtmod.counting() as counts:
        rt.run(iter(make_epochs(10)))
        assert counts.dispatch == {"observe_all": 10, "epoch_step": 10,
                                   "reference": 0, "hint_refresh": 0,
                                   "record_sync": 3}     # ceil(10 / 4)
        assert counts.trace["epoch_step"] == 0       # no per-K retrace


def test_manual_step_flush_semantics():
    """K=1 ``step`` keeps its historical per-epoch dict; K>1 returns the
    batches it flushed (empty until a buffer fills) and ``flush`` drains
    the partial tail on demand."""
    epochs = make_epochs(5)
    rt1 = make_runtime(sync_every=1)
    out = rt1.step(epochs[0])
    assert set(out) == set(ALL_POLICIES)
    assert all(hasattr(r, "time_s") for r in out.values())

    rt = make_runtime(sync_every=3)
    assert rt.step(epochs[0]) == {}
    assert rt.step(epochs[1]) == {}
    assert rt.step(epochs[2]) == {}                  # buffer full, not pulled
    flushed = rt.step(epochs[3])                     # pulled AFTER dispatching
    assert {len(v) for v in flushed.values()} == {3}
    assert [r.epoch for r in flushed["hmu_oracle"]] == [0, 1, 2]
    tail = rt.flush()
    assert {len(v) for v in tail.values()} == {1}
    assert rt.flush() == {}                          # idempotent when drained
    for recs in rt.records.values():
        assert len(recs) == 4
    # bit-identity with the per-epoch-sync loop holds for the manual path too
    rt1b = make_runtime(sync_every=1)
    for e in epochs[:4]:
        rt1b.step(e)
    for lane in ALL_POLICIES:
        for x, y in zip(rt1b.records[lane], rt.records[lane]):
            assert x.to_dict() == y.to_dict(), lane


# ------------------------------------------------------------ runtime reuse
def test_second_run_returns_only_its_own_records():
    """Bugfix regression: ``run`` snapshots the record index, so a reused
    runtime's second trajectory holds only the second stream's records;
    the full history stays on :meth:`trajectory`."""
    rt = make_runtime(sync_every=3)
    t1 = rt.run(iter(make_epochs(4, seed=0)))
    t2 = rt.run(iter(make_epochs(3, seed=1)))
    for lane in ALL_POLICIES:
        assert len(t1.lane(lane)) == 4
        assert len(t2.lane(lane)) == 3
        assert [r.epoch for r in t2.lane(lane)] == [4, 5, 6]
        full = rt.trajectory().lane(lane)
        assert len(full) == 7
        assert full[4:] == list(t2.lane(lane))
    # summaries built from t2 must not mix stream-1 epochs
    assert all(r.epoch >= 4 for lane in ALL_POLICIES for r in t2.lane(lane))


def test_run_after_manual_steps_excludes_them():
    rt = make_runtime(sync_every=2)
    rt.step(make_epochs(1, seed=5)[0])               # still buffered
    t = rt.run(iter(make_epochs(3, seed=6)))
    for lane in ALL_POLICIES:
        assert len(t.lane(lane)) == 3                # manual step not included
        assert len(rt.records[lane]) == 4            # ...but kept in history


# ----------------------------------------------------------------- donation
def test_epoch_step_donates_the_previous_state_buffers():
    """Donation regression: observe_all and _epoch_step both take the state
    via ``donate_argnums=0`` — after a step the previous epoch's collector,
    placement, and record-accumulator buffers must be invalidated, not
    copied.  (A silent donation regression would double peak memory at the
    5.24M-page paper scale.)"""
    rt = make_runtime(sync_every=2)
    rt.step(make_epochs(1, seed=0)[0])               # warm the trace
    prev = rt._state
    rt.step(make_epochs(1, seed=1)[0])
    assert prev.bundle.true_counts.is_deleted()      # donated by observe_all
    assert prev.placement.slot_to_block.is_deleted()  # donated by _epoch_step
    assert prev.out_buf["drained_lo"].is_deleted()      # accumulator rides along


# --------------------------------------------- hints under the batched sync
def test_hint_identity_skip_unchanged_under_pipelining():
    """The per-epoch hint refresh is a transfer, not a dispatch, and the
    identity-skip cache still short-circuits with sync_every>1: a static
    pipeline whose ranks never change uploads once, and hint_refresh counts
    the same for K=1 and K=4 over the same stream."""
    from repro.hints import HintPipeline, LookaheadWindow

    def counted(sync_every):
        rt = EpochRuntime(
            400, 40, policies=ALL_POLICIES, pebs_period=101, nb_scan_rate=90,
            sync_every=sync_every,
            hints=HintPipeline(400, lookahead=LookaheadWindow(400, depth=1)))
        epochs = make_epochs(6, seed=3)
        rt.step(epochs[0], lookahead=(epochs[1],))   # warm
        rt.flush()
        with rtmod.counting() as counts:
            traj = rt.run(iter(epochs))
            return dict(counts.dispatch), traj

    d1, t1 = counted(1)
    d4, t4 = counted(4)
    assert d1["hint_refresh"] == d4["hint_refresh"] > 0
    assert d4["record_sync"] == 2                    # ceil(6 / 4)
    assert d1["record_sync"] == 6
    for lane in ALL_POLICIES:
        for a, b in zip(t1.lane(lane), t4.lane(lane)):
            assert a.to_dict() == b.to_dict(), lane


# ----------------------------------------------------------- scenario parity
@pytest.mark.parametrize("name", sorted(SCENARIO_FACTORIES))
def test_scenario_sync_every_parity(name):
    """ISSUE acceptance: every workload scenario's trajectory and summary
    are identical under the batched sync (K=3 over 4 epochs — one full
    buffer plus a partial tail), hints enabled."""
    base = run_scenario(SCENARIO_FACTORIES[name](), hints=True)
    batched = run_scenario(SCENARIO_FACTORIES[name](), hints=True,
                           sync_every=3)
    assert batched["trajectory"] == base["trajectory"]
    assert batched["summary"] == base["summary"]


def test_fleet_sync_every_parity_including_tenant_rows():
    """ISSUE acceptance: the multi-tenant fleet's per-tenant (L, T)
    accounting rows ride the batched sync unchanged — global trajectory,
    summary, and every tenant record identical for K=3 vs K=1."""
    from repro.fleet import FleetScenario, TenantSpec, run_fleet

    def fleet():
        return FleetScenario(
            [TenantSpec(SCENARIO_FACTORIES["dlrm"](), weight=10.0,
                        name="dlrm"),
             TenantSpec(SCENARIO_FACTORIES["mmap_bench"](), weight=1.0,
                        name="scanner"),
             TenantSpec(SCENARIO_FACTORIES["moe_experts"](), weight=1.0,
                        name="moe")],
            k_hot=300, capacity="weighted")

    base = run_fleet(fleet(), hints=True)
    batched = run_fleet(fleet(), hints=True, sync_every=3)
    assert batched["trajectory"] == base["trajectory"]
    assert batched["summary"] == base["summary"]
    assert batched["tenants"] == base["tenants"]


@pytest.mark.slow
def test_sharded_sync_every_parity():
    """ISSUE acceptance: the batched sync is sharding-transparent — the
    (K, L)/(K, L, T) accumulator leaves replicate over the mesh and an
    8-device sync_every=3 run equals the single-device per-epoch-sync run
    exactly (subprocess: device count must be set before jax init)."""
    r = run_py("""
        import dataclasses, json
        from repro.dlrm import datagen
        import jax
        from repro.launch.mesh import make_telemetry_mesh
        from repro.scenarios.dlrm import run_online

        spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)
        kw = dict(spec=spec, n_epochs=4, batches_per_epoch=2, shift_at=2,
                  seed=0, hints=True)
        ref = run_online(**kw)
        mesh = make_telemetry_mesh(8)
        with jax.set_mesh(mesh):
            shd = run_online(mesh=mesh, sync_every=3, **kw)
        assert json.dumps(ref["trajectory"], sort_keys=True) == \\
            json.dumps(shd["trajectory"], sort_keys=True)
        assert json.dumps(ref["summary"], sort_keys=True) == \\
            json.dumps(shd["summary"], sort_keys=True)
        print("OK")
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
