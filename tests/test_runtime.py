"""Epoch-driven runtime tests: the fused observe_all path is bit-identical to
the per-batch path and issues one jit dispatch per epoch; the fused
device-resident epoch_step is bit-identical to the per-lane reference path
and holds a whole epoch to two dispatches; sharded state matches
single-device; on the phase-shift workload proactive/EWMA over HMU counts
beats NB two-touch on modeled time in every post-shift epoch."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import runtime as rtmod
from repro.core import telemetry as tel
from repro.core.manager import TieringManager
from repro.core.runtime import ALL_POLICIES, EpochRuntime
from repro.dlrm import datagen

REPO = Path(__file__).resolve().parent.parent
SUBPROC_ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"),
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")


def run_py(code: str, timeout=480):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=SUBPROC_ENV,
                          timeout=timeout, cwd=REPO)


# ------------------------------------------------------------- fused observe
def make_batches(n_blocks=400, n_batches=5, batch=3000, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n_blocks, (n_batches, batch)).astype(np.int32)


def test_observe_all_bit_identical_to_per_batch_path():
    n = 400
    batches = make_batches(n)
    kw = dict(pebs_period=101, nb_scan_rate=90)
    ref = TieringManager(n, 40, **kw)
    for b in batches:
        ref.observe(b)
    fused = TieringManager(n, 40, **kw)
    fused.observe_epoch(batches)
    ref_leaves = jax.tree_util.tree_leaves(ref.bundle)
    fused_leaves = jax.tree_util.tree_leaves(fused.bundle)
    assert len(ref_leaves) == len(fused_leaves)
    for a, b in zip(ref_leaves, fused_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_observe_all_one_dispatch_per_epoch(monkeypatch):
    """The fused path must issue exactly one observe_all call per epoch, never
    fall back to the per-batch collector jits, and re-use one trace across
    equal-shaped epochs."""
    n = 256
    batches = make_batches(n, n_batches=4, batch=1000)
    mgr = TieringManager(n, 32, pebs_period=97, nb_scan_rate=64)

    dispatches = []
    real_observe_all = tel.observe_all
    monkeypatch.setattr(
        tel, "observe_all",
        lambda bundle, arr: (dispatches.append(arr.shape),
                             real_observe_all(bundle, arr))[1])

    def forbidden(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("fused path must not use per-batch observe jits")

    monkeypatch.setattr(tel, "hmu_observe", forbidden)
    monkeypatch.setattr(tel, "pebs_observe", forbidden)
    monkeypatch.setattr(tel, "nb_observe", forbidden)
    monkeypatch.setattr(tel, "count_observe", forbidden)

    # warm the trace with an identically-shaped manager, then count re-traces
    tel.observe_all(tel.bundle_init(n, pebs_period=97, nb_scan_rate=64),
                    jnp.asarray(batches))
    dispatches.clear()
    with rtmod.counting() as counts:
        mgr.observe_epoch(batches)
        mgr.observe_epoch(make_batches(n, n_batches=4, batch=1000, seed=1))
        assert dispatches == [batches.shape, batches.shape]
        assert counts.observe_trace["observe_all"] == 0      # no re-trace


def test_observe_epoch_rejects_flat_stream():
    mgr = TieringManager(64, 8)
    with pytest.raises(ValueError):
        mgr.observe_epoch(np.zeros(100, np.int32))


# ----------------------------------------------------------- runtime basics
def test_runtime_rejects_unknown_policy():
    with pytest.raises(ValueError):
        EpochRuntime(64, 8, policies=("oracle_top_k_typo",))


def test_runtime_records_and_lane_invariants():
    n, k = 500, 50
    rt = EpochRuntime(n, k, policies=ALL_POLICIES, bytes_per_access=64.0,
                      block_bytes=1024.0, pebs_period=101, nb_scan_rate=125)
    rng = np.random.default_rng(0)
    for _ in range(3):
        rt.step(rng.integers(0, n, (2, 4000)).astype(np.int32))
    for name, lane in rt.lanes.items():
        recs = rt.records[name]
        assert [r.epoch for r in recs] == [0, 1, 2]
        # slot<->block maps stay mutually consistent and capacity-bounded
        s2b, b2s = lane.slot_to_block, lane.block_to_slot
        assert (s2b >= 0).sum() == (b2s >= 0).sum() <= k
        for slot, blk in enumerate(s2b):
            if blk >= 0:
                assert b2s[blk] == slot
        for r in recs:
            assert r.resident <= k
            assert r.time_s >= r.access_s >= 0
            assert 0.0 <= r.accuracy <= 1.0 and 0.0 <= r.coverage <= 1.0
    # epoch 0 serves everything from the slow tier (cold start)
    for name in rt.records:
        assert rt.records[name][0].resident == 0


def test_runtime_uniform_stream_converges_all_hmu_lanes():
    """On a stationary skewed stream every HMU-fed lane should reach high
    coverage of the true hot set after a couple of epochs."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    n, k = spec.n_pages, 200
    rt = EpochRuntime(n, k, policies=("hmu_oracle", "proactive_ewma"),
                      bytes_per_access=spec.row_bytes,
                      block_bytes=spec.page_bytes, nb_scan_rate=n // 2)
    s = datagen.ZipfPageSampler(spec, seed=3)
    for _ in range(4):
        rt.step(np.stack([s.sample(spec.lookups_per_batch) for _ in range(2)]))
    for name in ("hmu_oracle", "proactive_ewma"):
        assert rt.records[name][-1].coverage > 0.7, name


def test_trajectory_json_roundtrip():
    import json

    rt = EpochRuntime(128, 16, policies=("hmu_oracle",), nb_scan_rate=32)
    rng = np.random.default_rng(1)
    rt.step(rng.integers(0, 128, (2, 500)).astype(np.int32))
    data = json.loads(rt.trajectory().to_json(shift_at=0))
    assert data["n_blocks"] == 128 and data["k_hot"] == 16
    rec = data["lanes"]["hmu_oracle"][0]
    assert {"epoch", "time_s", "accuracy", "coverage",
            "promoted", "demoted"} <= set(rec)


# ------------------------------------------------- fused multi-lane step
def _phase_shift_run(fused: bool, spec, n_epochs=6, batches_per_epoch=3,
                     shift_at=3, **kw):
    n = spec.n_pages
    rt = EpochRuntime(n, fused=fused, policies=ALL_POLICIES,
                      bytes_per_access=spec.row_bytes,
                      block_bytes=spec.page_bytes, **kw)
    traj = rt.run(datagen.phase_shift_epochs(
        spec, n_epochs=n_epochs, batches_per_epoch=batches_per_epoch,
        shift_at=shift_at, rotate_by=n // 2, seed=0))
    return rt, traj


def test_fused_step_bit_identical_to_reference_path():
    """Tentpole acceptance: every EpochRecord field of every lane and epoch
    from the device-resident fused step equals the per-lane reference path
    bit for bit on a phase-shift workload, including the final placements."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    kw = dict(k_hot=250, pebs_period=401, nb_scan_rate=spec.n_pages // 4)
    rt_f, tf = _phase_shift_run(True, spec, **kw)
    rt_r, tr = _phase_shift_run(False, spec, **kw)
    for lane in ALL_POLICIES:
        ra, rb = tf.lane(lane), tr.lane(lane)
        assert len(ra) == len(rb) == 6
        for a, b in zip(ra, rb):
            assert a.to_dict() == b.to_dict(), (lane, a.epoch)
    lanes_f, lanes_r = rt_f.lanes, rt_r.lanes
    for name in ALL_POLICIES:
        np.testing.assert_array_equal(lanes_f[name].slot_to_block,
                                      lanes_r[name].slot_to_block)
        np.testing.assert_array_equal(lanes_f[name].block_to_slot,
                                      lanes_r[name].block_to_slot)


def test_fused_step_bit_identical_with_hints_and_rate_limit():
    """Same bit-identity under the non-default lane configs: static hints
    feeding the hinted lane and an NB promotion rate limit."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=10_000)
    rng = np.random.default_rng(7)
    hints = (rng.random(spec.n_pages) * (rng.random(spec.n_pages) < 0.1)
             ).astype(np.float32)
    kw = dict(k_hot=200, pebs_period=211, nb_scan_rate=spec.n_pages // 3,
              hint_rank=hints, hint_weight=0.4, nb_rate_limit=37,
              ewma_alpha=0.3)
    _, tf = _phase_shift_run(True, spec, **kw)
    _, tr = _phase_shift_run(False, spec, **kw)
    for lane in ALL_POLICIES:
        for a, b in zip(tf.lane(lane), tr.lane(lane)):
            assert a.to_dict() == b.to_dict(), (lane, a.epoch)


def test_fused_epoch_is_two_dispatches_and_one_trace():
    """Acceptance: one epoch of all five lanes = observe_all + epoch_step
    (two dispatches), nothing from the per-lane reference machinery, and
    equal-shaped epochs re-use one epoch_step trace.  (Counted inside
    runtime.counting(), so activity from other tests can't leak in.)"""
    n = 512
    rt = EpochRuntime(n, 64, policies=ALL_POLICIES, pebs_period=97,
                      nb_scan_rate=128)
    rng = np.random.default_rng(0)
    rt.step(rng.integers(0, n, (3, 1000)).astype(np.int32))  # warm the trace
    with rtmod.counting() as counts:
        for _ in range(3):
            rt.step(rng.integers(0, n, (3, 1000)).astype(np.int32))
        assert counts.dispatch == {"observe_all": 3, "epoch_step": 3,
                                   "reference": 0, "hint_refresh": 0,
                                   "record_sync": 3}
        assert counts.trace["epoch_step"] == 0               # no re-trace


def test_fused_runtime_lane_views_keep_invariants():
    n, k = 600, 60
    rt = EpochRuntime(n, k, policies=ALL_POLICIES, pebs_period=101,
                      nb_scan_rate=150)
    rng = np.random.default_rng(1)
    for _ in range(3):
        rt.step(rng.integers(0, n, (2, 5000)).astype(np.int32))
    for name, lane in rt.lanes.items():
        s2b, b2s = lane.slot_to_block, lane.block_to_slot
        assert (s2b >= 0).sum() == (b2s >= 0).sum() <= k
        for slot, blk in enumerate(s2b):
            if blk >= 0:
                assert b2s[blk] == slot, name
    assert rt.lanes["proactive_ewma"].pred is not None
    assert rt.lanes["hmu_oracle"].pred is None


@pytest.mark.slow
def test_sharded_observe_all_and_epoch_step_parity():
    """Tentpole acceptance: trajectories with all per-block state sharded
    over an 8-device mesh equal the single-device run exactly (subprocess:
    device count must be set before jax initializes)."""
    r = run_py("""
        import dataclasses, json
        from repro.dlrm import datagen, tracesim
        import jax
        from repro.launch.mesh import make_telemetry_mesh

        spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)
        # hints=True also proves the sharded per-epoch hint refresh
        # (device_put with the mesh sharding) stays bit-identical
        kw = dict(spec=spec, n_epochs=4, batches_per_epoch=2, shift_at=2,
                  seed=0, hints=True)
        ref = tracesim.run_online(**kw)
        mesh = make_telemetry_mesh(8)
        with jax.set_mesh(mesh):
            shd = tracesim.run_online(mesh=mesh, **kw)
        assert json.dumps(ref["trajectory"], sort_keys=True) == \\
            json.dumps(shd["trajectory"], sort_keys=True)
        print("OK")
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_paper_scale_sharded_online_run():
    """§VI at paper scale: a 5.24M-page phase-shift trajectory with sharded
    telemetry + lane state completes and produces sane records."""
    r = run_py("""
        import dataclasses
        from repro.dlrm import datagen, tracesim
        import jax
        from repro.launch.mesh import make_telemetry_mesh

        spec = datagen.DLRMTraceSpec(n_params=5_368_709_120,
                                     lookups_per_batch=400_000)
        assert spec.n_pages == 5_242_880
        mesh = make_telemetry_mesh(8)
        with jax.set_mesh(mesh):
            out = tracesim.run_online(
                spec=spec, mesh=mesh, n_epochs=3, batches_per_epoch=2,
                shift_at=2, k_hot=spec.n_pages // 64, seed=0)
        lanes = out["trajectory"]["lanes"]
        assert set(lanes) == set(%r)
        for recs in lanes.values():
            assert len(recs) == 3
            assert all(r["time_s"] > 0 for r in recs)
        # after one epoch the lanes lock on: the sparse stream leaves the
        # tail of the top-k tie-dominated (count-1 pages), so the threshold-
        # gated lanes show precision where the full-k oracle is diluted
        assert lanes["hmu_oracle"][1]["accuracy"] > 0.3
        assert lanes["reactive_watermark"][1]["accuracy"] > 0.6
        assert lanes["hinted"][1]["accuracy"] > 0.6
        print("OK")
    """ % (list(ALL_POLICIES),))
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ------------------------------------------------- hints + prefetch lane
def _hints_run(fused: bool, spec, n_epochs=6, batches_per_epoch=3,
               shift_at=3, prefetch_overlap=1.0, **kw):
    """Phase-shift run with a fresh default HintPipeline (pipelines are
    stateful, so every runtime gets its own)."""
    from repro.hints import HintPipeline

    n = spec.n_pages
    rt = EpochRuntime(n, fused=fused, policies=ALL_POLICIES,
                      bytes_per_access=spec.row_bytes,
                      block_bytes=spec.page_bytes,
                      hints=HintPipeline.for_dlrm(spec, seed=0),
                      prefetch_overlap=prefetch_overlap, **kw)
    traj = rt.run(datagen.phase_shift_epochs(
        spec, n_epochs=n_epochs, batches_per_epoch=batches_per_epoch,
        shift_at=shift_at, rotate_by=n // 2, seed=0))
    return rt, traj


def test_fused_step_bit_identical_with_hint_pipeline():
    """Tentpole acceptance: with the HintPipeline refreshing hint_rank /
    prefetch_rank every epoch, every EpochRecord field of all SIX lanes —
    including the prefetch lane's overlap-accounted time and hidden_s —
    matches the reference path bit for bit, as do the final placements."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    kw = dict(k_hot=250, pebs_period=401, nb_scan_rate=spec.n_pages // 4)
    rt_f, tf = _hints_run(True, spec, **kw)
    rt_r, tr = _hints_run(False, spec, **kw)
    assert len(ALL_POLICIES) == 6 and "prefetch" in ALL_POLICIES
    for lane in ALL_POLICIES:
        for a, b in zip(tf.lane(lane), tr.lane(lane)):
            assert a.to_dict() == b.to_dict(), (lane, a.epoch)
    lanes_f, lanes_r = rt_f.lanes, rt_r.lanes
    for name in ALL_POLICIES:
        np.testing.assert_array_equal(lanes_f[name].slot_to_block,
                                      lanes_r[name].slot_to_block)


def test_hint_enabled_fused_epoch_is_still_two_dispatches():
    """ISSUE acceptance: the per-epoch hint refresh is a state-leaf transfer
    (DISPATCH_COUNTS['hint_refresh']), not a dispatch — a prefetch-enabled
    epoch stays at observe_all + epoch_step, on one re-used trace."""
    from repro.hints import HintPipeline, LookaheadWindow

    n = 512
    rng = np.random.default_rng(0)

    def epoch():
        return rng.integers(0, n, (3, 1000)).astype(np.int32)

    rt = EpochRuntime(n, 64, policies=ALL_POLICIES, pebs_period=97,
                      nb_scan_rate=128,
                      hints=HintPipeline(n, lookahead=LookaheadWindow(n)))
    rt.step(epoch(), lookahead=(epoch(),))        # warm the trace
    with rtmod.counting() as counts:
        for _ in range(3):
            rt.step(epoch(), lookahead=(epoch(),))
        assert counts.dispatch == {"observe_all": 3, "epoch_step": 3,
                                   "reference": 0, "hint_refresh": 3,
                                   "record_sync": 3}
        assert counts.trace["epoch_step"] == 0               # no re-trace


def test_prefetch_beats_static_hinted_on_post_shift_coverage():
    """ISSUE acceptance: on the phase-shift trajectory the lookahead-driven
    prefetch lane beats the static hinted lane on hot-set coverage — the
    lookahead covers the rotation in the very epoch it happens, while the
    static table prior goes stale (and gets down-weighted)."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    shift_at = 3
    rt, traj = _hints_run(True, spec, shift_at=shift_at, k_hot=250,
                          pebs_period=401, nb_scan_rate=spec.n_pages // 4)
    pre_cov = np.array([r.coverage for r in traj.lane("prefetch")])
    hin_cov = np.array([r.coverage for r in traj.lane("hinted")])
    assert pre_cov[shift_at:].mean() > hin_cov[shift_at:].mean() + 0.2
    assert pre_cov[shift_at] > 0.9        # covered in the shift epoch itself
    assert rt.hints.detector.shifts_detected == 1


def test_prefetch_overlap_time_no_worse_than_stop_the_world():
    """ISSUE acceptance: the prefetch lane's overlap-accounted epoch time is
    no worse than non-overlapped migration in every epoch (and strictly
    better once it migrates), with everything else unchanged."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    kw = dict(k_hot=250, pebs_period=401, nb_scan_rate=spec.n_pages // 4)
    _, t_ov = _hints_run(True, spec, prefetch_overlap=1.0, **kw)
    _, t_st = _hints_run(True, spec, prefetch_overlap=0.0, **kw)
    ov, st = t_ov.times("prefetch"), t_st.times("prefetch")
    assert (ov <= st).all(), (ov, st)
    assert ov.sum() < st.sum()
    hidden = np.array([r.hidden_s for r in t_ov.lane("prefetch")])
    np.testing.assert_allclose(st - ov, hidden, rtol=1e-9)
    assert all(r.hidden_s == 0.0 for r in t_st.lane("prefetch"))
    # the overlap knob touches nothing but the prefetch lane's accounting
    for lane in ALL_POLICIES[:-1]:
        for a, b in zip(t_ov.lane(lane), t_st.lane(lane)):
            assert a.to_dict() == b.to_dict(), (lane, a.epoch)


def test_counting_scopes_and_restores_the_counters():
    """runtime.counting() hands back scope-relative views of all three
    counter dicts (zero-based at entry) and never mutates the live dicts, so
    tests and benchmark runs stop leaking dispatch counts into each other
    while module-level totals stay monotonic."""
    rtmod.DISPATCH_COUNTS["observe_all"] += 1    # pre-existing activity
    outer_before = dict(rtmod.DISPATCH_COUNTS)
    rt = EpochRuntime(64, 8, policies=("hmu_oracle",), nb_scan_rate=16)
    rng = np.random.default_rng(0)
    with rtmod.counting() as counts:
        assert counts.dispatch["observe_all"] == 0           # zero at entry
        assert counts.trace["epoch_step"] == 0
        assert counts.observe_trace["observe_all"] == 0
        rt.step(rng.integers(0, 64, (2, 100)).astype(np.int32))
        assert counts.dispatch["observe_all"] == 1
        assert counts.dispatch["epoch_step"] == 1
    # live totals: what was there before, plus the block's activity
    assert rtmod.DISPATCH_COUNTS["observe_all"] == \
        outer_before["observe_all"] + 1
    assert rtmod.DISPATCH_COUNTS["epoch_step"] == \
        outer_before["epoch_step"] + 1


def test_counting_is_safely_nestable():
    """Regression (fleet satellite): re-entering counting() must not blank
    the outer scope's accrual — run_fleet composes counting() around its
    per-tenant solo sub-runs inside callers' own counting() scopes.  The
    outer view must read correctly before, DURING, and after inner scopes
    (the old zero-in-place implementation blanked the outer view while an
    inner scope was open), inner activity must accrue outward, and the
    exception path must not corrupt anything."""
    base = rtmod.DISPATCH_COUNTS["observe_all"]
    with rtmod.counting() as outer:
        rtmod.DISPATCH_COUNTS["observe_all"] += 1
        with rtmod.counting() as inner:
            rtmod.DISPATCH_COUNTS["observe_all"] += 2
            assert inner.dispatch["observe_all"] == 2
            assert outer.dispatch["observe_all"] == 3    # visible mid-inner
        assert outer.dispatch["observe_all"] == 3
        # full-dict comparison works on views (benchmark gate idiom)
        assert dict(inner.dispatch.items())["observe_all"] == 2
        try:
            with rtmod.counting():
                rtmod.DISPATCH_COUNTS["observe_all"] += 1
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        assert outer.dispatch["observe_all"] == 4
    assert rtmod.DISPATCH_COUNTS["observe_all"] == base + 4


def test_pending_migration_resets_per_run():
    """Regression: pending_migration_s (the prefetch lane's boundary
    migration not yet charged to any record) must not carry into a reused
    runtime's next run() — the pending boundary belongs to the previous
    workload (where it is surfaced via the summary), so charging it against
    the new stream's first epoch would double-count it."""
    from repro.hints import HintPipeline, LookaheadWindow

    n = 400
    rng = np.random.default_rng(0)

    def epoch():
        return rng.integers(0, n, (2, 3000)).astype(np.int32)

    rt = EpochRuntime(n, 50, policies=("prefetch",), nb_scan_rate=100,
                      hints=HintPipeline(n, lookahead=LookaheadWindow(n)))
    # warm-up steps with live lookahead: the last boundary promotes, leaving
    # a pending migration that overlaps an epoch that never runs here
    rt.step(epoch(), lookahead=(epoch(),))
    rt.step(epoch(), lookahead=(epoch(),))
    assert rt.pending_migration_s > 0.0
    rt.run([epoch(), epoch()])
    first_rec_of_run = rt.records["prefetch"][2]
    assert first_rec_of_run.migration_s == 0.0   # previous pending not charged
    assert first_rec_of_run.hidden_s == 0.0


def test_prefetch_without_pipeline_stays_idle():
    """No hint pipeline -> empty lookahead window -> the prefetch lane never
    promotes (no churn from an absent compiler)."""
    n = 400
    rt = EpochRuntime(n, 50, policies=("prefetch",), nb_scan_rate=100)
    rng = np.random.default_rng(0)
    for _ in range(3):
        rt.step(rng.integers(0, n, (2, 2000)).astype(np.int32))
    recs = rt.records["prefetch"]
    assert all(r.promoted == 0 and r.resident == 0 for r in recs)
    assert all(r.host_events == 0.0 for r in recs)


# ------------------------------------------------- phase-shift acceptance
def test_proactive_beats_nb_every_post_shift_epoch():
    """ISSUE acceptance: on the phase-shift workload, proactive_ewma over HMU
    counts beats nb_two_touch on modeled time in EVERY post-shift epoch."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    n = spec.n_pages
    k, shift_at, n_epochs = 250, 3, 7
    rt = EpochRuntime(
        n, k, policies=("proactive_ewma", "nb_two_touch"),
        bytes_per_access=spec.row_bytes, block_bytes=spec.page_bytes,
        pebs_period=401, nb_scan_rate=n // 4,
    )
    traj = rt.run(datagen.phase_shift_epochs(
        spec, n_epochs=n_epochs, batches_per_epoch=4, shift_at=shift_at,
        rotate_by=n // 2, seed=0))
    pro = traj.times("proactive_ewma")[shift_at:]
    nb = traj.times("nb_two_touch")[shift_at:]
    assert pro.shape == nb.shape == (n_epochs - shift_at,)
    assert (pro < nb).all(), (pro, nb)


def test_proactive_recovers_accuracy_after_shift_nb_does_not():
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=20_000)
    n, k, shift_at = spec.n_pages, 250, 3
    rt = EpochRuntime(
        n, k, policies=("proactive_ewma", "nb_two_touch"),
        bytes_per_access=spec.row_bytes, block_bytes=spec.page_bytes,
        nb_scan_rate=n // 4,
    )
    traj = rt.run(datagen.phase_shift_epochs(
        spec, n_epochs=7, batches_per_epoch=4, shift_at=shift_at,
        rotate_by=n // 2, seed=0))
    pro_acc = [r.accuracy for r in traj.lane("proactive_ewma")]
    nb_acc = [r.accuracy for r in traj.lane("nb_two_touch")]
    # EWMA re-converges after the rotation; NB's cumulative two-touch doesn't
    assert pro_acc[-1] > 0.5
    assert pro_acc[-1] > nb_acc[-1] + 0.2


def test_phase_shift_generator_rotates_hot_set():
    spec = datagen.SMALL
    s = datagen.PhaseShiftSampler(spec, rotate_by=spec.n_pages // 2, seed=0)
    k = 100
    before = set(s.true_top_k_pages(k, phase=0).tolist())
    after = set(s.true_top_k_pages(k, phase=1).tolist())
    assert not before & after             # fully disjoint hot heads
    # samples actually concentrate on each phase's hot head
    for phase, hot in ((0, before), (1, after)):
        pages = s.sample(20_000, phase=phase)
        share = np.isin(pages, list(hot)).mean()
        assert share > 0.5, (phase, share)
