"""repro.faults — fault injection + degradation-aware tiering (PR 7).

The contract under test, in the ISSUE's words: a fault-free
:class:`FaultModel` is **bit-identical** to running with none (single
device, sharded, fleet, every ``sync_every=K``); with faults *on* the
epoch still costs exactly 2 dispatches and one trace; and each injected
fault degrades its collector the way the real mechanism does — saturation
pins counters, drops starve PEBS, resets wipe HMU deltas, stalls freeze
the NB scanner, staleness serves estimates ``d`` epochs late — while the
hardened runtime (quality-gated fallback + demotion hysteresis) holds
coverage where the naive lane collapses."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import runtime as rtmod
from repro.core import telemetry as tel
from repro.core.runtime import ALL_POLICIES, EpochRuntime
from repro.faults import (COLLECTORS, Counter64, FaultModel, Hardening,
                          LANE_COLLECTOR, counter_add, counter_init,
                          counter_scaled_add)
from repro.fleet import FleetScenario, TenantSpec, run_fleet
from repro.scenarios import DLRMScenario, KVCacheScenario, run_scenario
from repro.dlrm import datagen

REPO = Path(__file__).resolve().parent.parent
SUBPROC_ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"),
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")
SMALL_SPEC = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)


def run_py(code: str, timeout=480):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=SUBPROC_ENV,
                          timeout=timeout, cwd=REPO)


def make_runtime(**kw):
    kw.setdefault("policies", ALL_POLICIES)
    kw.setdefault("pebs_period", 101)
    kw.setdefault("nb_scan_rate", 90)
    return EpochRuntime(400, 40, fused=True, **kw)


def make_epochs(n_epochs, n_blocks=400, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n_blocks, (3, 2000)).astype(np.int32)
            for _ in range(n_epochs)]


def zipf_epochs(n_epochs, n_blocks=400, seed=3):
    rng = np.random.default_rng(seed)
    z = (rng.zipf(1.5, size=(n_epochs, 4, 4000)) % n_blocks).astype(np.int32)
    return [z[i] for i in range(n_epochs)]


# =====================================================  Counter64 exactness
def test_counter64_exact_across_2p24():
    """Satellite regression: float32 scalars silently stop incrementing at
    2^24; the hi/lo pair must march straight through."""
    c = counter_init()
    step = jnp.asarray(3_000_000, jnp.int32)
    for i in range(1, 8):                      # 21M > 2^24
        c = counter_add(c, step)
        assert float(c) == 3_000_000.0 * i
    assert int(c) == 21_000_000


def test_counter64_scaled_add_and_validation():
    a = counter_add(counter_init(), jnp.asarray(10_000_000, jnp.int32))
    b = counter_scaled_add(counter_init(), a, 3)
    assert float(b) == 30_000_000.0
    with pytest.raises(ValueError, match="scale"):
        counter_scaled_add(counter_init(), a, 64)
    with pytest.raises(ValueError, match="scale"):
        counter_scaled_add(counter_init(), a, -1)


def test_counter64_reads_like_the_old_float_scalar():
    """Every pre-existing caller reads event scalars via float(...) — the
    Counter64 must satisfy that protocol exactly."""
    st = tel.hmu_init(4, log_capacity=100)
    st = tel.hmu_observe(st, jnp.zeros((30,), jnp.int32))
    assert isinstance(st.log_used, Counter64)
    assert float(st.log_used) == 30.0
    assert int(float(st.log_dropped)) == 0


# ==========================================================  model validation
def test_fault_model_validation():
    with pytest.raises(ValueError, match="reset_p"):
        FaultModel.create(reset_p=np.zeros((2,), np.float32))
    with pytest.raises(ValueError, match="stale_epochs"):
        FaultModel.create(stale_epochs=-1)
    with pytest.raises(ValueError, match="pebs_drop_p"):
        FaultModel.create(pebs_drop_p=1.5)
    with pytest.raises(ValueError, match="entries"):
        FaultModel.create(pebs_drop_p=np.zeros((7,), np.float32), n_blocks=9)


def test_fault_model_for_segments_rejects_global_knobs_per_segment():
    with pytest.raises(ValueError, match="non-per-block"):
        FaultModel.for_segments((0, 5, 10), [{"reset_p": 1.0}, None])
    with pytest.raises(ValueError, match="offsets"):
        FaultModel.for_segments((0, 5), [{}, {}])


def test_fault_model_for_segments_builds_per_block_arrays():
    fm = FaultModel.for_segments(
        (0, 4, 10),
        [{"pebs_drop_p": 0.5, "hmu_counter_bits": 3}, None],
        nb_stall_p=0.25)
    drop = np.asarray(fm.pebs_drop_p)
    cap = np.asarray(fm.hmu_counter_max)
    np.testing.assert_allclose(drop[:4], 0.5)
    np.testing.assert_allclose(drop[4:], 0.0)
    assert (cap[:4] == 7).all() and (cap[4:] == np.iinfo(np.int32).max).all()
    assert float(fm.nb_stall_p) == 0.25


def test_hardening_validation():
    with pytest.raises(ValueError, match="hysteresis"):
        Hardening.make(demote_hysteresis=0)
    with pytest.raises(ValueError, match="unknown fallback lane"):
        Hardening.make(fallback={"nope": "hmu"})
    with pytest.raises(ValueError, match="compiler hints"):
        Hardening.make(fallback={"prefetch": "hmu"})
    with pytest.raises(ValueError, match="different collector"):
        Hardening.make(fallback={"hmu_oracle": "hmu"})
    with pytest.raises(ValueError, match="unknown fallback collector"):
        Hardening.make(fallback={"hmu_oracle": "tsc"})
    with pytest.raises(ValueError, match="quality_floor"):
        Hardening.make(quality_floor=1.5)


def test_faults_require_the_fused_path():
    with pytest.raises(ValueError, match="fused"):
        EpochRuntime(100, 10, fused=False, faults=FaultModel.create())
    with pytest.raises(ValueError, match="fused"):
        EpochRuntime(100, 10, fused=False, hardening=Hardening.make())


# ===========================================  neutral-model bit-identity
@pytest.mark.parametrize("sync_every", [1, 4])
def test_neutral_model_bit_identical_single_device(sync_every):
    """ISSUE acceptance: faults disabled => the fused path reproduces
    today's records and placements bit for bit, for K in {1, 4}."""
    epochs = make_epochs(6)
    base = make_runtime(sync_every=sync_every)
    tb = base.run(iter(epochs))
    neut = make_runtime(sync_every=sync_every,
                        faults=FaultModel.create(n_blocks=400))
    tn = neut.run(iter(epochs))
    for lane in ALL_POLICIES:
        for x, y in zip(tb.lane(lane), tn.lane(lane)):
            assert x.to_dict() == y.to_dict(), (lane, x.epoch)
        np.testing.assert_array_equal(base.lanes[lane].slot_to_block,
                                      neut.lanes[lane].slot_to_block)


def test_neutral_hardening_changes_nothing_but_reports_quality():
    """Hardening enabled on healthy telemetry: decisions (and every record
    field but the new quality estimate) match the unhardened run, and the
    estimate itself reads healthy (~1) for every collector-backed lane."""
    epochs = make_epochs(5)
    tb = make_runtime().run(iter(epochs))
    th = make_runtime(
        faults=FaultModel.create(n_blocks=400),
        hardening=Hardening.make(fallback={"hmu_oracle": "pebs"}),
    ).run(iter(epochs))
    for lane in ALL_POLICIES:
        for x, y in zip(tb.lane(lane), th.lane(lane)):
            dx, dy = x.to_dict(), y.to_dict()
            assert dx.pop("quality") == 1.0          # unhardened: constant
            q = dy.pop("quality")
            assert dx == dy, (lane, x.epoch)
            if LANE_COLLECTOR[lane] is None:
                assert q == 1.0                      # hint lanes never degrade
            else:
                assert q > 0.9, (lane, q)


def test_neutral_model_bit_identical_fleet():
    fl = FleetScenario([
        TenantSpec(DLRMScenario(spec=SMALL_SPEC, n_epochs=3,
                                batches_per_epoch=2)),
        TenantSpec(KVCacheScenario(batch=2, n_epochs=3, batches_per_epoch=2,
                                   accesses_per_batch=1024)),
    ])
    base = run_fleet(fl, hints=False, sync_every=2)
    neut = run_fleet(fl, hints=False, sync_every=2,
                     faults={"dlrm": {"pebs_drop_p": 0.0}})
    assert base["trajectory"] == neut["trajectory"]
    assert base["summary"] == neut["summary"]
    assert base["tenants"] == neut["tenants"]


@pytest.mark.slow
def test_neutral_model_bit_identical_sharded():
    """ISSUE acceptance: neutrality is sharding-transparent — an 8-device
    mesh run with a default FaultModel equals the meshless no-model run."""
    r = run_py("""
        import dataclasses, json
        from repro.dlrm import datagen
        from repro.faults import FaultModel
        import jax
        from repro.launch.mesh import make_telemetry_mesh
        from repro.scenarios import DLRMScenario, run_scenario

        spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)
        sc = DLRMScenario(spec=spec, n_epochs=4, batches_per_epoch=2,
                          shift_at=2)
        ref = run_scenario(sc, hints=True)
        mesh = make_telemetry_mesh(8)
        with jax.set_mesh(mesh):
            shd = run_scenario(
                DLRMScenario(spec=spec, n_epochs=4, batches_per_epoch=2,
                             shift_at=2),
                hints=True, mesh=mesh, sync_every=2,
                faults=FaultModel.create(n_blocks=sc.n_blocks))
        assert json.dumps(ref["trajectory"], sort_keys=True) == \\
            json.dumps(shd["trajectory"], sort_keys=True)
        print("OK")
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ==================================================  dispatch / trace gates
def test_faulty_epoch_still_two_dispatches_one_trace():
    """ISSUE acceptance: the whole fault model rides inside the two existing
    dispatches — injection adds zero dispatches and zero retraces."""
    rt = make_runtime(
        faults=FaultModel.create(pebs_drop_p=0.5, reset_p=0.02,
                                 nb_stall_p=0.2, stale_epochs=2,
                                 hmu_counter_bits=10, seed=5, n_blocks=400),
        hardening=Hardening.make(fallback={"hmu_oracle": "pebs"},
                                 demote_hysteresis=3),
    )
    rt.step(make_epochs(1, seed=9)[0])           # warm the trace
    rt.flush()
    with rtmod.counting() as counts:
        rt.run(iter(make_epochs(8)))
        assert counts.dispatch == {"observe_all": 8, "epoch_step": 8,
                                   "reference": 0, "hint_refresh": 0,
                                   "record_sync": 8}
        assert counts.trace["epoch_step"] == 0


# ==================================================  per-fault degradation
def test_hmu_saturation_pins_counters_at_the_cap():
    fm = FaultModel.create(hmu_counter_bits=3, n_blocks=8)   # cap = 7
    bundle = tel.bundle_init(8, faults=fm)
    batches = jnp.zeros((1, 100), jnp.int32)                 # 100 hits, block 0
    bundle = tel.observe_all(bundle, batches)
    counts = np.asarray(bundle.hmu.counts)
    assert counts[0] == 7                                    # clamped, not wrapped
    assert int(tel.hmu_saturated(bundle.hmu,
                                 bundle.faults.hmu_counter_max)) == 1
    assert int(np.asarray(bundle.true_counts)[0]) == 100     # truth unaffected


def test_hmu_saturating_observe_without_a_model_clamps_at_int32():
    """Satellite: the bare collector saturates at int32 max instead of
    wrapping negative (poisoning top-k)."""
    st = tel.hmu_init(4)
    st = dataclasses.replace(
        st, counts=st.counts.at[0].set(np.iinfo(np.int32).max - 2))
    st = tel.hmu_observe(st, jnp.zeros((10,), jnp.int32))
    assert int(np.asarray(st.counts)[0]) == np.iinfo(np.int32).max
    assert int(tel.hmu_saturated(st)) == 1


def test_pebs_drops_starve_the_sampled_histogram():
    fm = FaultModel.create(pebs_drop_p=1.0, n_blocks=16, seed=2)
    bundle = tel.bundle_init(16, pebs_period=3, faults=fm)
    bundle = tel.observe_all(
        bundle, jnp.arange(48, dtype=jnp.int32).reshape(2, 24) % 16)
    assert int(np.asarray(bundle.pebs.sampled).sum()) == 0
    assert float(bundle.pebs.host_events) == 0.0      # dropped != serviced
    assert float(bundle.faults.pebs_dropped) == 16.0  # 48 accesses / period 3


def test_nb_stall_freezes_scanner_and_counts_stalls():
    fm = FaultModel.create(nb_stall_p=1.0, n_blocks=10, seed=4)
    bundle = tel.bundle_init(10, nb_scan_rate=4, faults=fm)
    for _ in range(3):
        bundle = tel.observe_all(bundle, jnp.zeros((2, 5), jnp.int32))
    assert int(bundle.nb.scan_ptr) == 0               # cursor never moved
    assert int(np.asarray(bundle.nb.faults).sum()) == 0   # nothing unmapped
    assert int(bundle.faults.nb_stalls) == 6          # every batch tick stalled


def test_collector_reset_wipes_counts_and_ticks_the_event_counter():
    fm = FaultModel.create(reset_p=np.array([1.0, 0.0, 0.0], np.float32),
                           n_blocks=8, seed=0)
    bundle = tel.bundle_init(8, faults=fm)
    bundle = tel.observe_all(bundle, jnp.zeros((2, 50), jnp.int32))
    bundle = tel.observe_all(bundle, jnp.zeros((2, 50), jnp.int32))
    # each epoch resets HMU counts before observing: only one epoch survives
    assert int(np.asarray(bundle.hmu.counts)[0]) == 100
    assert int(np.asarray(bundle.faults.resets)[COLLECTORS.index("hmu")]) == 2
    assert int(np.asarray(bundle.true_counts)[0]) == 200


def test_staleness_serves_estimates_d_epochs_late():
    """One hot block per epoch, moving: with stale_epochs=d the placement
    must track the block that was hot d epochs ago, and the served-estimate
    coverage collapses while the accounting (d_true) stays current."""
    n, d = 64, 2
    epochs = [np.full((1, 512), e, np.int32) for e in range(8)]
    rt = EpochRuntime(n, 1, fused=True, policies=("hmu_oracle",),
                      faults=FaultModel.create(stale_epochs=d, n_blocks=n))
    traj = rt.run(iter(epochs))
    assert int(np.asarray(rt.lanes["hmu_oracle"].slot_to_block)[0]) == 7 - d
    assert traj.lane("hmu_oracle")[-1].coverage == 0.0   # d epochs behind
    fresh = EpochRuntime(n, 1, fused=True, policies=("hmu_oracle",),
                         faults=FaultModel.create(n_blocks=n))
    fresh.run(iter(epochs))
    # without staleness the same stream tracks the *current* hot block
    assert int(np.asarray(fresh.lanes["hmu_oracle"].slot_to_block)[0]) == 7


# ============================================  hardening: fallback + hysteresis
def test_fallback_holds_coverage_where_naive_lane_collapses():
    """ISSUE headline: HMU resetting every epoch guts the oracle lane's
    deltas; the hardened run watches quality crater and swaps the lane's
    input to PEBS, holding coverage the naive lane loses."""
    eps = zipf_epochs(12)
    fm = lambda: FaultModel.create(
        reset_p=np.array([1.0, 0.0, 0.0], np.float32), seed=11, n_blocks=400)
    naive = EpochRuntime(400, 40, fused=True, policies=("hmu_oracle",),
                         pebs_period=101, faults=fm())
    tn = naive.run(iter(eps))
    hard = EpochRuntime(400, 40, fused=True, policies=("hmu_oracle",),
                        pebs_period=101, faults=fm(),
                        hardening=Hardening.make(
                            fallback={"hmu_oracle": "pebs"}))
    th = hard.run(iter(eps))
    cn = np.mean([r.coverage for r in tn.lane("hmu_oracle")[3:]])
    ch = np.mean([r.coverage for r in th.lane("hmu_oracle")[3:]])
    assert ch > cn + 0.05, (cn, ch)
    # the record stream shows the detection: smoothed quality craters
    assert th.lane("hmu_oracle")[-1].quality < 0.2
    assert tn.lane("hmu_oracle")[-1].quality == 1.0      # naive: no estimator


def test_hysteresis_one_matches_unhardened_demotions():
    """H=1 is the seed behaviour: the hardened reactive lane demotes on the
    first cold epoch exactly like the unhardened run (quality aside)."""
    epochs = make_epochs(6, seed=7)
    tb = make_runtime(policies=("reactive_watermark",)).run(iter(epochs))
    th = make_runtime(policies=("reactive_watermark",),
                      faults=FaultModel.create(n_blocks=400),
                      hardening=Hardening.make(demote_hysteresis=1),
                      ).run(iter(epochs))
    for x, y in zip(tb.lane("reactive_watermark"),
                    th.lane("reactive_watermark")):
        dx, dy = x.to_dict(), y.to_dict()
        dx.pop("quality"), dy.pop("quality")
        assert dx == dy


def test_hysteresis_defers_demotion_until_h_cold_epochs():
    """A block hot once then silent: H=1 demotes it after its first cold
    epoch, H=4 keeps it resident through 3 cold epochs."""
    n, k = 32, 4
    hot = np.full((1, 256), 5, np.int32)
    cold = np.full((1, 256), 9, np.int32)            # keeps traffic flowing
    epochs = [hot, cold, cold, cold]
    def demotions(h):
        rt = EpochRuntime(n, k, fused=True, policies=("reactive_watermark",),
                          faults=FaultModel.create(n_blocks=n),
                          hardening=Hardening.make(demote_hysteresis=h))
        rt.run(iter(e.copy() for e in epochs))
        return [r.demoted for r in rt.records["reactive_watermark"]]
    d1, d4 = demotions(1), demotions(4)
    assert sum(d1[1:]) > 0                           # demoted while cold
    assert sum(d4[1:3]) == 0                         # survived 2 cold epochs
    assert sum(d4) <= sum(d1)


# ==========================================================  fleet integration
def test_fleet_per_tenant_profile_degrades_only_that_tenant():
    """Tenant-segmented drop_p: the faulty tenant's PEBS-backed accuracy
    falls while the healthy tenant keeps its signal (the collectors are
    shared; the per-block drop array is not)."""
    def fleet():
        return FleetScenario([
            TenantSpec(DLRMScenario(spec=SMALL_SPEC, n_epochs=4,
                                    batches_per_epoch=2)),
            TenantSpec(KVCacheScenario(batch=2, n_epochs=4,
                                       batches_per_epoch=2,
                                       accesses_per_batch=1024)),
        ], pebs_period=11)
    fl = fleet()
    fm = fl.build_faults({"dlrm": {"pebs_drop_p": 1.0}}, seed=1)
    drop = np.asarray(fm.pebs_drop_p)
    dl = fl.tenant_index("dlrm")
    assert (drop[fl.offsets[dl]:fl.offsets[dl + 1]] == 1.0).all()
    assert (drop[fl.offsets[dl + 1]:] == 0.0).all()
    out = run_fleet(fleet(), policies=("hinted",), hints=True, faults=fm)
    assert set(out["tenants"]) == {"dlrm", "kv_cache"}
    assert "hinted" in out["tenants"]["dlrm"]["lanes"]
    with pytest.raises(KeyError, match="unknown tenant"):
        fl.build_faults({"nope": {}})


def test_fleet_faulty_run_keeps_two_dispatches():
    fl = FleetScenario([
        TenantSpec(DLRMScenario(spec=SMALL_SPEC, n_epochs=3,
                                batches_per_epoch=2)),
        TenantSpec(KVCacheScenario(batch=2, n_epochs=3, batches_per_epoch=2,
                                   accesses_per_batch=1024)),
    ])
    with rtmod.counting() as c:
        run_fleet(fl, hints=False, sync_every=3,
                  faults={"dlrm": {"pebs_drop_p": 0.7}},
                  hardening=Hardening.make(fallback={"hinted": "hmu"}))
        assert c.dispatch["observe_all"] == 3
        assert c.dispatch["epoch_step"] == 3
        assert c.dispatch["reference"] == 0
        assert c.dispatch["record_sync"] == 1
