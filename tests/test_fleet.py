"""Fleet-layer tests: many workloads co-located in one runtime.

The tentpole invariants must survive multi-tenancy — fused-vs-reference
bit-identity and exactly 2 jit dispatches/epoch for a >=3-tenant mix with
hints AND quotas — plus the fleet's own plumbing: global<->local id
round-trips, per-tenant accounting conservation against the global record,
deterministic stream interleaving, quota isolation, and the mmap-bench
scenario satellite."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import runtime as rtmod
from repro.core.runtime import ALL_POLICIES, EpochRuntime, Tenancy
from repro.dlrm import datagen
from repro.fleet import (FleetScenario, TenantSpec, fair_quotas, make_tenancy,
                         run_fleet, tenant_trajectories)
from repro.scenarios import (DLRMScenario, MmapBenchScenario, build_hints,
                             run_scenario)
from repro.workloads import mmap_bench

REPO = Path(__file__).resolve().parent.parent
SUBPROC_ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"),
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")

SMALL_SPEC = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)


def small_dlrm(**kw):
    kw.setdefault("spec", SMALL_SPEC)
    kw.setdefault("n_epochs", 4)
    kw.setdefault("batches_per_epoch", 2)
    kw.setdefault("shift_at", 2)
    return DLRMScenario(**kw)


def small_scanner(**kw):
    kw.setdefault("n_epochs", 4)
    kw.setdefault("batches_per_epoch", 2)
    kw.setdefault("accesses_per_batch", 8_000)
    return MmapBenchScenario(**kw)


def small_moe(**kw):
    from repro.scenarios import MoEExpertScenario

    kw.setdefault("n_epochs", 4)
    kw.setdefault("batches_per_epoch", 2)
    kw.setdefault("shift_at", 2)
    kw.setdefault("batch", 2)
    return MoEExpertScenario(**kw)


def small_fleet(capacity="weighted", k_hot=300, **kw):
    return FleetScenario(
        [TenantSpec(small_dlrm(), weight=10.0, name="dlrm"),
         TenantSpec(small_scanner(), weight=1.0, name="scanner"),
         TenantSpec(small_moe(), weight=1.0, name="moe")],
        k_hot=k_hot, capacity=capacity, **kw)


# ----------------------------------------------------------- mmap satellite
def test_mmap_scenario_protocol_and_stream():
    sc = small_scanner()
    assert sc.n_blocks == sc.spec.n_pages
    assert sc.k_hot == sc.spec.k_hot
    eps1, eps2 = list(sc.epochs()), list(sc.epochs())
    assert len(eps1) == sc.n_epochs
    for a, b in zip(eps1, eps2):
        np.testing.assert_array_equal(a, b)          # deterministic per call
    for ep in eps1:
        assert ep.shape == (sc.batches_per_epoch, sc.accesses_per_batch)
        assert 0 <= ep.min() and ep.max() < sc.n_blocks
    # the 90/10 region split: hot pages dominate the stream
    hist = np.bincount(np.concatenate([e.ravel() for e in eps1]),
                       minlength=sc.n_blocks)
    hot_share = hist[: sc.spec.k_hot].sum() / hist.sum()
    assert 0.85 < hot_share < 0.95


def test_mmap_scenario_static_hints_mark_the_declared_arena():
    sc = small_scanner()
    layout = sc.hint_layout()
    assert layout.rank_to_page is not None
    pipe = build_hints(sc, clip_rank=sc.spec.k_hot)
    rank = pipe._static_rank
    assert (rank[: sc.spec.k_hot] == 1.0).all()      # flat within-arena prior
    assert (rank[sc.spec.k_hot:] == 0.0).all()


def test_mmap_scenario_runs_the_online_loop():
    """§III.A on the six-lane loop: the oracle lane converges onto the hot
    region, and both runtime invariants hold (bit-identity, 2 dispatches)."""
    sc = small_scanner()
    eps = list(sc.epochs())
    with rtmod.counting() as counts:
        fused = run_scenario(sc, hints=True, epochs=iter(eps))
        assert counts.dispatch["observe_all"] == sc.n_epochs
        assert counts.dispatch["epoch_step"] == sc.n_epochs
        assert counts.dispatch["reference"] == 0
    reference = run_scenario(sc, hints=True, fused=False, epochs=iter(eps))
    assert fused["trajectory"] == reference["trajectory"]
    assert fused["summary"]["hmu_oracle"]["final_coverage"] > 0.9


# ------------------------------------------------------------- id plumbing
@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=400), min_size=2,
                max_size=5),
       st.lists(st.integers(min_value=0, max_value=1 << 30), min_size=1,
                max_size=32))
def test_tenant_id_space_round_trip(sizes, raw_ids):
    """Property: global->local->global is the identity on every valid global
    id, the recovered tenant matches the owning range, and out-of-range ids
    raise."""
    scenarios = [small_scanner(
        spec=mmap_bench.MmapBenchSpec(total_bytes=s * 4096,
                                      hot_bytes=max(s // 2, 1) * 4096))
        for s in sizes]
    fleet = FleetScenario([TenantSpec(sc, name=f"t{i}")
                           for i, sc in enumerate(scenarios)])
    ids = np.asarray(raw_ids) % fleet.n_blocks
    tenant, local = fleet.to_local(ids)
    for g, t, l in zip(ids, tenant, local):
        assert fleet.offsets[t] <= g < fleet.offsets[t + 1]
        assert fleet.to_global(int(t), int(l))[()] == g
    with pytest.raises(ValueError):
        fleet.to_local(np.array([fleet.n_blocks]))
    with pytest.raises(ValueError):
        fleet.to_global(0, np.array([scenarios[0].n_blocks]))


def test_interleaver_is_deterministic_and_conserves_tenant_traffic():
    fleet = small_fleet()
    eps1 = [e.copy() for e in fleet.epochs()]
    eps2 = list(fleet.epochs())
    assert len(eps1) == fleet.n_epochs
    for a, b in zip(eps1, eps2):
        np.testing.assert_array_equal(a, b)
    # per-epoch per-tenant access counts survive the shuffle (up to the
    # deterministic sub-row tail drop)
    streams = [list(t.scenario.epochs()) for t in fleet.tenants]
    for e, ep in enumerate(eps1):
        assert ep.shape[0] == fleet.batches_per_epoch
        tenant, _ = fleet.to_local(ep.ravel())
        got = np.bincount(tenant, minlength=len(fleet.tenants))
        want = np.array([streams[i][e].size
                         for i in range(len(fleet.tenants))])
        dropped = want.sum() - got.sum()
        assert 0 <= dropped < fleet.batches_per_epoch
        assert (np.abs(got - want) <= dropped).all()


def test_fleet_rejects_bad_configs():
    with pytest.raises(ValueError, match="two tenants"):
        FleetScenario([TenantSpec(small_scanner())])
    with pytest.raises(ValueError, match="unique"):
        FleetScenario([TenantSpec(small_scanner()),
                       TenantSpec(small_scanner())])
    with pytest.raises(ValueError, match="min_quota"):
        FleetScenario([TenantSpec(small_scanner(), name="a"),
                       TenantSpec(small_scanner(seed=1), name="b")],
                      capacity="weighted", k_hot=1)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec(small_scanner(), weight=0.0)


# ---------------------------------------------------------------- capacity
def test_fair_quotas_exact_sum_proportional_and_floored():
    q = fair_quotas([3.0, 1.0, 4.0], 800)
    assert q.sum() == 800
    np.testing.assert_allclose(q / 800, np.array([3, 1, 4]) / 8, atol=1 / 800)
    # min-quota floor: a tiny tenant still gets a slot
    q = fair_quotas([1000.0, 1.0, 1.0], 10)
    assert q.sum() == 10 and (q >= 1).all()
    with pytest.raises(ValueError):
        fair_quotas([1.0, -1.0], 10)
    with pytest.raises(ValueError):
        fair_quotas([1.0, 1.0, 1.0], 2)              # cannot floor 3 tenants


def test_make_tenancy_policies():
    offs, hot = (0, 100, 300), (10, 50)
    assert make_tenancy(offs, hot, 60, "shared").caps is None
    part = make_tenancy(offs, hot, 60, "partition")
    assert part.caps == (10, 50)                     # demand-proportional
    wgt = make_tenancy(offs, hot, 60, "weighted", weights=[1.0, 1.0])
    assert wgt.caps == (30, 30)
    with pytest.raises(ValueError, match="weights"):
        make_tenancy(offs, hot, 60, "weighted")
    with pytest.raises(ValueError, match="capacity"):
        make_tenancy(offs, hot, 60, "fair-ish")


def test_tenancy_validation():
    with pytest.raises(ValueError, match="offsets"):
        EpochRuntime(100, 10, policies=("hmu_oracle",),
                     tenancy=Tenancy(offsets=(0, 50, 90), hot_k=(5, 5)))
    with pytest.raises(ValueError, match="hot_k"):
        EpochRuntime(100, 10, policies=("hmu_oracle",),
                     tenancy=Tenancy(offsets=(0, 50, 100), hot_k=(5, 60)))
    with pytest.raises(ValueError, match="caps"):
        EpochRuntime(100, 10, policies=("hmu_oracle",),
                     tenancy=Tenancy(offsets=(0, 50, 100), hot_k=(5, 5),
                                     caps=(8, 8)))    # sum > k_hot


# ------------------------------------------- tentpole: both invariants
@pytest.mark.parametrize("capacity", ["shared", "weighted"])
def test_fleet_fused_bit_identical_to_reference(capacity):
    """ISSUE acceptance: a 3-tenant mix (DLRM + scanner + MoE) with hints
    AND quotas is fused-vs-reference bit-identical — every EpochRecord field
    of every lane and epoch, every per-tenant raw counter row, and the
    derived tenant summaries."""
    fleet = small_fleet(capacity=capacity)
    eps = [e.copy() for e in fleet.epochs()]
    fused = run_fleet(fleet, hints=True, epochs=iter(eps))
    reference = run_fleet(fleet, hints=True, fused=False, epochs=iter(eps))
    assert set(fused["trajectory"]["lanes"]) == set(ALL_POLICIES)
    assert fused["trajectory"] == reference["trajectory"]
    assert fused["summary"] == reference["summary"]
    assert fused["tenants"] == reference["tenants"]


def test_fleet_epoch_is_two_dispatches():
    """ISSUE acceptance: a quota-enforcing, hint-enabled fleet epoch is
    exactly observe_all + epoch_step — the segment-capped select and the
    per-tenant reductions ride inside the one fused dispatch."""
    fleet = small_fleet()
    eps = [e.copy() for e in fleet.epochs()]        # data-gen outside counter
    with rtmod.counting() as counts:
        run_fleet(fleet, hints=True, epochs=iter(eps))
        assert counts.dispatch["observe_all"] == fleet.n_epochs
        assert counts.dispatch["epoch_step"] == fleet.n_epochs
        assert counts.dispatch["reference"] == 0
        assert counts.trace["epoch_step"] <= 1       # one trace, reused


def test_run_scenario_generic_path_inherits_tenancy():
    """The fleet is an AccessScenario: the plain run_scenario packaging
    installs its Tenancy through EpochRuntime.for_scenario (quotas active,
    composed pipeline attached)."""
    fleet = small_fleet()
    rt = EpochRuntime.for_scenario(fleet, policies=("hmu_oracle",))
    assert rt.tenancy is fleet.tenancy
    assert rt.tenancy.caps is not None
    out = run_scenario(fleet, policies=("hmu_oracle",), hints=True)
    assert out["trajectory"]["scenario"] == "fleet"


# --------------------------------------------------------- accounting
@pytest.mark.parametrize("offsets", [(0, 7), (0, 3, 10), (0, 500, 501, 2048)])
@pytest.mark.parametrize("kind", ["mask", "count"])
def test_per_tenant_sum_equals_the_segment_sum(offsets, kind):
    """The static-range sums give what a segment sum over the per-block
    tenant ids gives, on (lanes, n) masks and counts."""
    import jax
    rng = np.random.default_rng(len(offsets))
    n = offsets[-1]
    x = (rng.random((6, n)) < 0.3 if kind == "mask"
         else rng.integers(0, 1 << 20, (6, n), dtype=np.int32))
    tenant_of = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))
    want = jax.vmap(lambda row: jax.ops.segment_sum(
        row, tenant_of, num_segments=len(offsets) - 1))(x.astype(np.int32))
    got = rtmod._per_tenant_sum(x, offsets)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_per_tenant_accounting_conserves_the_global_record():
    """ISSUE acceptance: tenant numerators sum to the global record — every
    conservable column (n_fast / n_slow / resident / promoted / demoted)
    exactly, host tax to float tolerance via the access-share split."""
    fleet = small_fleet()
    eps = [e.copy() for e in fleet.epochs()]
    rt = EpochRuntime.for_scenario(fleet, policies=ALL_POLICIES,
                                   hints=fleet.build_pipeline())
    rt.run(iter(eps))
    trajs = tenant_trajectories(rt, fleet)
    lanes = list(rt.records)
    assert len(rt.tenant_records) == fleet.n_epochs
    for e in range(fleet.n_epochs):
        for lane in lanes:
            g = rt.records[lane][e]
            rows = [trajs[t.name][lane][e] for t in fleet.tenants]
            # the tenants' access counts partition the epoch's stream, and
            # re-pricing their sum with the fleet geometry recovers the
            # global record's access time exactly
            n_fast = sum(r.n_fast for r in rows)
            n_slow = sum(r.n_slow for r in rows)
            assert n_fast + n_slow == eps[e].size
            np.testing.assert_allclose(
                rt.system.access_time_s(n_fast, n_slow,
                                        fleet.bytes_per_access),
                g.access_s, rtol=1e-12)
            assert sum(r.resident for r in rows) == g.resident
            assert sum(r.promoted for r in rows) == g.promoted
            assert sum(r.demoted for r in rows) == g.demoted
            np.testing.assert_allclose(
                sum(r.host_tax_s for r in rows), g.host_tax_s, rtol=1e-9)
            for r in rows:
                assert 0.0 <= r.coverage <= 1.0
                assert 0.0 <= r.accuracy <= 1.0
                assert r.time_s >= r.access_s >= 0.0


def test_quota_caps_bound_admissions_and_converge_residency():
    """With sum(caps) <= k_hot every tenant's per-epoch admissions respect
    its cap (hard guarantee: each lane's select is segment-capped), and
    residency converges to the quota split up to the slack left by tenants
    whose cap exceeds their whole block space — quotas are work-conserving,
    so unused slots are reusable, but a tenant's own top-cap want is always
    admitted regardless."""
    fleet = small_fleet(capacity="weighted", k_hot=300)
    caps = np.asarray(fleet.tenancy.caps)
    sizes = np.asarray(fleet.tenancy.sizes)
    rt = EpochRuntime.for_scenario(fleet, policies=("hmu_oracle",))
    rt.run(fleet.epochs())
    for raw in rt.tenant_records:
        assert (raw["promoted"][0] <= caps).all()
    slack = int(np.maximum(caps - sizes, 0).sum())
    final = rt.tenant_records[-1]["resident"][0]
    assert final.sum() <= fleet.k_hot
    assert (final <= caps + slack).all()
    # the protected tenant holds its full quota under contention
    assert final[0] == caps[0]


# --------------------------------------------- interference vs isolation
def test_shared_pool_interference_vs_weighted_fair_isolation():
    """ISSUE acceptance (headline, small scale): a loud scanner under a
    shared pool craters the DLRM tenant's oracle-lane coverage; weighted-fair
    quotas sized to the DLRM solo hot set restore it to within a few points
    of the solo run."""
    spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=30_000)

    def tenants():
        return [
            TenantSpec(DLRMScenario(spec=spec, n_epochs=5,
                                    batches_per_epoch=2, shift_at=0),
                       weight=250.0, name="dlrm"),
            TenantSpec(small_scanner(
                n_epochs=5,
                spec=mmap_bench.MmapBenchSpec(total_bytes=640 * 4096,
                                              hot_bytes=512 * 4096),
                accesses_per_batch=60_000), weight=30.0, name="scanner"),
        ]

    solo = run_scenario(DLRMScenario(spec=spec, n_epochs=5,
                                     batches_per_epoch=2, shift_at=0),
                        policies=("hmu_oracle",), hints=False)
    solo_cov = solo["summary"]["hmu_oracle"]["final_coverage"]

    k_hot = 300                                     # < combined demand
    shared = run_fleet(FleetScenario(tenants(), k_hot=k_hot,
                                     capacity="shared"),
                       policies=("hmu_oracle",), hints=False)
    fair = run_fleet(FleetScenario(tenants(), k_hot=k_hot,
                                   capacity="weighted"),
                     policies=("hmu_oracle",), hints=False)
    cov_shared = shared["tenants"]["dlrm"]["lanes"]["hmu_oracle"][
        "final_coverage"]
    cov_fair = fair["tenants"]["dlrm"]["lanes"]["hmu_oracle"][
        "final_coverage"]
    assert fair["tenants"]["dlrm"]["cap"] >= 250    # quota covers solo k_hot
    assert solo_cov > 0.8
    assert cov_shared < solo_cov - 0.3              # noisy neighbour craters
    assert cov_fair > solo_cov - 0.05               # quotas isolate


# ----------------------------------------------------------- sharded parity
@pytest.mark.slow
def test_sharded_fleet_parity():
    """ISSUE acceptance: the quota-enforcing fleet epoch with all per-block
    state sharded over an 8-device mesh equals the single-device run
    exactly."""
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import dataclasses, json
        import numpy as np
        from repro.dlrm import datagen
        from repro.fleet import FleetScenario, TenantSpec, run_fleet
        import jax
        from repro.launch.mesh import make_telemetry_mesh
        from repro.scenarios import DLRMScenario, MmapBenchScenario

        spec = dataclasses.replace(datagen.SMALL, lookups_per_batch=8_000)
        def tenants():
            return [
                TenantSpec(DLRMScenario(spec=spec, n_epochs=3,
                                        batches_per_epoch=2, shift_at=2),
                           weight=10.0, name="dlrm"),
                TenantSpec(MmapBenchScenario(n_epochs=3, batches_per_epoch=2,
                                             accesses_per_batch=8_000),
                           weight=1.0, name="scanner"),
            ]
        kw = dict(k_hot=280, capacity="weighted")
        ref = run_fleet(FleetScenario(tenants(), **kw), hints=True)
        mesh = make_telemetry_mesh(8)
        with jax.set_mesh(mesh):
            shd = run_fleet(FleetScenario(tenants(), **kw), hints=True,
                            mesh=mesh)
        assert json.dumps(ref["trajectory"], sort_keys=True) == \\
            json.dumps(shd["trajectory"], sort_keys=True)
        assert json.dumps(ref["tenants"], sort_keys=True) == \\
            json.dumps(shd["tenants"], sort_keys=True)
        print("OK")
    """)], capture_output=True, text=True, env=SUBPROC_ENV, timeout=480,
        cwd=REPO)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
