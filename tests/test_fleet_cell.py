"""The benchmark's two-tenant fleet (``bench/configs/fleet_paper.json``,
run by ``bench/harness/fleet.py``) against its plain reference
(``bench/harness/fleet_reference.py``), at a thousandth of the deployment
on the CPU: every lane's records, every ``tenant/<tenant>/<lane>`` row and
every placement equal, exactly, and a wrong program is caught."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run_cell  # noqa: E402

CELL = "fleet_paper.weighted"
N_BLOCKS = 7_621                # 7,621,440 / 1,000
N_EPOCHS = 10                   # both DLRM phases and a rotation back
SEED = 2_200_000_011


@pytest.fixture(scope="module")
def cell():
    spec = run_cell.load_cell(CELL, N_BLOCKS)
    harness, config, traffic = spec["harness"], spec["config"], spec["traffic"]
    pool = harness.make_pool(config, traffic, SEED)
    rt = harness.build_runtime(config, traffic, pool)
    for i in range(N_EPOCHS):
        rt.step(pool.epoch(i))
    return dict(spec=spec, pool=pool, rt=rt,
                program=harness.program_rows(rt, config),
                ref=harness.reference(config, traffic, pool, N_EPOCHS))


def _streams(config):
    lanes = config["lanes"]
    return set(lanes) | {f"tenant/{t['name']}/{lane}"
                         for t in config["tenants"] for lane in lanes}


def test_program_rows_equal_the_reference(cell):
    config = cell["spec"]["config"]
    (rows, places), (ref_rows, ref_places) = cell["program"], cell["ref"]
    assert set(rows) == set(ref_rows) == _streams(config)
    assert all(len(r) == N_EPOCHS for r in rows.values())
    assert set(places) == set(ref_places) == set(config["lanes"])
    assert run_cell.compare(cell["program"], cell["ref"], 0) == (0, 0, 0)


def test_tenants_are_served_within_their_caps(cell):
    """Both tenants reach the fast tier, and no lane admits more than a
    tenant's cap in any epoch."""
    config = cell["spec"]["config"]
    rows = cell["program"][0]
    for t in config["tenants"]:
        hmu = rows[f"tenant/{t['name']}/hmu_oracle"]
        assert max(r["coverage"] for r in hmu) > 0.5
        for lane in config["lanes"]:
            assert all(r["promoted"] <= t["cap"]
                       for r in rows[f"tenant/{t['name']}/{lane}"])


def test_control_is_not_correct(cell):
    spec = cell["spec"]
    ctl = spec["harness"].reference(spec["config"], spec["traffic"],
                                    cell["pool"], N_EPOCHS, control=True)
    bad_fields, _, failed = run_cell.compare(ctl, cell["ref"], 0)
    assert bad_fields > 0 and failed > 0


def test_altered_tenant_row_is_caught(cell):
    """One tenant's count altered where the runtime keeps it: the tenant's
    row at that epoch, and only it, mismatches."""
    spec, rt = cell["spec"], cell["rt"]
    raw = rt.tenant_records[5]["promoted"]
    lane = spec["config"]["lanes"].index("hinted")
    raw[lane, 1] += 1
    try:
        got = spec["harness"].program_rows(rt, spec["config"])
    finally:
        raw[lane, 1] -= 1
    bad_fields, bad_slots, failed = run_cell.compare(got, cell["ref"], 0)
    # promoted, migration_s and time_s of tenant/mmap/hinted at epoch 5
    assert (bad_fields, bad_slots, failed) == (3, 0, 1)


def test_stated_geometry_is_what_the_fleet_derives():
    """The full-size configuration states the numbers the program's
    FleetScenario derives and the reference restates."""
    from harness.fleet_reference import geometry, tenant_configs
    from traffic import Pool

    spec = run_cell.load_cell(CELL)
    harness, config, traffic = spec["harness"], spec["config"], spec["traffic"]
    g = geometry(config, traffic)
    assert g["n_blocks"] == config["n_blocks"] == 7_621_440
    assert g["offsets"] == (0, 5_000_000, 7_621_440)
    assert g["caps"] == tuple(t["cap"] for t in config["tenants"]) \
        == (486_587, 262_144)
    assert sum(g["caps"]) == config["k_hot"] == 748_731
    for key in ("pebs_period", "bytes_per_access", "block_bytes",
                "nb_scan_rate"):
        assert g[key] == config[key], key

    from repro.fleet import FleetScenario, TenantSpec
    fleet = FleetScenario(
        [TenantSpec(harness._tenant_scenario(
            t["name"], conf, traffic["tenants"][t["name"]], Pool([], None)),
            weight=float(t["weight"]), name=t["name"])
         for t, conf in zip(config["tenants"], tenant_configs(config))],
        k_hot=config["k_hot"], capacity=config["capacity"])
    assert fleet.tenancy.caps == g["caps"]
    assert fleet.tenancy.hot_k == g["hot_k"] == (486_587, 262_144)
    assert fleet.offsets == g["offsets"]
    for key in ("pebs_period", "bytes_per_access", "block_bytes",
                "nb_scan_rate"):
        assert getattr(fleet, key) == g[key], key
    assert np.isclose(fleet.bytes_per_access, 576.3242443217396, rtol=0)
