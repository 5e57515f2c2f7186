"""The multi-tenant fleet: tenants side by side in one block space, one
fast tier split among them by quotas (``repro.fleet``).

The configuration lists its tenants, each with its own configuration
(``bench/configs/<config>.json``, read unchanged but for the fleet's
``stream_pool_epochs``), its quota weight and the cap that weight gives;
the traffic file gives each tenant's own mix under ``tenants``.  The
module gives the five functions ``single.py`` describes:

* ``make_pool`` draws each tenant's seeded epochs with the one stream
  generator (``traffic.py``) and merges them by ``FleetScenario.epochs``'
  rule: offset into the global id space, concatenated, shuffled per epoch,
  cut into ``batches_per_epoch`` rows;
* ``build_runtime`` is ``EpochRuntime.for_scenario`` on that
  ``FleetScenario``, which hands the runtime its ``Tenancy``;
* the streams are the lanes, each row an ``EpochRecord``'s fields as in
  ``single``, and ``tenant/<tenant>/<lane>``, each row a
  ``fleet.accounting.TenantRecord``'s fields; the placements are each
  lane's final ``slot_to_block``;
* the reference is ``fleet_reference.py``.

The configuration states the numbers the fleet derives from its tenants
(``n_blocks``, the caps, the merged geometry); ``build_runtime`` refuses a
program whose fleet derives others.
"""
from __future__ import annotations

import json

import numpy as np

from harness.fleet_reference import (TENANT_FIELDS, geometry,
                                     run_reference, tenant_configs)
from reference import RECORD_FIELDS
from traffic import Pool, make_pool as make_tenant_pool

__all__ = ["make_pool", "build_runtime", "program_rows", "reference",
           "shrink"]


def _tenant_scenario(name: str, config: dict, traffic: dict, pool):
    """One tenant as ``FleetScenario`` reads an ``AccessScenario``."""
    from harness.single import _Scenario

    scn = _Scenario(config, traffic, pool)
    scn.name = name
    scn.n_epochs = len(pool.epochs)
    scn.batches_per_epoch = int(traffic["batches_per_epoch"])
    scn.accesses_per_batch = int(traffic["batch"])
    scn.shift_at = int(traffic.get("epochs_per_phase", scn.n_epochs))
    scn.epochs = lambda: iter(pool.epochs)
    return scn


class FleetPool(Pool):
    """The merged epochs, and the ``FleetScenario`` that merged them."""

    def __init__(self, fleet):
        super().__init__(list(fleet.epochs()), None)
        self.fleet = fleet


def make_pool(config: dict, traffic: dict, seed: int) -> FleetPool:
    from repro.fleet import FleetScenario, TenantSpec

    specs = []
    for t, (entry, conf) in enumerate(zip(config["tenants"],
                                          tenant_configs(config))):
        conf = dict(conf, stream_pool_epochs=config["stream_pool_epochs"])
        mix = traffic["tenants"][entry["name"]]
        t_seed = int(np.random.SeedSequence([int(seed), t]).generate_state(
            1, np.uint64)[0])
        pool = make_tenant_pool(conf, mix, t_seed)
        specs.append(TenantSpec(_tenant_scenario(entry["name"], conf, mix,
                                                 pool),
                                weight=float(entry["weight"]),
                                name=entry["name"]))
    fleet = FleetScenario(specs, k_hot=int(config["k_hot"]),
                          capacity=config["capacity"], seed=int(seed))
    pool = FleetPool(fleet)
    shape = (int(traffic["batches_per_epoch"]), int(traffic["batch"]))
    if pool.epoch(0).shape != shape:
        raise ValueError(f"the traffic states epochs of {shape}; the fleet "
                         f"merges them into {pool.epoch(0).shape}")
    return pool


def build_runtime(config: dict, traffic: dict, pool: FleetPool):
    from repro.core.runtime import EpochRuntime

    fleet = pool.fleet
    derived = dict(n_blocks=fleet.n_blocks, k_hot=fleet.k_hot,
                   pebs_period=fleet.pebs_period,
                   bytes_per_access=fleet.bytes_per_access,
                   block_bytes=fleet.block_bytes,
                   nb_scan_rate=fleet.nb_scan_rate,
                   caps=list(fleet.tenancy.caps))
    stated = {key: config[key] for key in derived if key != "caps"}
    stated["caps"] = [t["cap"] for t in config["tenants"]]
    if derived != stated:
        raise ValueError(f"the configuration states {stated}; the fleet "
                         f"derives {derived}")
    rt = config["runtime"]
    run = EpochRuntime.for_scenario(
        fleet, policies=tuple(config["lanes"]), hints=None,
        prefetch_overlap=float(rt["prefetch_overlap"]),
        sync_every=int(traffic["sync_every"]),
        ewma_alpha=float(rt["ewma_alpha"]),
        hint_weight=float(rt["hint_weight"]),
        hmu_log_capacity=int(rt["hmu_log_capacity"]))
    run.fleet = fleet              # program_rows slices the rows by it
    return run


def program_rows(rt, config: dict):
    from repro.fleet import tenant_trajectories

    lanes = config["lanes"]
    rows = {lane: [{f: d[f] for f in RECORD_FIELDS}
                   for d in (r.to_dict() for r in rt.records[lane])]
            for lane in lanes}
    for tenant, per_lane in tenant_trajectories(rt, rt.fleet).items():
        for lane in lanes:
            rows[f"tenant/{tenant}/{lane}"] = [
                {f: d[f] for f in TENANT_FIELDS}
                for d in (r.to_dict() for r in per_lane[lane])]
    placed = rt.lanes
    return rows, {lane: np.array(placed[lane].slot_to_block)
                  for lane in lanes}


def reference(config: dict, traffic: dict, pool, n_epochs: int,
              control: bool = False):
    records, tenants, s2b = run_reference(config, traffic, pool, n_epochs,
                                          control=control)
    rows = dict(records)
    for tenant, per_lane in tenants.items():
        for lane, recs in per_lane.items():
            rows[f"tenant/{tenant}/{lane}"] = recs
    return rows, {lane: np.asarray(s2b[i])
                  for i, lane in enumerate(config["lanes"])}


def shrink(config: dict, traffic: dict, n_blocks: int):
    """Both tenants scale by one factor, as ``single.shrink`` scales a
    deployment; weights and caps become the shrunk hot sets, and the
    stated geometry is restated for the shrunk fleet."""
    from harness.single import shrink as shrink_one

    f = n_blocks / config["n_blocks"]
    config = json.loads(json.dumps(config))
    traffic = json.loads(json.dumps(traffic))
    for entry, conf in zip(config["tenants"], tenant_configs(config)):
        mix = traffic["tenants"][entry["name"]]
        conf, mix = shrink_one(conf, mix, max(int(conf["n_blocks"] * f), 1))
        entry["config"] = conf
        entry["weight"] = entry["cap"] = conf["k_hot"]
        traffic["tenants"][entry["name"]] = mix
    config["k_hot"] = sum(e["cap"] for e in config["tenants"])
    traffic["batch"] = sum(
        int(m["batches_per_epoch"]) * int(m["batch"])
        for m in traffic["tenants"].values()) // int(
            traffic["batches_per_epoch"])
    g = geometry(config, traffic)
    for key in ("n_blocks", "pebs_period", "bytes_per_access", "block_bytes",
                "nb_scan_rate"):
        config[key] = g[key]
    return config, traffic
