"""The one-tenant deployment: one block space under the six policy lanes.

A configuration names its harness module with ``"harness": "<name>"``
(``bench/harness/<name>.py``); one without the key runs here.  The module
gives the five functions the harness calls, and ``run_cell.py`` knows
nothing else of the deployment:

* ``make_pool(config, traffic, seed)`` -- the seeded epochs, a pool with
  ``epoch(i)`` and ``upcoming(i, depth)``;
* ``build_runtime(config, traffic, pool)`` -- the ``EpochRuntime`` on the
  default path (fused, the platform's kernels, hints when the mix has
  them);
* ``program_rows(rt, config)`` -- what the program produced, as
  ``(rows, placements)``: ``rows`` is ``{stream: [row dict]}``, each row
  with an ``epoch`` key, and ``placements`` is ``{name: ndarray}``;
* ``reference(config, traffic, pool, n_epochs, control=False)`` -- the
  same from the plain reference;
* ``shrink(config, traffic, n_blocks)`` -- the deployment and mix at
  ``n_blocks`` blocks, for CPU rehearsals and tests.

Here the streams are the lanes, each row an ``EpochRecord``'s fields
(``reference.RECORD_FIELDS``), and the placements each lane's final
``slot_to_block``.
"""
from __future__ import annotations

import json

import numpy as np

from reference import RECORD_FIELDS, run_reference
from traffic import make_pool

__all__ = ["make_pool", "build_runtime", "program_rows", "reference",
           "shrink"]


class _Scenario:
    """The deployment as ``EpochRuntime.for_scenario`` reads it."""

    def __init__(self, config: dict, traffic: dict, pool):
        from repro.core.costmodel import MemSystem, TierSpec
        ms = config["memory_system"]
        self.n_blocks = int(config["n_blocks"])
        self.k_hot = int(config["k_hot"])
        self.system = MemSystem(fast=TierSpec(**ms["fast"]),
                                slow=TierSpec(**ms["slow"]), mlp=ms["mlp"])
        self.bytes_per_access = float(config["bytes_per_access"])
        self.block_bytes = float(config["block_bytes"])
        self.pebs_period = int(config["pebs_period"])
        self.nb_scan_rate = max(self.n_blocks // (
            int(traffic["batches_per_epoch"])
            * int(config["nb_scan_passes_per_epoch"])), 1)
        self._config, self._pool = config, pool

    def hint_layout(self):
        from repro.hints import HintLayout
        return HintLayout(
            self.n_blocks, rank_to_page=self._pool.rank_to_page,
            alpha=float(self._config["popularity"].get("alpha", 0.0)),
            rows_per_page=int(self._config.get("rows_per_page", 1)))


def build_runtime(config: dict, traffic: dict, pool):
    from repro.core.runtime import EpochRuntime
    from repro.hints import (HintPipeline, LookaheadWindow,
                             PhaseChangeDetector, StaticTableHints)
    scn = _Scenario(config, traffic, pool)
    pipeline = None
    if traffic.get("hints"):
        h, t = config["hints"], traffic["hints"]
        n = scn.n_blocks
        det = h["detector"]
        pipeline = HintPipeline(
            n,
            static=StaticTableHints(
                scn.hint_layout(),
                clip_rank=max(n // int(h["static_clip_divisor"]), 1)),
            lookahead=LookaheadWindow(n, depth=int(t["depth"]),
                                      decay=float(h["lookahead_decay"])),
            detector=(PhaseChangeDetector(n, alpha=det["alpha"],
                                          threshold=det["threshold"],
                                          penalty=det["penalty"])
                      if t.get("detector", True) else None))
    rt = config["runtime"]
    return EpochRuntime.for_scenario(
        scn, policies=tuple(config["lanes"]), hints=pipeline,
        prefetch_overlap=float(rt["prefetch_overlap"]),
        sync_every=int(traffic["sync_every"]),
        ewma_alpha=float(rt["ewma_alpha"]),
        hint_weight=float(rt["hint_weight"]),
        hmu_log_capacity=int(rt["hmu_log_capacity"]))


def program_rows(rt, config: dict):
    rows = {lane: [{f: d[f] for f in RECORD_FIELDS}
                   for d in (r.to_dict() for r in rt.records[lane])]
            for lane in config["lanes"]}
    lanes = rt.lanes
    return rows, {lane: np.array(lanes[lane].slot_to_block)
                  for lane in config["lanes"]}


def reference(config: dict, traffic: dict, pool, n_epochs: int,
              control: bool = False):
    records, s2b = run_reference(config, traffic, pool, n_epochs,
                                 control=control)
    return records, {lane: np.asarray(s2b[i])
                     for i, lane in enumerate(config["lanes"])}


def shrink(config: dict, traffic: dict, n_blocks: int):
    """Every block count and batch scales by one factor."""
    f = n_blocks / config["n_blocks"]
    config = json.loads(json.dumps(config))
    traffic = dict(traffic)
    config["n_blocks"] = n_blocks
    config["k_hot"] = max(int(config["k_hot"] * f), 1)
    traffic["batch"] = max(int(traffic["batch"] * f), 1)
    for r in config["popularity"].get("regions", []):
        r["start"], r["end"] = int(r["start"] * f), int(r["end"] * f)
    return config, traffic
