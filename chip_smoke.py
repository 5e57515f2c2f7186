#!/usr/bin/env python3
"""Chip smoke test: the tiering epoch runtime on a TPU, through run_scenario.

    python3 chip_smoke.py               # phases A and B on one TPU chip
    python3 chip_smoke.py --four-chips  # phase C only, on a four-chip host

Phase A (DLRM SMALL, 5,000 pages): ``run_scenario(DLRMScenario(),
hints=True)`` on the default TPU path (compiled ``hist_select``, XLA
scatters) must be bit-identical to the same run with the kernels off and to
the ``fused=False`` reference oracle; a weighted-fair fleet run checks the
kernel's per-segment path the same way.

Phase B (paper-scale DLRM, ``datagen.PAPER``: 5,000,000 pages, 2.4 M
lookups per batch): all six lanes, hints on, ``sync_every=2``, four epochs
across a phase shift, at exactly two dispatches per epoch, with sanity
checks on the records.

Phase C (``--four-chips``): phase B's stream with all per-block state
sharded over a four-device mesh, compared bit for bit with the same stream
on one device.  With the option, nothing else runs.

Every phase prints its wall time, the host time of epoch 0's dispatches
(trace and compile included), dispatches per epoch, the implementation
each kernel site resolved to, and the device's ``peak_bytes_in_use``.  The
last line of standard output is one JSON object naming the device, printed
only when every phase passed.  A run that finds no TPU exits non-zero
without it.

``--rehearse`` runs the same phases on the CPU at reduced sizes with the
kernels in interpret mode, to check paths and control flow without a chip
(for phase C, give the CPU four devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  It prints no
result line.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
otherwise ``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.runtime import ALL_POLICIES, counting  # noqa: E402
from repro.dlrm import datagen  # noqa: E402
from repro.fleet import FleetScenario, TenantSpec, run_fleet  # noqa: E402
from repro.launch.mesh import make_telemetry_mesh  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.scenarios import (DLRMScenario, MmapBenchScenario,  # noqa: E402
                             run_scenario)
from repro.workloads import mmap_bench  # noqa: E402

TPU_KERNELS = {"select": "hist_select (compiled)", "scatter": "xla"}
CPU_KERNELS = {"select": "hist_select (interpret)",
               "scatter": "observe_scatter (interpret)"}
XLA_KERNELS = {"select": "xla", "scatter": "xla"}
# reduced paper-shaped spec for --rehearse: 20,000 pages, same skew and
# lookups-per-page ratio as datagen.PAPER
REHEARSAL_SPEC = dataclasses.replace(
    datagen.PAPER, n_params=20_480_000, lookups_per_batch=9_600)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what) -> None:
    """A phase's pass condition (not ``assert``: it must hold under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _same(a, b) -> bool:
    return json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _measured(label: str, n_epochs: int, run):
    """Run ``run()`` under the dispatch counters and the span tracer; print
    and return its result and the phase numbers."""
    with counting() as c, obs_trace.tracing() as tracer:
        t0 = time.perf_counter()
        out = run()
        wall = time.perf_counter() - t0
    epoch0 = sum(s.dur_s for s in tracer.spans if s.epoch == 0
                 and s.name in ("observe_all", "epoch_step"))
    disp = (c.dispatch["observe_all"] + c.dispatch["epoch_step"]) / n_epochs
    row = {"wall_s": wall, "epoch0_dispatch_s": epoch0,
           "dispatches_per_epoch": disp,
           "reference_dispatches": c.dispatch["reference"],
           "kernels": out["kernels"], "peak_bytes_in_use": _peak_bytes()}
    log(f"  {label}: {json.dumps(row)}")
    return out, row


def _fleet() -> FleetScenario:
    """DLRM beside a scanning noisy neighbour under weighted-fair quotas:
    two tenant segments in every capped selection."""
    dlrm = DLRMScenario(n_epochs=6, batches_per_epoch=2, shift_at=3)
    scanner = MmapBenchScenario(
        spec=mmap_bench.MmapBenchSpec(total_bytes=640 * 4096,
                                      hot_bytes=512 * 4096),
        n_epochs=6, batches_per_epoch=2, accesses_per_batch=60_000)
    return FleetScenario([TenantSpec(dlrm, weight=250.0, name="dlrm"),
                          TenantSpec(scanner, weight=60.0, name="scanner")],
                         k_hot=340, capacity="weighted")


def phase_a(kernels: dict, use_pallas) -> None:
    """Bit-identity at SMALL: default path vs kernels off vs reference."""
    log("phase A: DLRM SMALL, default path vs kernels off vs fused=False")
    sc = DLRMScenario()
    on, row = _measured("default", sc.n_epochs,
                        lambda: run_scenario(DLRMScenario(), hints=True,
                                             use_pallas=use_pallas))
    check(row["kernels"] == kernels, row["kernels"])
    check(row["dispatches_per_epoch"] == 2, row)
    off, row_off = _measured("kernels off", sc.n_epochs, lambda: run_scenario(
        DLRMScenario(), hints=True, use_pallas=False))
    check(row_off["kernels"] == XLA_KERNELS, row_off["kernels"])
    ref, _ = _measured("fused=False", sc.n_epochs, lambda: run_scenario(
        DLRMScenario(), hints=True, fused=False))
    check(_same(on["trajectory"], off["trajectory"]), "kernels on != off")
    check(_same(on["trajectory"], ref["trajectory"]), "fused != reference")
    log("  bit-identical: default == kernels off == fused=False")

    fleet = _fleet()
    log(f"phase A fleet: {fleet.n_blocks} blocks, weighted-fair caps "
        f"{fleet.tenancy.caps}")
    runs = {}
    for label, kw in (("default", dict(use_pallas=use_pallas)),
                      ("kernels off", dict(use_pallas=False)),
                      ("fused=False", dict(fused=False))):
        runs[label], row = _measured(
            f"fleet {label}", fleet.n_epochs,
            lambda kw=kw: run_fleet(_fleet(), hints=True, **kw))
    check(runs["default"]["kernels"] == kernels, runs["default"]["kernels"])
    for label in ("kernels off", "fused=False"):
        for part in ("trajectory", "tenants"):
            check(_same(runs["default"][part], runs[label][part]),
                  f"fleet {part}: default != {label}")
    log("  bit-identical: fleet default == kernels off == fused=False "
        "(trajectory and tenant rows)")


def _paper_scenario(spec) -> DLRMScenario:
    # k_hot as in the paper-scale runtime test: 1/64 of the pages
    return DLRMScenario(spec=spec, n_epochs=4, batches_per_epoch=4,
                        shift_at=2, k_hot=spec.n_pages // 64)


def _check_paper_records(out: dict, n_epochs: int) -> None:
    lanes = out["trajectory"]["lanes"]
    check(set(lanes) == set(ALL_POLICIES), sorted(lanes))
    for recs in lanes.values():
        check(len(recs) == n_epochs, f"{len(recs)} records")
        check(all(r["time_s"] > 0 for r in recs), "epoch time <= 0")
    # after one epoch the lanes lock on: the threshold-gated lane shows
    # precision where the full-k oracle is diluted by count-1 pages, and
    # the lookahead-fed prefetch lane holds the coming epoch's hot set
    acc = {name: lanes[name][1]["accuracy"] for name in lanes}
    log(f"  epoch-1 accuracy: {json.dumps(acc)}")
    check(acc["hmu_oracle"] > 0.3, acc)
    check(acc["reactive_watermark"] > 0.6, acc)
    check(acc["prefetch"] > 0.6, acc)


def phase_b(spec, kernels: dict, use_pallas) -> None:
    """Paper-scale DLRM: six lanes, hints, batched record sync."""
    sc = _paper_scenario(spec)
    log(f"phase B: DLRM {sc.n_blocks:,} pages, k_hot={sc.k_hot:,}, "
        f"{spec.lookups_per_batch:,} lookups x {sc.batches_per_epoch} "
        f"batches x {sc.n_epochs} epochs, shift at {sc.shift_at}, "
        f"sync_every=2")
    out, row = _measured("paper", sc.n_epochs, lambda: run_scenario(
        _paper_scenario(spec), hints=True, sync_every=2,
        use_pallas=use_pallas))
    check(row["kernels"] == kernels, row["kernels"])
    check(row["dispatches_per_epoch"] == 2, row)
    _check_paper_records(out, sc.n_epochs)


def phase_c(spec, n_devices: int) -> None:
    """Phase B's stream sharded over ``n_devices`` vs on one device."""
    sc = _paper_scenario(spec)
    log(f"phase C: DLRM {sc.n_blocks:,} pages sharded over {n_devices} "
        f"devices vs one device")
    epochs = list(sc.epochs())
    one, _ = _measured("one device", sc.n_epochs, lambda: run_scenario(
        _paper_scenario(spec), hints=True, sync_every=2, epochs=epochs))
    mesh = make_telemetry_mesh(n_devices)
    with jax.set_mesh(mesh):
        shd, row = _measured("sharded", sc.n_epochs, lambda: run_scenario(
            _paper_scenario(spec), hints=True, sync_every=2, epochs=epochs,
            mesh=mesh))
    check(row["kernels"] == XLA_KERNELS, row["kernels"])
    check(row["dispatches_per_epoch"] == 2, row)
    check(_same(one["trajectory"], shd["trajectory"]), "sharded != one")
    _check_paper_records(shd, sc.n_epochs)
    log(f"  bit-identical: {n_devices}-device mesh == one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase C (sharded over 4 chips) and nothing "
                         "else")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at reduced sizes, interpret-mode "
                         "kernels; prints no result line")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"refusing to run on the CPU", file=sys.stderr)
        return 2
    cache = use_compile_cache(ROOT)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    spec = REHEARSAL_SPEC if args.rehearse else datagen.PAPER
    kernels = CPU_KERNELS if args.rehearse else TPU_KERNELS
    # the default path on a TPU; off TPU the rehearsal asks for the
    # interpreted kernels explicitly
    use_pallas = True if args.rehearse else None
    t0 = time.perf_counter()
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        phase_c(spec, 4)
    else:
        phase_a(kernels, use_pallas)
        phase_b(spec, kernels, use_pallas)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    if args.rehearse:
        log("rehearsal only: no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
