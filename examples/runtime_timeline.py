"""Runtime self-observability: trace the epoch loop, render the timeline.

The fused runtime claims its `sync_every=K` record sync is *pipelined* —
the host keeps dispatching new epochs while a previous window's records
are still being pulled off the device. `repro.obs` makes that claim
visible instead of argued: span-trace a run, write a Chrome trace, and
open it in chrome://tracing or https://ui.perfetto.dev to watch the
`record_sync` span start after the next epoch's `observe_all` dispatch.
This walkthrough:

* runs the same workload obs-off and obs-on (tracing + metrics registry
  + runtime_span/runtime_metric export) and checks nothing changed —
  dispatch counts equal, records bit-identical,
* prints the span accounting (exactly one observe_all + one epoch_step
  per epoch, ceil(n_epochs/K) record_syncs),
* writes the Chrome trace artifact and asserts the pipelining is
  structurally visible in it,
* renders the metrics registry as Prometheus text exposition.

    PYTHONPATH=src python examples/runtime_timeline.py
"""
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np

from repro.core import runtime as rtmod
from repro.core.runtime import EpochRuntime
from repro.export import ExportClient, MemorySink, PrometheusTextSink
from repro.obs import chrometrace
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

N_BLOCKS, K_HOT, N_EPOCHS, SYNC_EVERY = 2_000, 200, 6, 3
POLICIES = ("hmu_oracle", "hinted", "nb_two_touch")


def run(eps, export=None):
    rt = EpochRuntime(N_BLOCKS, K_HOT, policies=POLICIES, pebs_period=16,
                      nb_scan_rate=N_BLOCKS // 4, fused=True,
                      sync_every=SYNC_EVERY, export=export)
    with rtmod.counting() as c:
        rt.run(iter(eps))
        return rt, dict(c.dispatch)


def main():
    rng = np.random.default_rng(31)
    eps = [(rng.zipf(1.3, size=(2, 8_000)) % N_BLOCKS).astype(np.int32)
           for _ in range(N_EPOCHS)]

    # --- 1. obs off: the baseline the watcher must not perturb -----------
    run(eps)                                       # warm the jit caches
    off_rt, off_disp = run(eps)

    # --- 2. obs on: tracing + registry mirror + export --------------------
    registry = obs_metrics.MetricsRegistry()
    sink = MemorySink()
    client = ExportClient(sink)
    with obs_trace.tracing(metrics=registry) as tracer:
        on_rt, on_disp = run(eps, export=client)
    for span in tracer.spans:
        client.export_runtime_span(span)
    client.export_metrics(registry)
    client.flush()
    stats = client.stats()
    client.close()

    identical = all(
        [a.to_dict() for a in off_rt.records[lane]]
        == [b.to_dict() for b in on_rt.records[lane]]
        for lane in POLICIES)
    print(f"non-interference: dispatches_equal={on_disp == off_disp} "
          f"records_bit_identical={identical} "
          f"({(on_disp['observe_all'] + on_disp['epoch_step']) // N_EPOCHS}"
          f" dispatches/epoch)")
    assert on_disp == off_disp and identical, "observability changed the run"

    by_name = {}
    for s in tracer.spans:
        by_name[s.name] = by_name.get(s.name, 0) + 1
    print("span accounting:", dict(sorted(by_name.items())))
    print(f"exported {stats['exported']} records "
          f"({sum(1 for r in sink.snapshot() if r['record_type'] == 'runtime_span')}"
          f" runtime_span, "
          f"{sum(1 for r in sink.snapshot() if r['record_type'] == 'runtime_metric')}"
          f" runtime_metric)")

    # --- 3. the timeline ---------------------------------------------------
    trace_path = Path(tempfile.mkdtemp(prefix="repro_obs_")) / "trace.json"
    doc = chrometrace.write_chrome_trace(
        trace_path, tracer.spans,
        metadata={"example": "runtime_timeline", "sync_every": SYNC_EVERY})
    visible = chrometrace.pipelining_visible(tracer.spans)
    syncs = [e["args"] for e in doc["traceEvents"]
             if e["name"] == "record_sync"]
    print(f"\nchrome trace -> {trace_path}")
    print(f"  {len(doc['traceEvents'])} events, record syncs: {syncs}")
    print(f"  pipelining visible (sync_every={SYNC_EVERY}): {visible}")
    assert visible, "sync_every>1 must make record_sync overlap dispatch"
    print("  open in chrome://tracing or https://ui.perfetto.dev")

    # --- 4. the registry as a Prometheus scrape ---------------------------
    prom = PrometheusTextSink()
    registry.publish(prom)
    print("\nPrometheus exposition (span-duration histogram excerpt):")
    lines = prom.render().splitlines()
    wanted = [ln for ln in lines if "repro_span_duration_s" in ln]
    for line in wanted[:10]:
        print(" ", line)


if __name__ == "__main__":
    main()
