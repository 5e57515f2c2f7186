"""repro.obs — span tracing, metrics registry, chrome-trace timelines.

Four layers under test, matching the observability PR's hard rule that
watching the runtime must cost the watched system nothing:

* **Registry** — counter/gauge/histogram families with labeled children,
  kind-mismatch and bad-bucket rejection, and the :class:`CounterDict`
  bridge that keeps ``core.runtime``'s ``DISPATCH_COUNTS``/``TRACE_COUNTS``
  dict API (including nested ``counting()`` scopes) while every increment
  lands in ``repro_dispatch_total{kind=...}``.
* **Tracer** — nestable spans over an injectable clock (exact durations
  with a fake clock), the NOOP_SPAN singleton identity, and a
  tracemalloc-verified zero-allocation disabled hot loop.
* **Timeline** — chrome trace-event conversion (host spans only) and
  ``pipelining_visible``: structurally True for a ``sync_every=K>1`` span
  pattern, False for K=1.
* **Named work** — the compiled epoch programs carry the ``selectk.*``,
  ``placement.free_slots`` and ``telemetry.*`` scopes (a quota fleet's
  also ``tenancy.*``) and the same ops without them; a traced fused epoch records one ``id_upload``,
  ``record_wait``, ``record_pull`` and ``record_assembly`` (and, hinted,
  ``hints``, ``hints.detector``, ``hints.lookahead``), with span args
  reaching ``jax.profiler.TraceAnnotation``.
* **Integration** — an enabled-tracer runtime run produces exactly the
  expected spans with zero added dispatches and bit-identical records vs
  disabled; runtime_span/runtime_metric wire records validate against the
  frozen schema; the Prometheus sink escapes hostile label values, emits
  HELP/TYPE for every family, and publishes the export client's own drop
  counters.
"""
import json
import tracemalloc

import numpy as np
import pytest

from repro.core import runtime as rtmod
from repro.core.runtime import EpochRuntime
from repro.export import (ExportClient, MemorySink, PrometheusTextSink,
                          SchemaError, runtime_metric_wire,
                          runtime_span_wire, validate_record)
from repro.obs import chrometrace
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import CounterDict, MetricsRegistry
from repro.obs.trace import (NOOP_SPAN, NULL_TRACER, Clock, Span, SpanTracer,
                             tracing)


class FakeClock(Clock):
    """Deterministic clock: each read returns the next scripted instant."""

    def __init__(self, start=0.0, step=1.0):
        self.t = start
        self.step = step
        super().__init__(self._tick)

    def _tick(self):
        t, self.t = self.t, self.t + self.step
        return t


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("repro_x_total", help="h").labels(kind="a")
        c.inc()
        c.inc(3)
        assert c.value == 4
        g = reg.gauge("repro_g").labels()
        g.set(2.5)
        assert g.value == 2.5
        h = reg.histogram("repro_d_s", buckets=(0.1, 1.0)).labels(span="s")
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.bucket_counts == [1, 1, 1]     # <=0.1, <=1.0, overflow
        assert h.count == 3 and h.sum == pytest.approx(5.55)

    def test_get_or_create_is_idempotent_but_kind_checked(self):
        reg = MetricsRegistry()
        fam = reg.counter("repro_x_total")
        assert reg.counter("repro_x_total") is fam
        with pytest.raises(ValueError, match="already registered as counter"):
            reg.gauge("repro_x_total")

    def test_bad_buckets_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="strictly increasing"):
            reg.histogram("repro_bad_s", buckets=(1.0, 1.0, 2.0))
        with pytest.raises(ValueError, match="strictly increasing"):
            reg.histogram("repro_bad2_s", buckets=(2.0, 1.0))
        with pytest.raises(ValueError, match="only apply to histograms"):
            obs_metrics.MetricFamily("repro_c_total", "counter",
                                     buckets=(1.0,))

    def test_counter_rejects_negative_increment(self):
        c = MetricsRegistry().counter("repro_x_total").labels(kind="a")
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_label_children_are_distinct_and_cached(self):
        fam = MetricsRegistry().counter("repro_x_total")
        a, b = fam.labels(kind="a"), fam.labels(kind="b")
        assert a is not b and fam.labels(kind="a") is a
        a.inc()
        assert (a.value, b.value) == (1, 0)
        assert len(fam.children()) == 2

    def test_counterdict_dict_api(self):
        fam = MetricsRegistry().counter("repro_x_total")
        view = CounterDict(fam, "kind", keys=("a", "b"))
        view["a"] += 2
        view["c"] = 7                        # new keys appear on assignment
        assert view["a"] == 2 and view["b"] == 0 and view["c"] == 7
        assert dict(view.items()) == {"a": 2, "b": 0, "c": 7}
        assert dict(view) == {"a": 2, "b": 0, "c": 7}
        assert view == {"a": 2, "b": 0, "c": 7}
        assert "a" in view and "z" not in view and len(view) == 3
        assert view.get("z", -1) == -1
        with pytest.raises(KeyError):
            view["z"]
        # increments are visible in the underlying registry family
        assert fam.labels(kind="a").value == 2

    def test_counterdict_requires_counter_family(self):
        with pytest.raises(ValueError, match="counter family"):
            CounterDict(MetricsRegistry().gauge("repro_g"), "kind")

    def test_runtime_counts_are_registry_views(self):
        assert isinstance(rtmod.DISPATCH_COUNTS, CounterDict)
        assert isinstance(rtmod.TRACE_COUNTS, CounterDict)
        fams = {f.name for f in obs_metrics.REGISTRY.families()}
        assert {"repro_dispatch_total", "repro_trace_total"} <= fams

    def test_counting_nests_over_registry_views(self):
        # the regression counting() guards: inner scopes must not blank
        # outer accrual, and inner activity accrues outward — now with the
        # module dicts backed by registry counters
        with rtmod.counting() as outer:
            rtmod.DISPATCH_COUNTS["observe_all"] += 1
            with rtmod.counting() as inner:
                rtmod.DISPATCH_COUNTS["observe_all"] += 2
                assert inner.dispatch["observe_all"] == 2
                assert outer.dispatch["observe_all"] == 3
            assert outer.dispatch["observe_all"] == 3
            assert dict(inner.dispatch)["observe_all"] == 2

    def test_publish_to_prometheus_sink(self):
        reg = MetricsRegistry()
        reg.counter("repro_x_total", help="things").labels(kind="a").inc(4)
        reg.gauge("repro_depth").labels(lane="l").set(3)
        reg.histogram("repro_d_s", help="dur",
                      buckets=(0.1, 1.0)).labels(span="s").observe(0.5)
        sink = PrometheusTextSink()
        reg.publish(sink)
        text = sink.render()
        assert '# HELP repro_x_total things' in text
        assert '# TYPE repro_x_total counter' in text
        assert 'repro_x_total{kind="a"} 4' in text
        assert 'repro_depth{lane="l"} 3' in text
        assert '# TYPE repro_d_s histogram' in text
        assert 'repro_d_s_bucket{span="s",le="0.1"} 0' in text
        assert 'repro_d_s_bucket{span="s",le="1"} 1' in text
        assert 'repro_d_s_bucket{span="s",le="+Inf"} 1' in text
        assert 'repro_d_s_sum{span="s"} 0.5' in text
        assert 'repro_d_s_count{span="s"} 1' in text


# ------------------------------------------------------------------ tracer
class TestTracer:
    def test_noop_span_is_a_singleton(self):
        assert NULL_TRACER.span("observe_all", epoch=3) is NOOP_SPAN
        assert NULL_TRACER.span("epoch_step") is NOOP_SPAN
        assert not NULL_TRACER.enabled and NULL_TRACER.spans == ()

    def test_disabled_hot_loop_allocates_nothing(self):
        tr = obs_trace.get_tracer()
        assert not tr.enabled

        def loop(tracer, iters):
            for step in range(iters):
                cm = (tracer.span("observe_all", epoch=step)
                      if tracer.enabled else NOOP_SPAN)
                with cm:
                    pass

        loop(tr, 256)                        # warm interning
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            loop(tr, 4096)
            grown = tracemalloc.get_traced_memory()[0] - base
        finally:
            tracemalloc.stop()
        assert grown == 0

    def test_fake_clock_gives_exact_durations(self):
        clock = FakeClock(start=10.0, step=1.0)
        tr = SpanTracer(clock=clock)
        with tr.span("observe_all", epoch=2):
            pass
        (s,) = tr.spans
        assert (s.name, s.epoch) == ("observe_all", 2)
        assert s.t0_s == 10.0 and s.dur_s == 1.0 and s.depth == 0

    def test_nesting_depth_and_args(self):
        tr = SpanTracer(clock=FakeClock())
        with tr.span("outer"):
            with tr.span("inner", epoch=1, arrays="a,b"):
                pass
        inner, outer = tr.spans            # inner closes first
        assert (inner.name, inner.depth, outer.depth) == ("inner", 1, 0)
        assert inner.args == {"arrays": "a,b"} and inner.epoch == 1
        assert outer.args is None

    def test_max_spans_drops_are_counted(self):
        tr = SpanTracer(clock=FakeClock(), max_spans=2)
        for _ in range(5):
            with tr.span("x"):
                pass
        assert len(tr.spans) == 2 and tr.dropped_spans == 3
        tr.clear()
        assert tr.spans == [] and tr.dropped_spans == 0

    def test_tracing_scope_installs_and_restores(self):
        before = obs_trace.get_tracer()
        with tracing(clock=FakeClock()) as tr:
            assert obs_trace.get_tracer() is tr and tr.enabled
            with tr.span("x"):
                pass
        assert obs_trace.get_tracer() is before
        assert [s.name for s in tr.spans] == ["x"]

    def test_metrics_mirror_records_span_durations(self):
        reg = MetricsRegistry()
        tr = SpanTracer(clock=FakeClock(), metrics=reg)
        with tr.span("observe_all"):
            pass
        (fam,) = [f for f in reg.families()
                  if f.name == "repro_span_duration_s"]
        (child,) = fam.children()
        assert dict(child.labels) == {"span": "observe_all"}
        assert child.count == 1 and child.sum == pytest.approx(1.0)

    def test_elapsed_s_uses_injected_clock(self):
        clock = FakeClock(start=5.0)
        assert obs_trace.elapsed_s(2.0, clock=clock) == 3.0


# ---------------------------------------------------------------- timeline
def span(name, t0, dur, *, tid="host", epoch=None, args=None, depth=0):
    return Span(name=name, t0_s=t0, dur_s=dur, tid=tid, depth=depth,
                epoch=epoch, args=args)


def pipelined_spans():
    """sync_every=2 shape: epoch 2's observe_all dispatches before the
    record_sync draining epochs [0, 2) begins."""
    return [
        span("observe_all", 0.0, 0.1, epoch=0),
        span("epoch_step", 0.1, 0.1, epoch=0),
        span("observe_all", 1.0, 0.1, epoch=1),
        span("epoch_step", 1.1, 0.1, epoch=1),
        span("observe_all", 2.0, 0.1, epoch=2),
        span("record_sync", 2.2, 0.5,
             args={"epoch_base": 0, "n_epochs": 2}),
        span("epoch_step", 2.8, 0.1, epoch=2),
    ]


class TestChromeTrace:
    def test_event_shape_and_normalisation(self):
        events = chrometrace.chrome_trace_events(
            [span("observe_all", 3.0, 0.25, epoch=7,
                  args={"arrays": "x"})])
        (e,) = events
        assert e["ph"] == "X" and e["cat"] == "runtime"
        assert e["ts"] == 0.0 and e["dur"] == pytest.approx(0.25e6)
        assert e["pid"] == 1 and e["tid"] == "host"
        assert e["args"] == {"epoch": 7, "arrays": "x"}

    def test_pipelining_visible_for_k_gt_1(self):
        assert chrometrace.pipelining_visible(pipelined_spans())

    def test_pipelining_not_visible_for_k_eq_1(self):
        serial = [
            span("observe_all", 0.0, 0.1, epoch=0),
            span("record_sync", 0.2, 0.1,
                 args={"epoch_base": 0, "n_epochs": 1}),
            span("observe_all", 1.0, 0.1, epoch=1),
            span("record_sync", 1.2, 0.1,
                 args={"epoch_base": 1, "n_epochs": 1}),
        ]
        assert not chrometrace.pipelining_visible(serial)

    def test_write_chrome_trace_round_trip(self, tmp_path):
        path = tmp_path / "trace.json"
        doc = chrometrace.write_chrome_trace(
            path, pipelined_spans(), metadata={"bench": "test"})
        on_disk = json.loads(path.read_text())
        assert on_disk == doc
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"] == {"bench": "test"}
        tids = {e["tid"] for e in doc["traceEvents"]}
        assert tids == {"host"}            # host spans only, no device track
        assert len(doc["traceEvents"]) == len(pipelined_spans())
        ts = [e["ts"] for e in doc["traceEvents"]]
        assert ts == sorted(ts)


# -------------------------------------------------------------- wire forms
class TestWire:
    def test_runtime_span_wire_validates(self):
        rec = runtime_span_wire(
            span("record_sync", 1.5, 0.25, depth=1,
                 args={"epoch_base": 4, "n_epochs": 2}),
            scenario="kv_cache")
        assert validate_record(rec) is rec
        assert rec["t_start_us"] == pytest.approx(1.5e6)
        assert rec["duration_us"] == pytest.approx(0.25e6)
        assert (rec["epoch_base"], rec["n_epochs_count"]) == (4, 2)
        assert rec["track"] == "host" and rec["scenario"] == "kv_cache"

    def test_runtime_metric_wire_counter_and_histogram(self):
        c = runtime_metric_wire("repro_dispatch_total", "counter", 12,
                                labels={"kind": "observe_all"})
        validate_record(c)
        h = runtime_metric_wire(
            "repro_span_duration_s", "histogram",
            labels={"span": "observe_all"}, bucket_le=[0.1, 1.0],
            bucket_counts=[3, 1, 0], sum_value=0.6, observations=4)
        validate_record(h)
        assert h["bucket_counts"] == [3, 1, 0] and h["sum"] == 0.6

    def test_frozen_shapes_still_enforced(self):
        rec = runtime_span_wire(span("observe_all", 0.0, 0.1))
        rec["surprise"] = 1
        with pytest.raises(SchemaError, match="unknown fields"):
            validate_record(rec)
        bad = runtime_metric_wire("m", "counter", 1)
        bad["kind"] = "timer"
        with pytest.raises(SchemaError, match="not one of"):
            validate_record(bad)
        # label values are string-typed on the wire
        typed = runtime_metric_wire("m", "counter", 1, labels={"k": "v"})
        typed["labels"]["k"] = 3
        with pytest.raises(SchemaError, match="labels.k"):
            validate_record(typed)


# ----------------------------------------------------------- prometheus sink
class TestPrometheusSink:
    def test_hostile_label_values_round_trip(self):
        sink = PrometheusTextSink()
        hostile = 'a\\b"c\nd'
        sink.set_counter("repro_x_total", 1, help="h", kind=hostile)
        line = [ln for ln in sink.render().splitlines()
                if ln.startswith("repro_x_total{")][0]
        assert line == 'repro_x_total{kind="a\\\\b\\"c\\nd"} 1'
        # unescaping recovers the original value
        raw = line.split('kind="', 1)[1].rsplit('"}', 1)[0]
        unescaped = (raw.replace("\\n", "\n").replace('\\"', '"')
                     .replace("\\\\", "\\"))
        assert unescaped == hostile

    def test_every_family_gets_help_and_type(self):
        sink = PrometheusTextSink()
        sink.write([{"scenario": "s", "lane": "l", "coverage": 0.5}])
        sink.set_counter("repro_c_total", 1)
        sink.set_gauge("repro_g", 2)
        sink.set_histogram("repro_h_s", (0.1,), (1, 0), 0.05)
        text = sink.render()
        for name in ("repro_coverage_ratio", "repro_c_total", "repro_g",
                     "repro_h_s"):
            assert f"# HELP {name} " in text
            assert f"# TYPE {name} " in text
        assert "\n# HELP repro_h_s Latency histogram\n" in "\n" + text

    def test_histogram_rendering_is_cumulative(self):
        sink = PrometheusTextSink()
        sink.set_histogram("repro_h_s", (0.1, 1.0), (2, 3, 1), 2.5,
                           span="observe_all")
        text = sink.render()
        assert 'repro_h_s_bucket{span="observe_all",le="0.1"} 2' in text
        assert 'repro_h_s_bucket{span="observe_all",le="1"} 5' in text
        assert 'repro_h_s_bucket{span="observe_all",le="+Inf"} 6' in text
        assert 'repro_h_s_sum{span="observe_all"} 2.5' in text
        assert 'repro_h_s_count{span="observe_all"} 6' in text

    def test_histogram_bucket_count_mismatch_raises(self):
        with pytest.raises(ValueError, match="len\\(bounds\\)\\+1"):
            PrometheusTextSink().set_histogram("repro_h_s", (0.1,), (1,), 0.0)

    def test_newline_in_help_is_escaped(self):
        sink = PrometheusTextSink()
        sink.set_counter("repro_c_total", 1, help="line1\nline2")
        assert "# HELP repro_c_total line1\\nline2" in sink.render()


# ------------------------------------------------------- export integration
class TestExportIntegration:
    def test_spans_and_metrics_flow_through_client(self):
        sink = MemorySink()
        client = ExportClient(sink, flush_interval_s=0.01)
        try:
            assert client.export_runtime_span(
                span("observe_all", 0.0, 0.1, epoch=3))
            reg = MetricsRegistry()
            reg.counter("repro_x_total").labels(kind="a").inc(2)
            reg.histogram("repro_d_s",
                          buckets=(0.1,)).labels(span="s").observe(0.05)
            assert client.export_metrics(reg) == 2
            client.flush(timeout=10)
        finally:
            client.close()
        recs = sink.snapshot()
        kinds = sorted(r["record_type"] for r in recs)
        assert kinds == ["runtime_metric", "runtime_metric", "runtime_span"]
        for rec in recs:
            validate_record(rec)

    def test_drop_counters_published_to_prometheus_sink(self):
        sink = PrometheusTextSink()
        client = ExportClient(sink, flush_interval_s=0.01)
        try:
            # invalid records are accepted at the door (enqueue never
            # validates — that would put schema work on the epoch loop) and
            # dropped by the flusher, where the drop must become a counter
            assert client.emit({"record_type": "nonsense"})
            client.export_runtime_metric("repro_x_total", "counter", 1)
            client.flush(timeout=10)
            text = sink.render()
        finally:
            client.close()
        assert 'repro_export_dropped_total{reason="invalid"} 1' in text
        assert "repro_export_emitted_total 2" in text
        assert "repro_export_exported_total 1" in text

    def test_export_spans_are_not_recursive(self):
        # the client's own enqueue/flush spans must not emit records (that
        # would self-amplify); they are only host spans on the tracer
        sink = MemorySink()
        client = ExportClient(sink, flush_interval_s=0.01)
        try:
            with tracing(clock=FakeClock()) as tr:
                client.export_runtime_metric("repro_x_total", "counter", 1)
                client.flush(timeout=10)
            names = {s.name for s in tr.spans}
            assert "export.enqueue" in names
        finally:
            client.close()
        assert all(r["record_type"] == "runtime_metric"
                   for r in sink.snapshot())


# -------------------------------------------------------- runtime integration
def _run(n, k, eps, export=None):
    rt = EpochRuntime(n, k, policies=("hmu_oracle", "nb_two_touch"),
                      pebs_period=8, nb_scan_rate=n // 4, fused=True,
                      sync_every=2, export=export)
    with rtmod.counting() as c:
        rt.run(iter(eps))
        disp = dict(c.dispatch)
    return rt, disp


class TestRuntimeIntegration:
    N, K, EPOCHS = 512, 64, 4

    @pytest.fixture(scope="class")
    def runs(self):
        rng = np.random.default_rng(7)
        eps = [(rng.zipf(1.2, size=(2, 512)) % self.N).astype(np.int32)
               for _ in range(self.EPOCHS)]
        _run(self.N, self.K, eps)                      # warm the jit caches
        obs_trace.disable()
        off_rt, off_disp = _run(self.N, self.K, eps)
        with tracing() as tracer:
            on_rt, on_disp = _run(self.N, self.K, eps)
        return off_rt, off_disp, on_rt, on_disp, tracer

    def test_zero_added_dispatches(self, runs):
        _, off_disp, _, on_disp, _ = runs
        assert on_disp == off_disp
        per_epoch = (on_disp["observe_all"]
                     + on_disp["epoch_step"]) / self.EPOCHS
        assert per_epoch == 2

    def test_bit_identical_records_and_placements(self, runs):
        off_rt, _, on_rt, _, _ = runs
        for lane in ("hmu_oracle", "nb_two_touch"):
            assert ([r.to_dict() for r in off_rt.records[lane]]
                    == [r.to_dict() for r in on_rt.records[lane]])
            assert np.array_equal(off_rt.lanes[lane].slot_to_block,
                                  on_rt.lanes[lane].slot_to_block)

    def test_exact_span_accounting(self, runs):
        *_, tracer = runs
        by_name = {}
        for s in tracer.spans:
            by_name[s.name] = by_name.get(s.name, 0) + 1
        assert by_name["observe_all"] == self.EPOCHS
        assert by_name["epoch_step"] == self.EPOCHS
        assert by_name["record_sync"] == self.EPOCHS // 2   # sync_every=2
        assert tracer.dropped_spans == 0

    def test_pipelining_visible_in_real_run(self, runs):
        *_, tracer = runs
        assert chrometrace.pipelining_visible(tracer.spans)
        sync = [s for s in tracer.spans if s.name == "record_sync"][0]
        assert set(sync.args) == {"epoch_base", "n_epochs"}

    def test_spans_export_as_valid_wire_records(self, runs):
        *_, tracer = runs
        for s in tracer.spans:
            validate_record(runtime_span_wire(s, scenario="test"))


# ------------------------------------------------------- named scopes (HLO)
EPOCH_STEP_SCOPES = ("selectk.threshold", "selectk.mask", "selectk.compact",
                     "selectk.order", "placement.free_slots")
COLLECTOR_SCOPES = ("telemetry.true", "telemetry.hmu", "telemetry.pebs",
                    "telemetry.nb")


def _scope_names(hlo_text):
    """Every component of every ``op_name`` in compiled HLO text."""
    import re
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/")}


def _without_metadata(hlo_text):
    """Compiled HLO with op metadata and the debug tables cut: the file,
    function, location and stack-frame tables printed between the
    module's header and its computations."""
    import re
    lines = [line for line in hlo_text.splitlines()
             if not re.match(r"(FileNames|FunctionNames|FileLocations|"
                             r"StackFrames)$|\d+ ", line)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


def _compiled_programs(rt, faults=False):
    """(_epoch_step, observe_all) compiled for ``rt``'s state at its size,
    freshly traced (new callables, so no cached trace is reused)."""
    import jax
    import jax.numpy as jnp
    from repro.core import telemetry as tel
    from repro.faults import FaultModel
    state = rt._state
    bundle = state.bundle
    if faults:
        bundle = tel.bundle_init(rt.n_blocks, pebs_period=8,
                                 faults=FaultModel.create(pebs_drop_p=0.1))
    step_fn = rtmod._epoch_step.__wrapped__
    obs_fn = tel.observe_all.__wrapped__
    step = jax.jit(lambda *a, **k: step_fn(*a, **k),
                   static_argnames=("cfg", "s_max")).lower(
        state, jnp.int32(4 * 512), jnp.int32(0), cfg=rt._cfg,
        s_max=64).compile().as_text()
    observe = jax.jit(lambda *a, **k: obs_fn(*a, **k),
                      static_argnames=("pallas",)).lower(
        bundle, jnp.zeros((4, 512), jnp.int32),
        pallas=rt._pallas).compile().as_text()
    return step, observe


class TestNamedScopes:
    N, K = 2048, 128

    @pytest.fixture(scope="class")
    def compiled(self):
        rt = EpochRuntime(self.N, self.K, pebs_period=8,
                          nb_scan_rate=self.N // 4, fused=True)
        return {"plain": _compiled_programs(rt),
                "faults": _compiled_programs(rt, faults=True)[1], "rt": rt}

    @pytest.mark.parametrize("scope", EPOCH_STEP_SCOPES)
    def test_epoch_step_hlo_names_the_scope(self, compiled, scope):
        step, _ = compiled["plain"]
        assert scope in _scope_names(step)

    @pytest.mark.parametrize("branch", ("plain", "faults"))
    @pytest.mark.parametrize("scope", COLLECTOR_SCOPES)
    def test_observe_all_hlo_names_each_collector(self, compiled, scope,
                                                  branch):
        observe = (compiled["plain"][1] if branch == "plain"
                   else compiled["faults"])
        assert scope in _scope_names(observe)

    def test_scopes_leave_the_compiled_ops_alone(self, compiled,
                                                 monkeypatch):
        import contextlib
        from repro.core import placement, selectk
        from repro.core import telemetry as tel
        for mod in (selectk, placement, tel):
            monkeypatch.setattr(mod, "named_scope",
                                lambda name: contextlib.nullcontext())
        bare = _compiled_programs(compiled["rt"])
        for scoped, plain in zip(compiled["plain"], bare):
            assert not _scope_names(plain) & set(EPOCH_STEP_SCOPES
                                                 + COLLECTOR_SCOPES)
            assert _without_metadata(scoped) == _without_metadata(plain)

    @pytest.mark.parametrize("scope", ("selectk.compact",
                                       "placement.free_slots"))
    def test_compaction_scatter_sits_in_its_scope(self, compiled, scope):
        """At this size both compactions take the scatter path, and the
        scatter op itself carries the scope the benchmark reads."""
        import re
        step, _ = compiled["plain"]
        names = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in step.splitlines()
                 if re.search(r"\bscatter\(", line) and "op_name=" in line]
        assert any(scope in name.split("/") for name in names), names

    def test_hist_select_kernel_is_named(self):
        import jax
        import jax.numpy as jnp
        from repro.kernels.hist_select.kernel import kth_key_u_pallas
        jaxpr = jax.make_jaxpr(lambda u: kth_key_u_pallas(
            u, jnp.zeros((256,), jnp.int32), jnp.asarray([3]), tile_n=128,
            interpret=True))(jnp.zeros((2, 256), jnp.uint32))
        (eqn,) = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name == "pallas_call"]
        assert eqn.params["name"] == "hist_select"


TENANCY_SCOPES = ("tenancy.select", "tenancy.hot_set", "tenancy.account")


class TestTenancyScopes:
    """What a quota fleet adds to the epoch step is named, and a runtime
    without a Tenancy compiles exactly as without the names."""
    N, K = 2048, 128

    @pytest.fixture(scope="class")
    def runtimes(self):
        from repro.core.runtime import Tenancy
        fleet = EpochRuntime(
            self.N, self.K, pebs_period=8, nb_scan_rate=self.N // 4,
            fused=True, tenancy=Tenancy(offsets=(0, 1536, self.N),
                                        hot_k=(96, 32), caps=(96, 32)))
        plain = EpochRuntime(self.N, self.K, pebs_period=8,
                             nb_scan_rate=self.N // 4, fused=True)
        return {"fleet": fleet, "plain": plain}

    @pytest.fixture(scope="class")
    def steps(self, runtimes):
        return {name: _compiled_programs(rt)[0]
                for name, rt in runtimes.items()}

    @pytest.fixture(scope="class")
    def bare_steps(self, runtimes):
        """The same programs with the runtime's scopes left out."""
        import contextlib
        mp = pytest.MonkeyPatch()
        mp.setattr(rtmod, "named_scope",
                   lambda name: contextlib.nullcontext())
        try:
            return {name: _compiled_programs(rt)[0]
                    for name, rt in runtimes.items()}
        finally:
            mp.undo()

    @pytest.mark.parametrize("scope", TENANCY_SCOPES)
    def test_fleet_epoch_step_names_the_scope(self, steps, scope):
        assert scope in _scope_names(steps["fleet"])

    def test_scopes_leave_the_fleet_ops_alone(self, steps, bare_steps):
        assert not _scope_names(bare_steps["fleet"]) & set(TENANCY_SCOPES)
        assert (_without_metadata(steps["fleet"])
                == _without_metadata(bare_steps["fleet"]))

    def test_program_without_tenancy_is_unchanged(self, steps, bare_steps):
        assert not _scope_names(steps["plain"]) & set(TENANCY_SCOPES)
        assert _scope_names(steps["plain"]) == _scope_names(
            bare_steps["plain"])
        assert (_without_metadata(steps["plain"])
                == _without_metadata(bare_steps["plain"]))


# ------------------------------------------------ compaction algorithm count
def test_compact_impl_counter_ticks_once_per_trace_per_site():
    """At DLRM SMALL (5,000 pages) both compaction sites take the scatter
    path: one tick each per trace of the epoch step, none per epoch."""
    import itertools
    from repro.core import selectk
    from repro.dlrm import datagen
    from repro.scenarios import DLRMScenario
    # a k_hot no other test uses, so this process traces the step afresh
    scn = DLRMScenario(spec=datagen.SMALL, n_epochs=3, k_hot=251)
    rt = EpochRuntime.for_scenario(scn, policies=("hmu_oracle", "hinted"))
    cells = {(site, impl): selectk.COMPACT_IMPL.labels(site=site, impl=impl)
             for site, impl in itertools.product(("select", "free_slots"),
                                                 ("scatter", "search"))}
    before = {key: c.value for key, c in cells.items()}
    with rtmod.counting() as c:
        for batches in scn.epochs():
            rt.step(batches)
        rt.flush()
    assert c.trace["epoch_step"] == 1
    ticks = {key: cells[key].value - before[key] for key in cells}
    assert ticks == {("select", "scatter"): 1, ("select", "search"): 0,
                     ("free_slots", "scatter"): 1,
                     ("free_slots", "search"): 0}
    assert selectk.compact_impl(scn.n_blocks, scn.k_hot) == "scatter"


# ----------------------------------------------------- runtime's finer spans
def _hinted_run(n, k, eps):
    from repro.hints import HintPipeline, LookaheadWindow, PhaseChangeDetector
    pipe = HintPipeline(n, static=np.linspace(1, 0, n, dtype=np.float32),
                        lookahead=LookaheadWindow(n, depth=1),
                        detector=PhaseChangeDetector(n))
    rt = EpochRuntime(n, k, pebs_period=8, nb_scan_rate=n // 4, fused=True,
                      hints=pipe)
    for i, ep in enumerate(eps):
        rt.step(ep, lookahead=eps[i + 1:i + 2])
    return rt


class TestRuntimeSpans:
    N, K, EPOCHS = 512, 64, 3
    PER_EPOCH = ("hints", "hints.detector", "hints.lookahead", "id_upload",
                 "observe_all", "epoch_step", "record_sync", "record_wait",
                 "record_pull", "record_assembly")

    @pytest.fixture(scope="class")
    def runs(self):
        rng = np.random.default_rng(11)
        eps = [(rng.zipf(1.2, size=(2, 256)) % self.N).astype(np.int32)
               for _ in range(self.EPOCHS)]
        _hinted_run(self.N, self.K, eps)               # warm the jit caches
        obs_trace.disable()
        off = _hinted_run(self.N, self.K, eps)
        with tracing() as tracer:
            on = _hinted_run(self.N, self.K, eps)
        return off, on, tracer, eps

    @pytest.mark.parametrize("name", PER_EPOCH)
    def test_one_span_per_fused_epoch(self, runs, name):
        *_, tracer, _ = runs
        assert sum(s.name == name for s in tracer.spans) == self.EPOCHS

    def test_wait_and_pull_nest_inside_record_sync(self, runs):
        *_, tracer, _ = runs
        syncs = [s for s in tracer.spans if s.name == "record_sync"]
        for name in ("record_wait", "record_pull"):
            for s in (s for s in tracer.spans if s.name == name):
                (outer,) = [o for o in syncs
                            if o.t0_s <= s.t0_s
                            and s.t0_s + s.dur_s <= o.t0_s + o.dur_s]
                assert s.depth == outer.depth + 1
                assert s.args == {"epoch_base": outer.args["epoch_base"]}
        for s in (s for s in tracer.spans if s.name == "record_assembly"):
            assert s.depth == 0 and s.args["n_epochs"] == 1

    def test_hint_providers_nest_inside_hints(self, runs):
        *_, tracer, _ = runs
        hints = [s for s in tracer.spans if s.name == "hints"]
        assert [s.epoch for s in hints] == list(range(self.EPOCHS))
        for s in tracer.spans:
            if s.name.startswith("hints."):
                assert any(h.t0_s <= s.t0_s
                           and s.t0_s + s.dur_s <= h.t0_s + h.dur_s
                           and s.depth == h.depth + 1 for h in hints)

    def test_id_upload_carries_the_bytes(self, runs):
        *_, tracer, eps = runs
        ups = [s for s in tracer.spans if s.name == "id_upload"]
        assert [s.epoch for s in ups] == list(range(self.EPOCHS))
        assert [s.args["bytes"] for s in ups] == [ep.nbytes for ep in eps]
        # the upload comes before, not inside, the observe_all dispatch
        obs = [s for s in tracer.spans if s.name == "observe_all"]
        for u, o in zip(ups, obs):
            assert u.t0_s + u.dur_s <= o.t0_s and u.depth == o.depth

    def test_records_bit_identical_tracing_on_and_off(self, runs):
        off, on, *_ = runs
        for lane in off.records:
            assert ([r.to_dict() for r in off.records[lane]]
                    == [r.to_dict() for r in on.records[lane]])
            assert np.array_equal(off.lanes[lane].slot_to_block,
                                  on.lanes[lane].slot_to_block)


def test_span_args_reach_trace_annotation(monkeypatch):
    import jax
    seen = []

    class Recorder:
        def __init__(self, name, **kwargs):
            seen.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    tr = SpanTracer(clock=FakeClock(), xla_annotations=True)
    with tr.span("id_upload", epoch=4, bytes=1024):
        pass
    with tr.span("record_sync", epoch_base=2, n_epochs=1):
        pass
    with tr.span("hints.detector"):
        pass
    with tr.span("hint_refresh", epoch=1, arrays="hint_rank,prefetch_rank"):
        pass
    # the profiler's name#k=v,...# encoding would split a comma; the
    # span itself keeps the value as given
    assert seen == [("id_upload", {"bytes": 1024, "epoch": 4}),
                    ("record_sync", {"epoch_base": 2, "n_epochs": 1}),
                    ("hints.detector", {}),
                    ("hint_refresh", {"arrays": "hint_rank+prefetch_rank",
                                      "epoch": 1})]
    assert tr.spans[-1].args == {"arrays": "hint_rank,prefetch_rank"}


SPAN_SITES = ("hints", "hints.detector", "hints.lookahead", "id_upload",
              "observe_all", "epoch_step", "record_sync", "record_wait",
              "record_pull", "record_assembly")


def _span_site_lines(path):
    """Line numbers in ``path`` that look up the tracer, build a guarded
    span or enter one."""
    with open(path) as f:
        return {i for i, line in enumerate(f, 1)
                if "_tr" in line or "NOOP_SPAN" in line or "with cm" in line}


@pytest.fixture(scope="module")
def disabled_steps():
    """Hinted fused epochs with tracing off, the last ones under
    tracemalloc: the span names the disabled tracer was asked for, and
    the allocations still held afterwards that pass through a span site
    of the runtime or the hint pipeline, or through the tracer module."""
    from repro.hints import pipeline
    n, k = 512, 64
    rng = np.random.default_rng(5)
    eps = [(rng.zipf(1.2, size=(2, 256)) % n).astype(np.int32)
           for _ in range(6)]
    obs_trace.disable()
    asked = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs_trace.NullTracer, "span",
                   lambda self, name, **kw: asked.append(name) or NOOP_SPAN)
        rt = _hinted_run(n, k, eps[:2])                # compiled and warm
        tracemalloc.start(64)
        try:
            before = tracemalloc.take_snapshot()
            for i in range(2, len(eps)):
                rt.step(eps[i], lookahead=eps[i + 1:i + 2])
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
    sites = {path: _span_site_lines(path)
             for path in (rtmod.__file__, pipeline.__file__)}
    held = [st for st in after.compare_to(before, "traceback")
            if st.size_diff > 0 and any(
                fr.filename == obs_trace.__file__
                or fr.lineno in sites.get(fr.filename, ())
                for fr in st.traceback)]
    return asked, held


@pytest.mark.parametrize("name", SPAN_SITES)
def test_disabled_new_span_sites_allocate_nothing(disabled_steps, name):
    asked, held = disabled_steps
    # the guard skips the span() call, kwargs and all ...
    assert name not in asked
    # ... and nothing the span sites or the tracer allocate is kept
    assert held == []
