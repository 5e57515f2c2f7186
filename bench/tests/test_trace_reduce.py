"""The trace reduction on hand-made intervals, and on a recorded trace."""
import json
from pathlib import Path

import pytest

import run_cell
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    # window [0, 100) ns, two epochs; device ops by hand
    ops = {"/device:TPU:0": [
        (10, 20, "fusion.1", "jit_observe_all(1)"),
        (30, 50, "fusion.2", "jit__epoch_step(2)"),
        (40, 45, '%kth_key_u.3 = s32[5,8,1] custom-call(s32[5,64] %k), '
                  'custom_call_target="tpu_custom_call"',
         "jit__epoch_step(2)"),                          # nested in 2
        (60, 70, "fusion.4", "jit_observe_all(1)"),
        (90, 120, "fusion.5", "jit__epoch_step(2)"),      # clipped at 100
    ]}
    modules = {"/device:TPU:0": [
        (10, 20, "jit_observe_all(1)"), (30, 50, "jit__epoch_step(2)"),
        (60, 70, "jit_observe_all(1)"), (90, 120, "jit__epoch_step(2)")]}
    spans = [(0, 55, "served_epoch"), (0, 6, "hint_ranks"),
             (6, 8, "hint_set"), (7, 8, "hint_refresh"),
             (20, 29, "record_sync"), (55, 100, "served_epoch"),
             (72, 88, "record_sync")]
    return tr.Trace((0.0, 100.0), ops, modules, spans, n_epochs=2)


def test_union_merges_overlaps():
    assert tr.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.union_ns([]) == 0


def test_sums_over_the_window():
    t = _synthetic()
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx((10 + 20 + 10 + 10) * 1e-9)
    assert t.module_s("jit_observe_all") == pytest.approx(20e-9)
    assert t.module_s("jit__epoch_step") == pytest.approx(30e-9)
    assert t.kernel_s("jit__epoch_step") == pytest.approx(5e-9)
    assert t.span_s("record_sync") == pytest.approx(25e-9)
    assert t.span_s("observe_all") is None


def test_gaps_go_to_the_innermost_open_span():
    t = _synthetic()
    assert t.gaps() == [(0.0, 10), (20, 30), (50, 60), (70, 90)]
    assert t.host_activity(4) == "hint_ranks"
    assert t.host_activity(7.5) == "hint_refresh"
    assert t.host_activity(25) == "record_sync"
    assert t.host_activity(52) == "served_epoch"
    b = t.breakdown()
    idle = dict(b["idle_gaps"])
    assert idle == pytest.approx({"hint_ranks": 10e-9,
                                  "served_epoch": 10e-9,
                                  "record_sync": 30e-9})
    assert b["device_ops"][0] == ["jit__epoch_step/fusion.2",
                                  pytest.approx(15e-9)]
    assert t.self_times()["jit__epoch_step/kth_key_u.3"] == \
        pytest.approx(5e-9)


def test_readers_on_the_synthetic_trace():
    t = _synthetic()
    read = {name: run_cell.load_reader(name)(t) for name in (
        "hint_refresh_ms", "observe_ms", "epoch_step_ms",
        "select_kernel_ms", "record_sync_ms", "device_idle_pct")}
    # providers (6) plus hand-over (2), not the upload nested in it again
    assert read["hint_refresh_ms"] == pytest.approx(8e-9 / 2 * 1e3)
    assert read["observe_ms"] == pytest.approx(20e-9 / 2 * 1e3)
    assert read["epoch_step_ms"] == pytest.approx(30e-9 / 2 * 1e3)
    assert read["select_kernel_ms"] == pytest.approx(5e-9 / 2 * 1e3)
    assert read["record_sync_ms"] == pytest.approx(25e-9 / 2 * 1e3)
    assert read["device_idle_pct"] == pytest.approx(50.0)


def test_readers_find_nothing_in_an_empty_trace():
    t = tr.Trace((0.0, 100.0), {}, {}, [], n_epochs=1)
    for name in ("hint_refresh_ms", "observe_ms", "epoch_step_ms",
                 "select_kernel_ms", "record_sync_ms", "device_idle_pct"):
        assert run_cell.load_reader(name)(t) is None


# ---- a trace recorded on one TPU v5e: mmap_paper.scan cut to 65,536
# blocks, three traced epochs (bench/tests/data/tiny_mmap.xplane.pb.gz)
RECORDED = {"observe_ms": 6.308214, "epoch_step_ms": 11.924393333333333,
            "select_kernel_ms": 0.294527, "record_sync_ms": 18.388016,
            "device_idle_pct": 13.240577418719301}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    d = tmp_path_factory.mktemp("trace")
    raw = gzip.decompress((DATA / "tiny_mmap.xplane.pb.gz").read_bytes())
    (d / "tiny.xplane.pb").write_bytes(raw)
    return d


def _covered(intervals):
    """Covered length by a sweep over +1/-1 endpoint events."""
    import numpy as np
    iv = np.asarray(intervals, np.float64)
    pts = np.concatenate([iv[:, 0], iv[:, 1]])
    delta = np.concatenate([np.ones(len(iv)), -np.ones(len(iv))])
    order = np.lexsort((-delta, pts))
    pts, depth = pts[order], np.cumsum(delta[order])
    return float(np.sum(np.diff(pts)[depth[:-1] > 0]))


def test_recorded_trace_against_a_direct_reading(recorded):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(recorded / "tiny.xplane.pb"))
    window = ops = mods = None
    syncs = []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name == "/device:TPU:0":
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines["XLA Ops"].events]
            mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in lines["XLA Modules"].events]
        for line in plane.lines:
            for e in line.events:
                if e.name == tr.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "record_sync":
                    syncs.append(e.duration_ns)
    w0, w1 = window
    inside = [(max(s, w0), min(e, w1)) for s, e in ops if e > w0 and s < w1]
    busy = _covered(inside)
    observe = sum(min(e, w1) - max(s, w0) for s, e, n in mods
                  if n.startswith("jit_observe_all") and e > w0 and s < w1)

    t = tr.load(recorded, n_epochs=3)
    assert t.window_s() == pytest.approx((w1 - w0) * 1e-9, rel=1e-12)
    assert t.busy_s() == pytest.approx(busy * 1e-9, rel=1e-9)
    assert t.module_s("jit_observe_all") == pytest.approx(observe * 1e-9,
                                                          rel=1e-9)
    assert t.span_s("record_sync") == pytest.approx(sum(syncs) * 1e-9,
                                                    rel=1e-9)


def test_recorded_trace_gives_the_recorded_numbers(recorded):
    t = tr.load(recorded, n_epochs=3)
    for name, want in RECORDED.items():
        assert run_cell.load_reader(name)(t) == pytest.approx(want,
                                                              rel=1e-9)
    assert run_cell.load_reader("hint_refresh_ms")(t) is None  # hints off
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        t.window_s() - t.busy_s(), rel=1e-9)
