"""The trace reduction: the ``tf_op`` decoder, :class:`trace_reduce.Trace`
and every metric reader, on hand-made traces and on traces recorded on
one TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

import run_cell
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
READERS = sorted(p.stem for p in (DATA.parents[1] / "metrics").glob("*.py"))


# ---- the wire decoder on a hand-encoded XSpace -----------------------------
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name, events, stat_names):
    """An XPlane: ``events`` maps metadata id -> (HLO text, [XStat])."""
    parts = [(1, 3), (2, name), (3, _msg((1, 0), (2, "XLA Ops")))]
    for sid, sname in stat_names.items():
        parts.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, (text, stats) in events.items():
        meta = _msg((1, eid), (2, text), (4, text.split(" ")[0]),
                    *[(5, s) for s in stats])
        parts.append((4, _msg((1, eid), (2, meta))))
    return _msg(*parts)


def test_tf_op_paths_reads_str_and_ref_values():
    stats = {1: "tf_op", 2: "flops", 3: "jit(f)/selectk.order/sort:"}
    device = _plane("/device:TPU:0", {
        10: ("%fusion.1 = s32[8] fusion(...)",
             [_msg((1, 2), (3, 99)),
              _msg((1, 1), (5, "jit(f)/selectk.compact/gather:"))]),
        11: ("%sort.2 = s32[8] sort(...)", [_msg((1, 1), (7, 3))]),
        12: ("%copy.3 = s32[8] copy(...)", [_msg((1, 2), (3, 1))]),
    }, stats)
    host = _plane("/host:CPU", {
        1: ("record_sync", [_msg((1, 1), (5, "not a device op"))])}, stats)
    raw = _msg((1, host), (1, device), (2, "/host:CPU"))
    assert tr.tf_op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = s32[8] fusion(...)": "jit(f)/selectk.compact/gather:",
        "%sort.2 = s32[8] sort(...)": "jit(f)/selectk.order/sort:"}}


def test_tf_op_paths_refuses_a_truncated_buffer():
    device = _plane("/device:TPU:0", {
        10: ("%f = s32[8] fusion()", [_msg((1, 1), (5, "jit(f)/a/b"))])},
        {1: "tf_op"})
    raw = _msg((1, device))
    with pytest.raises(ValueError):
        tr.tf_op_paths(raw[:-3])


# ---- hand-made intervals --------------------------------------------------
def test_union_merges_overlaps():
    assert tr.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.union_ns([]) == 0


def _programs():
    """Window [0, 100) ns, two epochs: the two programs, a Mosaic kernel
    and the harness's and runtime's coarse spans."""
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


def _scopes():
    """Window [0, 100) ns, two epochs: a while loop (20-40) whose body ops
    run inside it, all in selectk.compact (counted once, 20 ns), the
    runtime's finer spans, and the ids' uploads and copies."""
    ops = {"/device:TPU:0": [
        (5, 10, "obs.1", "jit_observe_all(1)"),
        (20, 40, "while.2", "jit__epoch_step(2)"),
        (22, 30, "fusion.3", "jit__epoch_step(2)"),
        (31, 39, "fusion.3", "jit__epoch_step(2)"),
        (42, 48, "fusion.4", "jit__epoch_step(2)"),
        (60, 64, "obs.1", "jit_observe_all(1)"),
        (70, 75, "fusion.5", "jit__epoch_step(2)"),
        (95, 110, "while.2", "jit__epoch_step(2)"),   # clipped at 100
    ]}
    modules = {"/device:TPU:0": [(5, 10, "jit_observe_all(1)"),
                                 (20, 48, "jit__epoch_step(2)"),
                                 (60, 64, "jit_observe_all(1)"),
                                 (70, 75, "jit__epoch_step(2)"),
                                 (95, 110, "jit__epoch_step(2)")]}
    tf_ops = {"/device:TPU:0": {
        "obs.1": "jit(observe_all)/while/body/telemetry.hmu/scatter-add:",
        "while.2": "jit(_epoch_step)/selectk.compact/vmap()/while:",
        "fusion.3": "jit(_epoch_step)/selectk.compact/vmap()/while/body:",
        "fusion.4": "jit(_epoch_step)/placement.free_slots/gather:",
        "fusion.5": "jit(_epoch_step)/selectk.compactness/add:"}}
    spans = [(0, 55, "served_epoch"), (0, 4, "hints"),
             (0.5, 3, "hints.detector"), (3, 3.5, "hints.lookahead"),
             (4, 5, "id_upload"), (10, 20, "record_sync"),
             (48, 52, "record_sync"), (49, 51, "record_wait"),
             (51, 52, "record_pull"), (52, 54, "record_assembly"),
             (55, 60, "id_upload"), (76, 80, "record_sync"),
             (90, 100, "record_sync")]
    # the ids' copies seen done: one before the first upload, one of
    # another size, then one after each upload
    uploads = [(4, 64), (55, 64)]
    copies = [(3, 64), (6, 32), (7, 64), (62, 64)]
    return tr.Trace((0.0, 100.0), ops, modules, spans, 2, tf_ops,
                    uploads, copies)


SYNTHETIC = {"programs": _programs, "scopes": _scopes}


def test_sums_over_the_window():
    t = _programs()
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx((10 + 20 + 10 + 10) * 1e-9)
    assert t.module_s("jit_observe_all") == pytest.approx(20e-9)
    assert t.module_s("jit__epoch_step") == pytest.approx(30e-9)
    assert t.kernel_s("jit__epoch_step") == pytest.approx(5e-9)
    assert t.span_s("record_sync") == pytest.approx(25e-9)
    assert t.span_s("observe_all") is None


def test_scope_time_counts_a_loop_and_its_body_once():
    t = _scopes()
    assert t.scope_s("selectk.compact", "jit__epoch_step") == \
        pytest.approx((20 + 5) * 1e-9)           # 20-40 and 95-100
    assert t.scope_s("placement.free_slots", "jit__epoch_step") == \
        pytest.approx(6e-9)
    assert t.scope_s("telemetry.hmu", "jit_observe_all") == \
        pytest.approx(9e-9)
    # a scope is a whole path component, and the module must match
    assert t.scope_s("selectk.compactness", "jit__epoch_step") == \
        pytest.approx(5e-9)
    assert t.scope_s("telemetry.hmu", "jit__epoch_step") == 0.0
    assert t.scope_s("selectk.order", "jit__epoch_step") == 0.0


def test_spans_and_record_lags():
    t = _scopes()
    assert t.span_s("id_upload") == pytest.approx(6e-9)
    assert t.span_s("hints.detector") == pytest.approx(2.5e-9)
    assert t.span_s("hint_ranks") is None
    # each record_sync's end minus the last op end at or before it:
    # 20 - 10, 52 - 48, 80 - 75, 100 - 75 (the op ending at 110 is later)
    assert t.record_lags_s() == pytest.approx(
        [10e-9, 4e-9, 5e-9, 25e-9])
    # each id_upload's start to its bytes' copy seen done: 7 - 4, 62 - 55
    assert t.upload_lags_s() == pytest.approx([3e-9, 7e-9])


@pytest.mark.parametrize("synthetic, t, want", [
    ("programs", 4, "hint_ranks"),          # opened with served_epoch
    ("programs", 7.5, "hint_refresh"),
    ("programs", 25, "record_sync"),
    ("programs", 52, "served_epoch"),
    ("scopes", 50, "record_wait"),
    ("scopes", 2, "hints.detector"),
    ("scopes", 56, "id_upload"),
    ("scopes", 85, "outside spans"),
])
def test_innermost_open_span(synthetic, t, want):
    assert SYNTHETIC[synthetic]().host_activity(t) == want


def test_gaps_go_to_the_innermost_open_span():
    t = _programs()
    assert t.gaps() == [(0.0, 10), (20, 30), (50, 60), (70, 90)]
    b = t.breakdown()
    idle = dict(b["idle_gaps"])
    assert idle == pytest.approx({"hint_ranks": 10e-9,
                                  "served_epoch": 10e-9,
                                  "record_sync": 30e-9})
    assert b["device_ops"][0] == ["jit__epoch_step/fusion.2",
                                  pytest.approx(15e-9)]
    assert t.self_times()["jit__epoch_step/kth_key_u.3"] == \
        pytest.approx(5e-9)


def test_idle_goes_to_the_finest_span():
    t = _scopes()
    idle = dict(t.breakdown()["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(t.window_s() - t.busy_s())
    assert idle["hints.detector"] == pytest.approx(5e-9)   # gap 0-5


@pytest.mark.parametrize("synthetic, name, want", [
    # providers (6) plus hand-over (2), not the upload nested in it again
    ("programs", "hint_refresh_ms", 8e-9 / 2 * 1e3),
    ("programs", "observe_ms", 20e-9 / 2 * 1e3),
    ("programs", "epoch_step_ms", 30e-9 / 2 * 1e3),
    ("programs", "select_kernel_ms", 5e-9 / 2 * 1e3),
    ("programs", "record_sync_ms", 25e-9 / 2 * 1e3),
    ("programs", "device_idle_pct", 50.0),
    ("scopes", "select_compact_ms", 25e-9 / 2 * 1e3),
    ("scopes", "plan_compact_ms", 6e-9 / 2 * 1e3),
    ("scopes", "hint_detector_ms", 2.5e-9 / 2 * 1e3),
    ("scopes", "hint_lookahead_ms", 0.5e-9 / 2 * 1e3),
    ("scopes", "id_upload_ms", 10e-9 / 2 * 1e3),
    ("scopes", "record_lag_ms", 11e-9 * 1e3),
])
def test_readers_on_the_synthetic_trace(synthetic, name, want):
    got = run_cell.load_reader(name)(SYNTHETIC[synthetic]())
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_an_empty_trace(name):
    t = tr.Trace((0.0, 100.0), {}, {}, [], n_epochs=1)
    assert run_cell.load_reader(name)(t) is None


# ---- a trace file with a span that no list names ----------------------------
_UNLISTED = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 16000 }
    events { metadata_id: 4 offset_ps: 40000 duration_ps: 35000 }
    events { metadata_id: 2 offset_ps: 120000 duration_ps: 5000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 75000 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "tenancy.caps" } }
  event_metadata { key: 3 value { id: 3 name: "tenancy.accounting" } }
  event_metadata { key: 4 value { id: 4 name: "hints" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 20000 duration_ps: 30000 }
    events { metadata_id: 1 offset_ps: 90000 duration_ps: 10000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 30000 }
    events { metadata_id: 2 offset_ps: 90000 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "jit_f(1)" } }
}
'''


def test_a_span_in_no_list_is_read_and_gets_its_idle_gap(tmp_path):
    """``tenancy.caps`` and ``tenancy.accounting`` are named nowhere in
    the harness: the trace keeps them, ``span_s`` reads them, and idle
    gaps go to them.  The window is [1000, 1100) ns; the second
    ``tenancy.caps`` lies outside it and the ``worker`` thread's span is
    not the serving thread's."""
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(_UNLISTED)
    (tmp_path / "host.xplane.pb").write_bytes(raw)
    t = tr.load(tmp_path, n_epochs=1)
    assert t.window == (1000.0, 1100.0)
    assert {name for _, _, name in t.spans} == {
        "tenancy.caps", "tenancy.accounting", "hints"}
    assert t.span_s("tenancy.caps") == pytest.approx(40e-9)
    assert t.span_s("tenancy.accounting") == pytest.approx(16e-9)
    # device idle 1000-1020 (in tenancy.accounting at 1010) and 1050-1090
    # (in hints at 1070); none after the last op
    assert t.gaps() == [(1000.0, 1020.0), (1050.0, 1090.0)]
    assert dict(t.breakdown()["idle_gaps"]) == pytest.approx(
        {"tenancy.accounting": 20e-9, "hints": 40e-9})


# ---- traces recorded on one TPU v5e ----------------------------------------
# tiny_mmap: mmap_paper.scan cut to 65,536 blocks, three traced epochs, a
# program from before the named scopes; tiny_dlrm: dlrm_paper.hinted cut
# small, 14 traced epochs, the scoped program with the runtime's spans
RECORDED_EPOCHS = {"tiny_mmap": 3, "tiny_dlrm": 14}
# every reader's value on them, as the harness read it before the trace
# object carried every span (None: the trace holds nothing for it)
PARENT_VALUES = {
    "tiny_mmap": {
        "device_idle_pct": 13.240577418719301,
        "epoch_step_ms": 11.924393333333333,
        "hint_detector_ms": None, "hint_lookahead_ms": None,
        "hint_refresh_ms": None, "id_upload_ms": None,
        "observe_ms": 6.308214, "plan_compact_ms": None,
        "record_lag_ms": 1.0222356666666668,
        "record_sync_ms": 18.388016, "select_compact_ms": None,
        "select_kernel_ms": 0.294527},
    "tiny_dlrm": {
        "device_idle_pct": 57.870869553040414,
        "epoch_step_ms": 3.212789571428572,
        "hint_detector_ms": 0.15109578571428572,
        "hint_lookahead_ms": 0.2343007142857143,
        "hint_refresh_ms": 0.9052855714285716,
        "id_upload_ms": 1.0107828571428572,
        "observe_ms": 1.4170666428571428,
        "plan_compact_ms": 0.9590367142857144,
        "record_lag_ms": 4.901414214285714,
        "record_sync_ms": 7.001987857142858,
        "select_compact_ms": 1.0946978571428574,
        "select_kernel_ms": 0.0967367857142857},
}
# the host spans a trace of the scoped program holds, outermost first, as
# the harness and the runtime nest them (the order the breakdown used
# before it went by containment), and the program's named scopes
NESTED_SPANS = ("served_epoch", "hints", "hint_ranks", "hints.detector",
                "hints.lookahead", "hint_set", "hint_refresh", "id_upload",
                "observe_all", "epoch_step", "record_sync", "record_wait",
                "record_pull", "record_assembly")
SCOPES = {
    "jit_observe_all": ("telemetry.true", "telemetry.hmu", "telemetry.pebs",
                        "telemetry.nb"),
    "jit__epoch_step": ("selectk.threshold", "selectk.mask",
                        "selectk.compact", "selectk.order",
                        "placement.free_slots"),
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The directory of a recorded trace, unpacked on first use."""
    dirs = {}

    def unpack(name):
        if name not in dirs:
            d = tmp_path_factory.mktemp(name)
            raw = gzip.decompress(
                (DATA / f"{name}.xplane.pb.gz").read_bytes())
            (d / f"{name}.xplane.pb").write_bytes(raw)
            dirs[name] = d
        return dirs[name]
    return unpack


def _load(recorded, name):
    return tr.load(recorded(name), n_epochs=RECORDED_EPOCHS[name])


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
    d = recorded("tiny_mmap")
    data = ProfileData.from_file(str(d / "tiny_mmap.xplane.pb"))
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

    t = tr.load(d, n_epochs=3)
    assert t.window_s() == pytest.approx((w1 - w0) * 1e-9, rel=1e-12)
    assert t.busy_s() == pytest.approx(busy * 1e-9, rel=1e-9)
    assert t.module_s("jit_observe_all") == pytest.approx(observe * 1e-9,
                                                          rel=1e-9)
    assert t.span_s("record_sync") == pytest.approx(sum(syncs) * 1e-9,
                                                    rel=1e-9)


@pytest.mark.parametrize("name, reader", [
    (name, reader) for name, values in PARENT_VALUES.items()
    for reader in values])
def test_recorded_readers_give_the_parents_values(recorded, name, reader):
    """To the last digit: the trace object changed, the numbers did not."""
    got = run_cell.load_reader(reader)(_load(recorded, name))
    assert got == PARENT_VALUES[name][reader]


@pytest.mark.parametrize("name", sorted(RECORDED_EPOCHS))
def test_recorded_breakdown(recorded, name):
    t = _load(recorded, name)
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        t.window_s() - t.busy_s(), rel=1e-9)


@pytest.mark.parametrize("name", sorted(RECORDED_EPOCHS))
def test_containment_gives_the_nesting_order(recorded, name):
    """Over the spans the harness and the runtime nest, the innermost span
    by containment is the one deepest in their nesting order, at every
    idle gap."""
    full = _load(recorded, name)
    t = tr.Trace(full.window, full.ops, full.modules,
                 [s for s in full.spans if s[2] in NESTED_SPANS], 1)
    gaps = t.gaps()
    assert len(gaps) > 100
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [n for a, b, n in t.spans if a <= mid < b]
        want = (max(open_, key=NESTED_SPANS.index) if open_
                else "outside spans")
        assert t.host_activity(mid) == want


def test_unscoped_program_gives_tf_ops_but_no_scopes(recorded):
    t = _load(recorded, "tiny_mmap")
    paths = t.tf_ops["/device:TPU:0"]
    assert any(p.startswith("jit(_epoch_step)/") for p in paths.values())
    assert any(p.startswith("jit(observe_all)/") for p in paths.values())
    for module, scopes in SCOPES.items():
        for scope in scopes:
            assert t.scope_s(scope, module) == 0.0
    assert tr.scopes(t) == {}


def test_scoped_program_names_its_work(recorded):
    t = _load(recorded, "tiny_dlrm")
    for module, scopes in SCOPES.items():
        for scope in scopes:
            assert t.scope_s(scope, module) > 0.0, scope
        within = sum(t.scope_s(s, module) for s in scopes)
        assert within <= t.module_s(module) * (1 + 1e-9)
        assert set(scopes) <= set(tr.scopes(t)[module])
    for name in NESTED_SPANS:
        assert t.span_s(name) is not None, name


def test_scoped_readers_against_a_direct_reading(recorded):
    from jax.profiler import ProfileData
    d = recorded("tiny_dlrm")
    t = _load(recorded, "tiny_dlrm")
    n = sum(1 for s, e, name in t.spans if name == "record_sync"
            and t.window[0] < e <= t.window[1])
    assert n == t.n_epochs
    data = ProfileData.from_file(str(next(d.glob("*.xplane.pb"))))
    ups, done = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                inside = (t.window[0] <= e.start_ns
                          and e.start_ns + e.duration_ns <= t.window[1])
                if e.name == "id_upload" and inside:
                    assert stats["bytes"] > 0 and "epoch" in stats
                    ups.append((e.start_ns, stats["bytes"]))
                elif e.name == tr.COPY_DONE:
                    done.append((e.start_ns, stats["size"]))
    lags = [min(d for d, size in done if size == b and d >= u) - u
            for u, b in ups]
    read = {name: run_cell.load_reader(name)(t) for name in (
        "select_compact_ms", "plan_compact_ms", "hint_detector_ms",
        "hint_lookahead_ms", "id_upload_ms", "record_lag_ms",
        "epoch_step_ms")}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert len(lags) == n
    assert read["id_upload_ms"] == pytest.approx(
        sum(lags) / n * 1e-9 * 1e3, rel=1e-9)
    assert read["select_compact_ms"] + read["plan_compact_ms"] <= \
        read["epoch_step_ms"]


def test_command_line_prints_the_window_per_epoch(recorded, capsys):
    assert tr.main([str(recorded("tiny_dlrm"))]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["epochs"] == RECORDED_EPOCHS["tiny_dlrm"]
    t = _load(recorded, "tiny_dlrm")
    assert out["modules_ms"]["jit__epoch_step"] == pytest.approx(
        PARENT_VALUES["tiny_dlrm"]["epoch_step_ms"])
    assert out["scopes_ms"]["jit__epoch_step"]["selectk.compact"] == \
        pytest.approx(PARENT_VALUES["tiny_dlrm"]["select_compact_ms"])
    assert set(NESTED_SPANS) <= set(out["spans_ms"])
    assert len(out["record_lags_ms"]) == len(t.record_lags_s())
    assert sum(out["idle_ms"].values()) == pytest.approx(
        (t.window_s() - t.busy_s()) / t.n_epochs * 1e3)
