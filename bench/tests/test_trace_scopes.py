"""Named scopes and the runtime's finer spans: the ``tf_op`` decoder,
``ScopedTrace`` and the six readers, on hand-made traces and on traces
recorded on one TPU v5e."""
import gzip
from pathlib import Path

import pytest

import run_cell
import trace_reduce as tr
import trace_scopes as ts

DATA = Path(__file__).resolve().parent / "data"
READERS = ("select_compact_ms", "plan_compact_ms", "hint_detector_ms",
           "hint_lookahead_ms", "id_upload_ms", "record_lag_ms")


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
    assert ts.tf_op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = s32[8] fusion(...)": "jit(f)/selectk.compact/gather:",
        "%sort.2 = s32[8] sort(...)": "jit(f)/selectk.order/sort:"}}


def test_tf_op_paths_refuses_a_truncated_buffer():
    device = _plane("/device:TPU:0", {
        10: ("%f = s32[8] fusion()", [_msg((1, 1), (5, "jit(f)/a/b"))])},
        {1: "tf_op"})
    raw = _msg((1, device))
    with pytest.raises(ValueError):
        ts.tf_op_paths(raw[:-3])


# ---- ScopedTrace on hand-made intervals ------------------------------------
def _synthetic():
    # window [0, 100) ns, two epochs; a while loop (20-40) whose body ops
    # run inside it, all in selectk.compact: counted once, 20 ns
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
    return ts.ScopedTrace((0.0, 100.0), ops, modules, spans, 2, tf_ops,
                          uploads, copies)


def test_scope_time_counts_a_loop_and_its_body_once():
    t = _synthetic()
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
    t = _synthetic()
    assert t.span_s("id_upload") == pytest.approx(6e-9)
    assert t.span_s("hints.detector") == pytest.approx(2.5e-9)
    assert t.span_s("hint_ranks") is None
    # each record_sync's end minus the last op end at or before it:
    # 20 - 10, 52 - 48, 80 - 75, 100 - 75 (the op ending at 110 is later)
    assert t.record_lags_s() == pytest.approx(
        [10e-9, 4e-9, 5e-9, 25e-9])
    # each id_upload's start to its bytes' copy seen done: 7 - 4, 62 - 55
    assert t.upload_lags_s() == pytest.approx([3e-9, 7e-9])


def test_idle_goes_to_the_finest_span():
    t = _synthetic()
    assert t.host_activity(50) == "record_wait"
    assert t.host_activity(2) == "hints.detector"
    assert t.host_activity(56) == "id_upload"
    assert t.host_activity(85) == "outside spans"
    idle = dict(t.breakdown()["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(t.window_s() - t.busy_s())
    assert idle["hints.detector"] == pytest.approx(5e-9)   # gap 0-5


def test_readers_on_the_synthetic_trace():
    t = _synthetic()
    read = {name: run_cell.load_reader(name)(t) for name in READERS}
    assert read == pytest.approx({
        "select_compact_ms": 25e-9 / 2 * 1e3,
        "plan_compact_ms": 6e-9 / 2 * 1e3,
        "hint_detector_ms": 2.5e-9 / 2 * 1e3,
        "hint_lookahead_ms": 0.5e-9 / 2 * 1e3,
        "id_upload_ms": 10e-9 / 2 * 1e3,
        "record_lag_ms": 11e-9 * 1e3})


def test_readers_find_nothing_without_names():
    empty = ts.ScopedTrace((0.0, 100.0), {}, {}, [], 1, {})
    for name in READERS:
        assert run_cell.load_reader(name)(empty) is None


# ---- of(): the harness hands readers a plain Trace --------------------------
@pytest.fixture(scope="module")
def recorded_dirs(tmp_path_factory):
    """A directory per recorded trace, unpacked on first use."""
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


def test_of_reads_the_same_window_again(recorded_dirs, tmp_path, capsys):
    d = recorded_dirs("tiny_mmap")
    plain = tr.load(d, n_epochs=3)
    scoped = ts.of(plain, trace_dir=d)
    assert isinstance(scoped, ts.ScopedTrace)
    assert scoped.window == plain.window and scoped.n_epochs == 3
    assert ts.of(scoped) is scoped
    assert capsys.readouterr().err == ""
    assert ts.of(plain, trace_dir=tmp_path) is None         # no trace file
    assert "no scoped trace under" in capsys.readouterr().err
    other = tr.Trace((0.0, 1.0), {}, {}, [], n_epochs=3)
    assert ts.of(other, trace_dir=d) is None                # another window
    assert "not the one read" in capsys.readouterr().err


# ---- a trace of the program before its scopes (tiny_mmap) -------------------
def test_unscoped_program_gives_tf_ops_but_no_scopes(recorded_dirs):
    d = recorded_dirs("tiny_mmap")
    t = ts.load(d, n_epochs=3)
    paths = t.tf_ops["/device:TPU:0"]
    assert any(p.startswith("jit(_epoch_step)/") for p in paths.values())
    assert any(p.startswith("jit(observe_all)/") for p in paths.values())
    for module, scopes in ts.SCOPES.items():
        for scope in scopes:
            assert t.scope_s(scope, module) == 0.0
    read = {name: run_cell.load_reader(name)(t) for name in READERS}
    assert {k for k, v in read.items() if v is not None} == {"record_lag_ms"}
    # the old readers read what they read before
    assert run_cell.load_reader("record_sync_ms")(t) == pytest.approx(
        18.388016, rel=1e-9)


# ---- a trace of the scoped program (tiny_dlrm) -----------------------------
def test_scoped_program_names_its_work(recorded_dirs):
    d = recorded_dirs("tiny_dlrm")
    t = ts.load(d, n_epochs=1)
    for module, scopes in ts.SCOPES.items():
        for scope in scopes:
            assert t.scope_s(scope, module) > 0.0, scope
        within = sum(t.scope_s(s, module) for s in scopes)
        assert within <= t.module_s(module) * (1 + 1e-9)
    for name in ts.SPANS[1:]:
        if name not in ("hint_ranks", "hint_set"):       # harness spans
            assert t.span_s(name) is not None, name


def test_scoped_readers_against_a_direct_reading(recorded_dirs):
    from jax.profiler import ProfileData
    d = recorded_dirs("tiny_dlrm")
    t = ts.load(d, n_epochs=1)
    n = sum(1 for s, e, name in t.spans if name == "record_sync"
            and t.window[0] < e <= t.window[1])
    t.n_epochs = n
    data = ProfileData.from_file(str(next(d.glob("*.xplane.pb"))))
    ups, done = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if (e.name == "id_upload" and t.window[0] <= e.start_ns
                        and e.start_ns + e.duration_ns <= t.window[1]):
                    assert stats["bytes"] > 0 and "epoch" in stats
                    ups.append((e.start_ns, stats["bytes"]))
                elif e.name == ts.COPY_DONE:
                    done.append((e.start_ns, stats["size"]))
    lags = [min(d for d, size in done if size == b and d >= u) - u
            for u, b in ups]
    read = {name: run_cell.load_reader(name)(t) for name in READERS}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert len(lags) == n
    assert read["id_upload_ms"] == pytest.approx(
        sum(lags) / n * 1e-9 * 1e3, rel=1e-9)
    assert read["select_compact_ms"] + read["plan_compact_ms"] <= \
        run_cell.load_reader("epoch_step_ms")(t)
