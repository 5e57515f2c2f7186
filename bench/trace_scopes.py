#!/usr/bin/env python3
"""What the program names inside itself, read from a cell's trace.

``trace_reduce`` reads device ops by their HLO text and the harness's own
host spans.  This module adds the names the program puts on its work:

* each device op's ``tf_op``: the HLO ``op_name`` path, for example
  ``jit(_epoch_step)/selectk.compact/vmap(jit(searchsorted))/...``, which
  carries the ``jax.named_scope`` of the code that emitted the op.  The
  profiler stores it as a stat of the op's event *metadata*, which
  ``jax.profiler.ProfileData`` does not expose, so :func:`tf_op_paths`
  reads it with a small decoder of the ``XSpace`` wire format;
* the runtime's spans below the harness's (:data:`SPANS`);
* each host-to-device copy the TPU runtime saw done (:data:`COPY_DONE`),
  which times the ``id_upload`` span's copy on the host's clock.

A fused op counts toward the scope of its own ``op_name``: XLA gives a
fusion the ``op_name`` of one of the instructions it fused.

Metric readers call :func:`of` with the :class:`trace_reduce.Trace` the
harness hands them; tests build a :class:`ScopedTrace` with :func:`load`.
Without a trace file, or with one that holds none of these names (a
program from before the scopes), a reader finds nothing and returns None.

Run on a traced window's directory, the module prints the device time
per scope, the runtime's spans and the idle gaps by finest span, per
epoch::

    python3 bench/trace_scopes.py bench_out/trace
"""
from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402

__all__ = ["SPANS", "SCOPES", "ScopedTrace", "load", "of", "tf_op_paths"]

# where bench/run_cell.py writes the traced window
TRACE_DIR = Path(__file__).resolve().parent.parent / "bench_out" / "trace"

# host spans, outermost first: the harness's, the runtime's, and the
# runtime's finer spans inside them (an idle gap goes to the innermost)
SPANS = ("served_epoch", "hints", "hint_ranks", "hints.detector",
         "hints.lookahead", "hint_set", "hint_refresh", "id_upload",
         "observe_all", "epoch_step", "record_sync", "record_wait",
         "record_pull", "record_assembly")
# the program's named scopes, by the jitted program they sit in
SCOPES = {
    "jit_observe_all": ("telemetry.true", "telemetry.hmu", "telemetry.pebs",
                        "telemetry.nb"),
    "jit__epoch_step": ("selectk.threshold", "selectk.mask",
                        "selectk.compact", "selectk.order",
                        "placement.free_slots"),
}
_TF_OP = "tf_op"
# the TPU runtime's event for a host-to-device copy seen done (stat size)
COPY_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"


# ---- XSpace wire format ----------------------------------------------------
def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None):
    """``(field, value)`` for each field of the message in
    ``buf[start:end]``; a length-delimited value is its ``(start, end)``."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        if pos > end:
            raise ValueError("field runs past its message")
        yield field, val


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, entries: List[Tuple[int, int]]):
    """Values of protobuf map entries (key 1, value 2)."""
    for s, e in entries:
        for field, val in _fields(buf, s, e):
            if field == 2:
                yield val


def tf_op_paths(raw: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane: {op's HLO text: tf_op path}}`` from a serialized
    ``XSpace``: XSpace.planes (1) -> XPlane name (2), event_metadata (4),
    stat_metadata (5); XEventMetadata name (2), stats (5); XStat
    metadata_id (1), str_value (5), ref_value (7)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for f, val in _fields(raw, *plane):
            if f == 2:
                name = _text(raw, val)
            elif f == 4:
                events.append(val)
            elif f == 5:
                stats.append(val)
        if not trace_reduce._DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for meta in _map_values(raw, stats):
            sid, sname = None, ""
            for f, val in _fields(raw, *meta):
                if f == 1:
                    sid = val
                elif f == 2:
                    sname = _text(raw, val)
            stat_names[sid] = sname
        paths: Dict[str, str] = {}
        for meta in _map_values(raw, events):
            ename, path = "", None
            for f, val in _fields(raw, *meta):
                if f == 2:
                    ename = _text(raw, val)
                elif f == 5:
                    path = _tf_op_stat(raw, val, stat_names) or path
            if path is not None:
                paths[ename] = path
        out[name] = paths
    return out


def _tf_op_stat(raw: bytes, stat: Tuple[int, int],
                stat_names: Dict[int, str]) -> Optional[str]:
    mid, value = None, None
    for f, val in _fields(raw, *stat):
        if f == 1:
            mid = val
        elif f == 5:
            value = _text(raw, val)
        elif f == 7:
            value = stat_names.get(val)
    return value if stat_names.get(mid) == _TF_OP else None


# ---- the scoped trace ------------------------------------------------------
class ScopedTrace(trace_reduce.Trace):
    """A :class:`trace_reduce.Trace` whose host spans are those named in
    :data:`SPANS`, with each device op's ``tf_op`` path; its breakdown
    gives idle gaps to the finest span open."""

    def __init__(self, window, ops, modules, spans, n_epochs,
                 tf_ops: Dict[str, Dict[str, str]],
                 uploads: Tuple[tuple, ...] = (),
                 copies_done: Tuple[tuple, ...] = ()):
        super().__init__(window, ops, modules, spans, n_epochs)
        self.tf_ops = tf_ops      # plane -> {HLO text -> tf_op path}
        self.uploads = uploads    # [(id_upload start, its bytes arg)]
        self.copies_done = copies_done  # [(COPY_DONE start, size)]

    def scope_s(self, scope: str, module_prefix: str) -> float:
        """Device time of the ops inside the programs named
        ``module_prefix...`` whose ``tf_op`` path holds the component
        ``scope`` (a while loop and the body ops it runs count once),
        averaged over the chips."""
        if not self.ops:
            return 0.0
        per = []
        for plane, ops in self.ops.items():
            paths = self.tf_ops.get(plane, {})
            per.append(trace_reduce.union_ns(
                c for c in (trace_reduce._clip((s, e), self.window)
                            for s, e, name, mod in ops
                            if mod.startswith(module_prefix)
                            and scope in paths.get(name, "").split("/"))
                if c))
        return sum(per) / len(per) * 1e-9

    def record_lags_s(self) -> List[float]:
        """For each ``record_sync`` span ending in the window: its end
        minus the end of the last device op that ended before it (the
        records' time on the way to the host after the device finished),
        averaged over the chips."""
        ends = [sorted(e for _, e, *_ in ops) for ops in self.ops.values()]
        lags = []
        for _, t, name in self.spans:
            if name != "record_sync" or not (
                    self.window[0] < t <= self.window[1]):
                continue
            per = [t - plane[j] for plane in ends
                   for j in [bisect.bisect_right(plane, t) - 1] if j >= 0]
            if per:
                lags.append(sum(per) / len(per) * 1e-9)
        return lags

    def upload_lags_s(self) -> List[float]:
        """For each ``id_upload`` span starting in the window: the first
        host-to-device copy of its ``bytes`` that the TPU runtime saw
        done after the span's start, minus that start.  ``device_put``
        returns before the copy ends; this holds the copy.  Both ends are
        on the host's clock."""
        lags = []
        for s, nbytes in self.uploads:
            if not self.window[0] <= s < self.window[1]:
                continue
            done = [t for t, size in self.copies_done
                    if size == nbytes and t >= s]
            if done:
                lags.append((min(done) - s) * 1e-9)
        return lags

    def host_activity(self, t: float) -> str:
        """Innermost span of :data:`SPANS` open at time ``t``."""
        best, depth = "outside spans", -1
        for s, e, name in self.spans:
            if s <= t < e and SPANS.index(name) > depth:
                best, depth = name, SPANS.index(name)
        return best


def _newest(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _host_events(path: Path) -> Tuple[list, list, list]:
    """The spans of :data:`SPANS`, each ``id_upload``'s start and bytes,
    and each host-to-device copy seen done, from the host planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, uploads, copies = [], [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                if ev.name in SPANS:
                    spans.append((start, start + ev.duration_ns, ev.name))
                    if ev.name == "id_upload":
                        nbytes = dict(ev.stats).get("bytes")
                        if nbytes is not None:
                            uploads.append((start, int(nbytes)))
                elif ev.name == COPY_DONE:
                    size = dict(ev.stats).get("size")
                    if size is not None:
                        copies.append((start, int(size)))
    return spans, uploads, copies


def load(trace_dir: Path, n_epochs: int) -> ScopedTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``, as
    :func:`trace_reduce.load` does, with the names above."""
    path = _newest(trace_dir)
    base = trace_reduce.load(trace_dir, n_epochs)
    spans, uploads, copies = _host_events(path)
    return ScopedTrace(base.window, base.ops, base.modules, spans, n_epochs,
                       tf_op_paths(path.read_bytes()), uploads, copies)


# the last trace read again, for the six readers of one run
_LAST: Dict[tuple, ScopedTrace] = {}


def of(trace, trace_dir: Path = TRACE_DIR) -> Optional[ScopedTrace]:
    """``trace`` with its names: itself when it is a ScopedTrace, else
    the newest trace under ``trace_dir`` read again, if it is the same
    window.  When there is none, or its window is another, it says so on
    standard error and returns None.

    The harness hands readers a ``trace_reduce.Trace``, which keeps
    neither the file it was read from nor the ops' ``tf_op``; hence this
    second reading, until ``trace_reduce`` carries both."""
    if isinstance(trace, ScopedTrace):
        return trace
    try:
        path = _newest(trace_dir)
        key = (str(path), path.stat().st_mtime_ns, trace.n_epochs)
        if key not in _LAST:
            scoped = load(trace_dir, trace.n_epochs)
            _LAST.clear()
            _LAST[key] = scoped
    except (OSError, ValueError) as exc:
        print(f"trace_scopes: no scoped trace under {trace_dir}: {exc}",
              file=sys.stderr)
        return None
    scoped = _LAST[key]
    if scoped.window != trace.window:
        print(f"trace_scopes: {path} holds the window {scoped.window}, "
              f"not the one read, {trace.window}", file=sys.stderr)
        return None
    return scoped


def per_epoch_ms(seconds: Optional[float], trace) -> Optional[float]:
    """Seconds over the window -> milliseconds per epoch; None for none."""
    if not seconds:
        return None
    return seconds / trace.n_epochs * 1e3


# ---- command line -----------------------------------------------------------
def summary(t: ScopedTrace) -> dict:
    """Per-epoch milliseconds of every scope, program and span, each record
    pull's lag, and the idle time by finest span."""
    return {
        "epochs": t.n_epochs,
        "scopes_ms": {scope: per_epoch_ms(t.scope_s(scope, module), t)
                      for module, scopes in SCOPES.items()
                      for scope in scopes},
        "modules_ms": {module: per_epoch_ms(t.module_s(module), t)
                       for module in SCOPES},
        "spans_ms": {name: per_epoch_ms(t.span_s(name), t)
                     for name in SPANS},
        "record_lags_ms": [lag * 1e3 for lag in t.record_lags_s()],
        "upload_lags_ms": [lag * 1e3 for lag in t.upload_lags_s()],
        "idle_ms": {k: per_epoch_ms(v, t)
                    for k, v in t.breakdown()["idle_gaps"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?", default=str(TRACE_DIR))
    ap.add_argument("--epochs", type=int,
                    help="epochs in the window (default: its served_epoch "
                         "spans, else its record_sync spans)")
    args = ap.parse_args(argv)
    t = load(Path(args.trace_dir), n_epochs=1)
    n = args.epochs
    if n is None:
        for name in ("served_epoch", "record_sync"):
            n = sum(1 for s, e, k in t.spans if k == name
                    and t.window[0] <= s and e <= t.window[1])
            if n:
                break
    t.n_epochs = max(n, 1)
    print(json.dumps(summary(t)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
