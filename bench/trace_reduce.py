#!/usr/bin/env python3
"""Reduce one profiler trace of a cell's window to per-layer numbers.

The harness runs the traced window under ``jax.profiler`` with the
runtime's ``repro.obs`` spans mirrored into it as ``TraceAnnotation``s, so
host spans and device events come from one ``.xplane.pb``.  This module
reads it once into a :class:`Trace`:

* the traced window is the harness's ``bench_window`` span;
* device work is the ``XLA Ops`` line of each TPU plane; a program's time
  is the ``XLA Modules`` events named after its jit (``jit_observe_all``,
  ``jit__epoch_step``), and a kernel's time the ops of the Mosaic custom
  calls inside those modules;
* each device op's ``tf_op``: the HLO ``op_name`` path, for example
  ``jit(_epoch_step)/selectk.compact/vmap(jit(searchsorted))/...``, which
  carries the ``jax.named_scope`` of the code that emitted the op.  The
  profiler stores it as a stat of the op's event *metadata*, which
  ``jax.profiler.ProfileData`` does not expose, so :func:`tf_op_paths`
  reads it with a small decoder of the ``XSpace`` wire format.  A fused op
  counts toward the scope of its own ``op_name``: XLA gives a fusion the
  ``op_name`` of one of the instructions it fused;
* every host span of the thread that serves the window, whatever its name:
  the harness's, the runtime's and JAX's own, so a span that a new
  configuration brings is read with no edit here;
* each ``id_upload`` span's start and bytes, and each host-to-device copy
  the TPU runtime saw done (:data:`COPY_DONE`), which time the upload on
  the host's clock;
* busy time is the union of op intervals inside the window, averaged over
  the chips; an idle gap is a stretch of the window in which no op runs,
  attributed to the innermost host span open at its midpoint.

Metric readers in ``bench/metrics/`` take the :class:`Trace` the harness
hands them and return one number, or ``None`` when the trace holds nothing
for them.

Run on a traced window's directory, the module prints the device time per
program and per named scope, the host time per span, the record and upload
lags, and the idle gaps by innermost span, per epoch::

    python3 bench/trace_reduce.py bench_out/trace
"""
from __future__ import annotations

import argparse
import bisect
import json
import math
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["Trace", "load", "union_ns", "tf_op_paths", "WINDOW_SPAN",
           "COPY_DONE"]

WINDOW_SPAN = "bench_window"
# the TPU runtime's event for a host-to-device copy seen done (stat size)
COPY_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
# where bench/run_cell.py writes the traced window
TRACE_DIR = Path(__file__).resolve().parent.parent / "bench_out" / "trace"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# a Mosaic kernel's op: its HLO text names the TPU custom-call target
_KERNEL = 'custom_call_target="tpu_custom_call"'
_TF_OP = "tf_op"


def op_name(hlo: str) -> str:
    """``%fusion.8 = f32[...] fusion(...)`` -> ``fusion.8``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


Interval = Tuple[float, float]


def union_ns(intervals: Iterable[Interval]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    s, e = max(iv[0], w[0]), min(iv[1], w[1])
    return (s, e) if e > s else None


class Trace:
    """Device ops, program executions, op names and host spans of one
    window."""

    def __init__(self, window: Interval, ops: Dict[str, List[tuple]],
                 modules: Dict[str, List[tuple]], spans: List[tuple],
                 n_epochs: int,
                 tf_ops: Optional[Dict[str, Dict[str, str]]] = None,
                 uploads: Tuple[tuple, ...] = (),
                 copies_done: Tuple[tuple, ...] = ()):
        self.window = window
        self.ops = ops            # plane -> [(start, end, name, module)]
        self.modules = modules    # plane -> [(start, end, name)]
        self.spans = spans        # [(start, end, name)], host clock
        self.n_epochs = int(n_epochs)
        self.tf_ops = tf_ops or {}  # plane -> {HLO text -> tf_op path}
        self.uploads = uploads    # [(id_upload start, its bytes arg)]
        self.copies_done = copies_done  # [(COPY_DONE start, size)]

    # ---- time sums (seconds over the window) ----------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals in the window, averaged over the chips."""
        if not self.ops:
            return 0.0
        per = [union_ns(c for c in (_clip((s, e), self.window)
                                    for s, e, *_ in ops) if c)
               for ops in self.ops.values()]
        return sum(per) / len(per) * 1e-9

    def module_s(self, prefix: str) -> float:
        """Device time of the program executions named ``prefix...``,
        summed over the window and averaged over the chips."""
        if not self.modules:
            return 0.0
        per = [sum(c[1] - c[0] for c in (_clip((s, e), self.window)
                                          for s, e, name in mods
                                          if name.startswith(prefix)) if c)
               for mods in self.modules.values()]
        return sum(per) / len(per) * 1e-9

    def kernel_s(self, module_prefix: str) -> float:
        """Device time of the Mosaic custom calls inside the programs named
        ``module_prefix...``, averaged over the chips."""
        if not self.ops:
            return 0.0
        per = [sum(c[1] - c[0] for c in (
            _clip((s, e), self.window) for s, e, name, mod in ops
            if mod.startswith(module_prefix) and _KERNEL in name)
            if c)
            for ops in self.ops.values()]
        return sum(per) / len(per) * 1e-9

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
            per.append(union_ns(
                c for c in (_clip((s, e), self.window)
                            for s, e, name, mod in ops
                            if mod.startswith(module_prefix)
                            and scope in paths.get(name, "").split("/"))
                if c))
        return sum(per) / len(per) * 1e-9

    def span_s(self, name: str) -> Optional[float]:
        """Host time inside spans named ``name``; None if there are none."""
        hits = [c for c in (_clip((s, e), self.window)
                            for s, e, n in self.spans if n == name) if c]
        if not hits:
            return None
        return sum(e - s for s, e in hits) * 1e-9

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

    # ---- idle gaps and the breakdown ------------------------------------
    def gaps(self) -> List[Interval]:
        """Stretches of the window in which no op ran on the first chip."""
        if not self.ops:
            return [self.window]
        plane = sorted(self.ops)[0]
        ivs = sorted(c for c in (_clip((s, e), self.window)
                                 for s, e, *_ in self.ops[plane]) if c)
        out, t = [], self.window[0]
        for s, e in ivs:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def _span_order(self) -> List[tuple]:
        return sorted((s, -e, i, name)
                      for i, (s, e, name) in enumerate(self.spans))

    @staticmethod
    def _innermost(order: List[tuple], t: float) -> str:
        # the open span with the greatest (start, -end, index)
        for j in range(bisect.bisect_right(order, (t, math.inf)) - 1, -1,
                       -1):
            if -order[j][1] > t:
                return order[j][3]
        return "outside spans"

    def host_activity(self, t: float) -> str:
        """Innermost host span open at time ``t``: of the spans open then,
        the one that started last (of two that started together, the one
        that ends first, then the one recorded later)."""
        return self._innermost(self._span_order(), t)

    def self_times(self) -> Dict[str, float]:
        """Seconds per op on the first chip, less the ops nested inside it
        (a while loop's body runs inside the while op), keyed
        ``module/op``."""
        if not self.ops:
            return {}
        plane = sorted(self.ops)[0]
        ivs = sorted((c[0], -c[1], f"{mod.split('(')[0]}/{op_name(name)}")
                     for c, name, mod in (
                         (_clip((s, e), self.window), name, mod)
                         for s, e, name, mod in self.ops[plane]) if c)
        out: Dict[str, float] = defaultdict(float)
        stack: List[list] = []      # [end, key, nested time, start]
        for s, neg_e, key in ivs:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                self._pop(stack, out)
            if stack:
                stack[-1][2] += e - s
            stack.append([e, key, 0.0, s])
        while stack:
            self._pop(stack, out)
        return dict(out)

    @staticmethod
    def _pop(stack: List[list], out: Dict[str, float]) -> None:
        e, key, child, s = stack.pop()
        out[key] += (e - s - child) * 1e-9

    def breakdown(self) -> dict:
        """Top device ops by self time, and idle time by host activity."""
        op_time = self.self_times()
        order = self._span_order()
        idle: Dict[str, float] = defaultdict(float)
        for s, e in self.gaps():
            idle[self._innermost(order, (s + e) / 2)] += (e - s) * 1e-9
        top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


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
        if not _DEVICE_PLANE.match(name):
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


# ---- reading a trace file ---------------------------------------------------
def _events(line):
    for ev in line.events:
        yield float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name


def _device_plane(plane, ops: dict, modules: dict) -> None:
    lines = {line.name: line for line in plane.lines}
    mods = sorted(_events(lines["XLA Modules"])) \
        if "XLA Modules" in lines else []
    starts = [m[0] for m in mods]
    plane_ops = []
    if "XLA Ops" in lines:
        for s, e, name in _events(lines["XLA Ops"]):
            j = bisect.bisect_right(starts, s) - 1
            mod = mods[j][2] if j >= 0 and s < mods[j][1] else ""
            plane_ops.append((s, e, name, mod))
    if plane_ops or mods:
        ops[plane.name] = plane_ops
        modules[plane.name] = mods


def load(trace_dir: Path, n_epochs: int) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    raw = files[-1].read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    ops: Dict[str, List[tuple]] = {}
    modules: Dict[str, List[tuple]] = {}
    uploads, copies = [], []
    window = serving = None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            _device_plane(plane, ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = []
                for ev in line.events:
                    s = float(ev.start_ns)
                    events.append((s, s + ev.duration_ns, ev.name))
                    if ev.name == WINDOW_SPAN:
                        window, serving = events[-1][:2], events
                    elif ev.name == "id_upload":
                        nbytes = dict(ev.stats).get("bytes")
                        if nbytes is not None:
                            uploads.append((s, int(nbytes)))
                    elif ev.name == COPY_DONE:
                        size = dict(ev.stats).get("size")
                        if size is not None:
                            copies.append((s, int(size)))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    # the serving thread's spans: those of the line that holds the window
    spans = [(s, e, name) for s, e, name in serving
             if name != WINDOW_SPAN and s < window[1] and e > window[0]]
    return Trace(window, ops, modules, spans, n_epochs, tf_op_paths(raw),
                 tuple(uploads), tuple(copies))


# ---- command line -----------------------------------------------------------
def per_epoch_ms(seconds: Optional[float], trace: Trace) -> Optional[float]:
    """Seconds over the window -> milliseconds per epoch; None for none."""
    if not seconds:
        return None
    return seconds / trace.n_epochs * 1e3


def scopes(trace: Trace) -> Dict[str, List[str]]:
    """The named scopes in the ops' ``tf_op`` paths, by program: the path
    components that hold a ``.`` and no call, such as
    ``selectk.compact``."""
    found: Dict[str, set] = defaultdict(set)
    for plane, ops in trace.ops.items():
        paths = trace.tf_ops.get(plane, {})
        for _, _, name, mod in ops:
            for part in paths.get(name, "").split("/"):
                if "." in part and "(" not in part and not part.endswith(":"):
                    found[mod.split("(")[0]].add(part)
    return {mod: sorted(names) for mod, names in sorted(found.items())}


def summary(t: Trace) -> dict:
    """Per-epoch milliseconds of every program, scope and span, each record
    pull's and id upload's lag, and the idle time by innermost span."""
    programs = sorted({name.split("(")[0] for mods in t.modules.values()
                       for _, _, name in mods})
    return {
        "epochs": t.n_epochs,
        "scopes_ms": {module: {scope: per_epoch_ms(t.scope_s(scope, module),
                                                   t)
                               for scope in names}
                      for module, names in scopes(t).items()},
        "modules_ms": {module: per_epoch_ms(t.module_s(module), t)
                       for module in programs},
        "spans_ms": {name: per_epoch_ms(t.span_s(name), t)
                     for name in sorted({n for _, _, n in t.spans})},
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
