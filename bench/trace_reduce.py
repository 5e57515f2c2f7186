"""Reduce one profiler trace of a cell's window to per-layer numbers.

The harness runs the traced window under ``jax.profiler`` with the
runtime's ``repro.obs`` spans mirrored into it as ``TraceAnnotation``s, so
host spans and device events come from one ``.xplane.pb``.  This module
reads it with ``jax.profiler.ProfileData`` and nothing else:

* the traced window is the harness's ``bench_window`` span;
* device work is the ``XLA Ops`` line of each TPU plane; a program's time
  is the ``XLA Modules`` events named after its jit (``jit_observe_all``,
  ``jit__epoch_step``), and a kernel's time the ops of the Mosaic custom
  calls inside those modules;
* busy time is the union of op intervals inside the window, averaged over
  the chips; an idle gap is a stretch of the window in which no op runs,
  attributed to the innermost host span open at its midpoint.

Metric readers in ``bench/metrics/`` take a :class:`Trace` and return one
number, or ``None`` when the trace holds nothing for them.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["Trace", "load", "union_ns", "WINDOW_SPAN", "HOST_SPANS"]

WINDOW_SPAN = "bench_window"
# host spans, outermost first: the harness's per-epoch span and its spans
# around the hint providers (``hint_ranks``) and the rank hand-over
# (``hint_set``), then the runtime's own repro.obs spans
HOST_SPANS = ("served_epoch", "hint_ranks", "hint_set", "hint_refresh",
              "observe_all", "epoch_step", "record_sync")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# a Mosaic kernel's op: its HLO text names the TPU custom-call target
_KERNEL = 'custom_call_target="tpu_custom_call"'


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
    """Device ops, program executions and host spans of one window."""

    def __init__(self, window: Interval, ops: Dict[str, List[tuple]],
                 modules: Dict[str, List[tuple]], spans: List[tuple],
                 n_epochs: int):
        self.window = window
        self.ops = ops            # plane -> [(start, end, name, module)]
        self.modules = modules    # plane -> [(start, end, name)]
        self.spans = spans        # [(start, end, name)], host clock
        self.n_epochs = int(n_epochs)

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

    def span_s(self, name: str) -> Optional[float]:
        """Host time inside spans named ``name``; None if there are none."""
        hits = [c for c in (_clip((s, e), self.window)
                            for s, e, n in self.spans if n == name) if c]
        if not hits:
            return None
        return sum(e - s for s, e in hits) * 1e-9

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

    def host_activity(self, t: float) -> str:
        """Innermost host span open at time ``t``."""
        best, depth = "outside spans", -1
        for s, e, name in self.spans:
            if s <= t < e:
                d = HOST_SPANS.index(name)
                if d > depth:
                    best, depth = name, d
        return best

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
        idle: Dict[str, float] = defaultdict(float)
        for s, e in self.gaps():
            idle[self.host_activity((s + e) / 2)] += (e - s) * 1e-9
        top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _events(line):
    for ev in line.events:
        yield float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name


def load(trace_dir: Path, n_epochs: int) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: Dict[str, List[tuple]] = {}
    modules: Dict[str, List[tuple]] = {}
    spans: List[tuple] = []
    window = None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
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
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for s, e, name in _events(line):
                    if name == WINDOW_SPAN:
                        window = (s, e)
                    elif name in HOST_SPANS:
                        spans.append((s, e, name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return Trace(window, ops, modules, spans, n_epochs)

