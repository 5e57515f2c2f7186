"""Chrome trace-event JSON writer — the pipelining proof as a timeline.

Converts recorded :class:`~repro.obs.trace.Span` objects into the Trace
Event Format consumed by ``chrome://tracing`` and https://ui.perfetto.dev
(``{"traceEvents": [...]}`` with ``ph: "X"`` complete events, timestamps
in microseconds).  Host threads map to tracks by thread name.  Every event
is a host span: device time comes from the profiler's own trace (spans
enter ``jax.profiler.TraceAnnotation`` when ``xla_annotations=True``), not
from here.

:func:`pipelining_visible` is the structural check behind the PR 6
pipelining claim, now readable off the timeline: with ``sync_every=K>1``
there must exist a ``record_sync`` span that *begins after* the dispatch
of an epoch newer than any epoch it drains — i.e. the host kept feeding
the device while the previous window's records were still in flight.
"""
from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional, Sequence

__all__ = [
    "chrome_trace_events", "write_chrome_trace", "pipelining_visible",
]

_PID = 1


def _t_base(spans: Sequence) -> float:
    return min((s.t0_s for s in spans), default=0.0)


def chrome_trace_events(spans: Sequence, *, t_base: Optional[float] = None,
                        cat: str = "runtime") -> List[dict]:
    """Spans -> chrome ``ph:"X"`` complete events (ts/dur in microseconds,
    normalised so the earliest span starts at ts=0)."""
    base = _t_base(spans) if t_base is None else t_base
    events: List[dict] = []
    for s in spans:
        args: Dict[str, object] = {}
        if s.epoch is not None:
            args["epoch"] = s.epoch
        if s.args:
            args.update(s.args)
        events.append({
            "name": s.name, "ph": "X", "cat": cat,
            "ts": (s.t0_s - base) * 1e6, "dur": s.dur_s * 1e6,
            "pid": _PID, "tid": s.tid,
            "args": args,
        })
    return events


def pipelining_visible(spans: Iterable) -> bool:
    """True iff some record_sync span started after the host had already
    dispatched an epoch newer than every epoch that sync drains.

    ``sync_every=1`` can never satisfy this (each epoch is drained before
    the next is dispatched); ``sync_every=K>1`` must (``_step_fused``
    dispatches ``observe_all`` for epoch *e* before draining epochs
    ``[e-K, e)``), so the check is deterministic, not timing-dependent.
    """
    spans = list(spans)
    observe_starts = {s.epoch: s.t0_s for s in spans
                      if s.name == "observe_all" and s.epoch is not None}
    for s in spans:
        if s.name != "record_sync" or not s.args:
            continue
        base, n = s.args.get("epoch_base"), s.args.get("n_epochs")
        if base is None or n is None:
            continue
        for epoch, t0 in observe_starts.items():
            if epoch >= base + n and t0 <= s.t0_s:
                return True
    return False


def write_chrome_trace(path, spans: Sequence, *,
                       metadata: Optional[dict] = None) -> dict:
    """Write ``{"traceEvents": [...]}`` JSON for chrome://tracing; returns
    the document (also handy for asserting on it in tests)."""
    events = chrome_trace_events(spans)
    doc: Dict[str, object] = {
        "traceEvents": sorted(events, key=lambda e: (e["ts"], e["tid"])),
        "displayTimeUnit": "ms",
    }
    if metadata:
        doc["otherData"] = dict(metadata)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, separators=(",", ":"), sort_keys=True)
        fh.write("\n")
    return doc
