#!/usr/bin/env python3
"""Run one benchmark cell of the tiering epoch runtime on the chip.

    python3 bench/run_cell.py --workload dlrm_paper.hinted --seed 7 \
        --seconds 30 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, the deployment) under a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

1. **Set-up** -- places the persistent compilation cache, loads the
   configuration's harness module (``bench/harness/<name>.py``, named by
   its ``"harness"`` key, ``single`` without one), which draws the cell's
   seeded pool of epochs on the host and builds the ``EpochRuntime`` on
   the default path, and serves ``warmup_epochs`` epochs so every program
   the window runs is compiled.
2. **Window** -- hands pool epochs to ``EpochRuntime.step`` in order,
   cyclically, one in flight, until ``--seconds`` have passed; then flushes
   and blocks until the device is done.  With ``--trace 1`` the window runs
   under the profiler and the per-layer metrics are read from its trace.
3. **Check** -- takes the program's rows and placements, reads the
   device's peak memory, frees the runtime, replays every served epoch
   through the harness module's plain reference and compares every field
   of every row of every stream, and every placement, exactly.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and ``checks`` -- each compared number beside its limit -- last.
The same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.  ``--rehearse`` runs the cell on the CPU at a reduced size and
prints the would-be result to standard error only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE_DIR = trace_reduce.TRACE_DIR
REHEARSAL_BLOCKS = 20_000


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, n_blocks: Optional[int] = None) -> dict:
    """The cell's entry, configuration, harness module, traffic mix and
    metric lists; at ``n_blocks`` blocks where given (rehearsals, tests),
    as the harness module shrinks it."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    config = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    harness = load_harness(config.get("harness", "single"))
    if n_blocks is not None:
        config, traffic = harness.shrink(config, traffic, n_blocks)
    return dict(
        cell=cell, config=config, traffic=traffic, harness=harness,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def check_device(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def device_peaks(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json; add them with their source")
    return peaks[kind]


def _load_module(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The ``read(trace)`` of per-layer metric ``name``."""
    return _load_module("metrics", name).read


def load_harness(name: str):
    """Harness module ``name``: ``make_pool``, ``build_runtime``,
    ``program_rows``, ``reference`` and ``shrink`` (see
    ``bench/harness/single.py``)."""
    return _load_module("harness", name)


class _Compiles:
    """Counts programs compiled or loaded from the persistent cache (``n``)
    and the cache's hits (``hits``); ``n - hits`` were compiled."""

    def __init__(self):
        import jax
        self.n = self.hits = 0

        def duration(event: str, secs: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(event)


class _HostWatch:
    """Per-epoch host readings of the window, for telling a stall on the
    host from one on the device: process CPU seconds and time in the
    garbage collector, read at every handoff."""

    FIELDS = ("cpu_s", "gc_ms")

    def __init__(self):
        self.marks: List[tuple] = []
        self._gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def mark(self) -> None:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.marks.append((ru.ru_utime + ru.ru_stime, self._gc_s * 1e3))

    def close(self) -> Dict[str, list]:
        """Per-epoch deltas between consecutive marks."""
        gc.callbacks.remove(self._gc)
        m = np.asarray(self.marks, np.float64)
        return {f: np.diff(m[:, j]).tolist()
                for j, f in enumerate(self.FIELDS)}


def _annotate(obj, method: str, span: str) -> None:
    """Put every call of ``obj.method`` inside a profiler span ``span``
    (the hint providers run in no span of the runtime's own)."""
    import jax
    fn = getattr(obj, method)

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(span):
            return fn(*args, **kwargs)
    setattr(obj, method, wrapped)


def serve(rt, pool, traffic: dict, seconds: float, first: int,
          annotate: bool):
    """Closed-loop window: epoch ``first`` onward, one in flight, until
    ``seconds`` have passed.  Returns (epochs served, per-epoch latency from
    handoff to its records on the host, window seconds).  Logs every
    epoch's latency with its host readings (:class:`_HostWatch`)."""
    import jax
    depth = int(traffic["hints"]["depth"]) if traffic.get("hints") else 0
    lane0 = next(iter(rt.records))
    handed: List[float] = []
    done: List[float] = []

    def settle(now: float) -> None:
        arrived = len(rt.records[lane0]) - first - len(done)
        done.extend([now] * arrived)

    watch = _HostWatch()

    def one(i: int) -> None:
        watch.mark()
        handed.append(time.perf_counter())
        rt.step(pool.epoch(i), lookahead=pool.upcoming(i, depth))
        settle(time.perf_counter())

    t0 = time.perf_counter()
    i = first
    while True:
        if annotate:
            with jax.profiler.TraceAnnotation("served_epoch"):
                one(i)
        else:
            one(i)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    rt.flush()
    rt.block_until_ready()
    t1 = time.perf_counter()
    settle(t1)
    watch.mark()
    lat = np.asarray(done) - np.asarray(handed)
    readings = dict(latency_ms=(lat * 1e3).tolist(), **watch.close())
    log(f"window epochs {json.dumps(readings)}")
    return i - first, lat, t1 - t0


def _count_mismatches(got: list, want: list, bad_epochs: set) -> int:
    """Mismatching fields between two streams of rows, row by row; a row
    on one side only counts each of its fields.  Adds the epochs of the
    rows at fault to ``bad_epochs``."""
    bad = 0
    for a, b in itertools.zip_longest(got, want):
        if a is None or b is None:
            row = b if a is None else a
            diff = len(row)
        else:
            diff = sum(f not in a or f not in b
                       or not np.array_equal(a[f], b[f])
                       for f in a.keys() | b.keys())
            row = b
        if diff:
            bad += diff
            bad_epochs.add(row["epoch"])
    return bad


def compare(program: tuple, ref: tuple, first: int):
    """Exact comparison of the program's ``(rows, placements)`` with the
    reference's: mismatching row fields over every stream, the window
    epochs (from ``first`` on) that hold them, and mismatching placement
    entries over every placement array."""
    (rows, places), (ref_rows, ref_places) = program, ref
    bad_epochs: set = set()
    bad_fields = sum(
        _count_mismatches(rows.get(name, []), ref_rows.get(name, []),
                          bad_epochs)
        for name in sorted(rows.keys() | ref_rows.keys()))
    bad_slots = 0
    for name in sorted(places.keys() | ref_places.keys()):
        a, b = places.get(name), ref_places.get(name)
        if a is None or b is None or np.shape(a) != np.shape(b):
            bad_slots += max(np.size(x) for x in (a, b) if x is not None)
        else:
            bad_slots += int(np.sum(np.asarray(a) != np.asarray(b)))
    failed = len([e for e in bad_epochs if e >= first])
    return bad_fields, bad_slots, failed


def run(spec: dict, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, reference=None,
        trace_dir: Path = TRACE_DIR) -> dict:
    """One run of a cell (see the module doc); returns the result object.
    ``reference`` stands in for the harness module's (tests)."""
    import jax
    from repro.compile_cache import use_compile_cache
    from repro.obs import trace as obs_trace

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    harness = spec["harness"]
    devices = check_device(int(cell["chips"]), require_tpu)
    kind = devices[0].device_kind
    if require_tpu:
        peaks = device_peaks(kind)
    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _Compiles()

    t_dev = time.perf_counter() - T_START
    pool = harness.make_pool(config, traffic, seed)
    t_pool = time.perf_counter() - T_START
    rt = harness.build_runtime(config, traffic, pool)
    t_built = time.perf_counter() - T_START
    log(f"kernels {json.dumps(rt.kernels)}")
    warm = int(traffic["warmup_epochs"])
    depth = int(traffic["hints"]["depth"]) if traffic.get("hints") else 0
    for i in range(warm):
        rt.step(pool.epoch(i), lookahead=pool.upcoming(i, depth))
    rt.flush()
    rt.block_until_ready()
    setup_s = time.perf_counter() - T_START
    compiles_setup, hits_setup = compiles.n, compiles.hits
    log(f"set-up at s: devices {t_dev} pool {t_pool} runtime {t_built} "
        f"warm-up done {setup_s}")

    if trace:
        if getattr(rt, "hints", None) is not None:
            _annotate(rt.hints, "epoch_ranks", "hint_ranks")
            _annotate(rt, "set_hint_ranks", "hint_set")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        obs_trace.enable(xla_annotations=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        if trace:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                served, lat, window_s = serve(rt, pool, traffic, seconds,
                                              warm, annotate=True)
        else:
            served, lat, window_s = serve(rt, pool, traffic, seconds, warm,
                                          annotate=False)
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs_trace.disable()
    compiles_window = compiles.n - compiles_setup
    n_epochs = warm + served
    accesses = served * int(pool.epoch(0).size)
    log(f"epochs warm-up {warm} window {served}; programs set-up "
        f"{compiles_setup} ({hits_setup} from the persistent cache), "
        f"window {compiles_window}")

    program = harness.program_rows(rt, config)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    del rt
    gc.collect()

    t_ref = time.perf_counter()
    ref = (reference or harness.reference)(config, traffic, pool, n_epochs)
    log(f"reference replayed {n_epochs} epochs in "
        f"{time.perf_counter() - t_ref} s")
    bad_fields, bad_slots, failed = compare(program, ref, warm)
    checks = {
        "record_mismatches": {"value": bad_fields, "limit": 0},
        "placement_mismatches": {"value": bad_slots, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(served),
              "failed": int(failed)}
    if trace:
        tr = trace_reduce.load(trace_dir, n_epochs=served)
        metrics = {}
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(tr)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        result.update(metrics=metrics, device=device,
                      breakdown=tr.breakdown())
    else:
        values = {
            "accesses_per_s": accesses / window_s,
            "epoch_p90_ms": float(np.percentile(lat, 90)) * 1e3,
            "peak_hbm_mb": peak / 1e6,
            "setup_s": setup_s,
        }
        log(f"epoch latency median {float(np.median(lat)) * 1e3} ms, max "
            f"{float(np.max(lat)) * 1e3} ms over {len(lat)} epochs; window "
            f"{window_s} s; accesses {accesses}")
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}, device=device)
    if require_tpu:
        log(f"peak memory share of HBM "
            f"{100.0 * peak / float(peaks['hbm_bytes'])} %")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at a reduced size; prints no result line")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    spec = load_cell(args.workload,
                     REHEARSAL_BLOCKS if args.rehearse else None)
    try:
        result = run(spec, args.seed, args.seconds, bool(args.trace),
                     require_tpu=not args.rehearse)
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    line = json.dumps(result)
    if args.rehearse:
        log(f"rehearsal result (not a measurement): {line}")
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    if not args.rehearse:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
