"""A configuration brings its own semantics as new files.

A toy deployment -- a configuration naming ``"harness": "toy"``, its
``bench/harness/toy.py`` and a traffic file, with entries added to
``BENCHMARK.json`` -- runs through an unedited ``bench/run_cell.py`` in a
copy of the benchmark, and its own streams decide ``correct``.
"""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

import run_cell

BENCH = Path(__file__).resolve().parents[1]
SEED = 3_141_592_653_589     # larger than 32 bits hold

TOY = '''"""A toy deployment: a per-block access counter on the device that
keeps its ``k_hot`` hottest blocks, with a stream of its own per half of
the block space."""
import jax
import numpy as np


class Pool:
    def __init__(self, epochs):
        self.epochs = epochs

    def epoch(self, i):
        return self.epochs[i % len(self.epochs)]

    def upcoming(self, i, depth):
        return tuple(self.epoch(i + 1 + d) for d in range(depth))


def make_pool(config, traffic, seed):
    rng = np.random.default_rng(seed)
    shape = (int(traffic["batches_per_epoch"]), int(traffic["batch"]))
    return Pool([rng.integers(0, config["n_blocks"], shape, dtype=np.int32)
                 for _ in range(config["stream_pool_epochs"])])


@jax.jit
def _count(counts, ids):
    return counts.at[ids.ravel()].add(1)


def _rows(epoch, counts, top):
    half = len(counts) // 2
    return {"hot": {"epoch": epoch, "hottest": int(top[0]),
                    "hot_accesses": int(counts[top].sum())},
            "half/0/touched": {"epoch": epoch,
                               "blocks": int((counts[:half] > 0).sum())}}


class Runtime:
    kernels = {}

    def __init__(self, n, k):
        self.counts = jax.numpy.zeros((n,), jax.numpy.int32)
        self.k = k
        self.top = None
        self.records = {"hot": [], "half/0/touched": []}

    def step(self, batches, lookahead=()):
        self.counts = _count(self.counts, jax.numpy.asarray(batches))
        self.top = jax.lax.top_k(self.counts, self.k)[1]
        counts, top = jax.device_get((self.counts, self.top))
        for name, row in _rows(len(self.records["hot"]), counts,
                               top).items():
            self.records[name].append(row)

    def flush(self):
        return {}

    def block_until_ready(self):
        jax.block_until_ready(self.counts)
        return self


def build_runtime(config, traffic, pool):
    return Runtime(int(config["n_blocks"]), int(config["k_hot"]))


def program_rows(rt, config):
    rows = {name: list(recs) for name, recs in rt.records.items()}
    if config.get("extra_row"):
        last = rows["half/0/touched"][-1]
        rows["half/0/touched"].append(dict(last, epoch=last["epoch"] + 1))
    return rows, {"hot": np.asarray(rt.top)}


def reference(config, traffic, pool, n_epochs, control=False):
    counts = np.zeros((int(config["n_blocks"]),), np.int64)
    rows = {"hot": [], "half/0/touched": []}
    top = None
    for e in range(n_epochs):
        counts += np.bincount(pool.epoch(e).ravel(), minlength=len(counts))
        top = np.argsort(-counts, kind="stable")[:int(config["k_hot"])]
        for name, row in _rows(e, counts, top).items():
            rows[name].append(row)
    return rows, {"hot": top}


def shrink(config, traffic, n_blocks):
    return dict(config, n_blocks=n_blocks), traffic
'''


@pytest.fixture
def toy_bench(tmp_path, monkeypatch):
    """A copy of the benchmark with the toy deployment added as new files
    and new entries; its ``run_cell`` module."""
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "harness" / "toy.py").write_text(TOY)
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "harness": "toy", "n_blocks": 512, "k_hot": 8,
         "stream_pool_epochs": 3}))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"name": "burst", "batches_per_epoch": 2, "batch": 64,
         "hints": None, "warmup_epochs": 2}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "a test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.burst", "config": "toy",
                               "traffic": "burst", "chips": 1,
                               "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "toy_run_cell", tmp_path / "bench" / "run_cell.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod, tmp_path
    # the harness placed the compile cache in the copy; put it back
    from repro.compile_cache import use_compile_cache
    use_compile_cache(BENCH.parent)


def test_copied_files_are_the_repositorys(toy_bench):
    _, root = toy_bench
    for path in BENCH.rglob("*.py"):
        rel = path.relative_to(BENCH)
        if rel.parts[0] not in ("tests", "__pycache__"):
            assert (root / "bench" / rel).read_bytes() == path.read_bytes()


def test_a_new_harness_module_decides_correct(toy_bench):
    rc, root = toy_bench
    spec = rc.load_cell("toy.burst")
    assert spec["harness"].__file__ == str(root / "bench/harness/toy.py")
    r = rc.run(spec, SEED, 0.5, False, require_tpu=False,
               trace_dir=root / "trace")
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_an_extra_stream_row_is_not_correct(toy_bench):
    rc, root = toy_bench
    spec = rc.load_cell("toy.burst")
    spec["config"]["extra_row"] = True
    r = rc.run(spec, SEED, 0.5, False, require_tpu=False,
               trace_dir=root / "trace")
    assert r["correct"] is False
    assert r["failed"] == 1
    assert r["checks"]["record_mismatches"]["value"] == 2   # its 2 fields
    assert r["checks"]["placement_mismatches"]["value"] == 0


@pytest.mark.parametrize("got, want, fields, slots, failed", [
    # one field off in a window epoch
    ({"a": [{"epoch": 0, "x": 1}, {"epoch": 1, "x": 2}]},
     {"a": [{"epoch": 0, "x": 1}, {"epoch": 1, "x": 3}]}, 1, 0, 1),
    # a stream the reference lacks counts every field of every row
    ({"a": [{"epoch": 0, "x": 1}], "b": [{"epoch": 1, "y": 1}]},
     {"a": [{"epoch": 0, "x": 1}]}, 2, 0, 1),
    # a field on one side only; an epoch before the window is not failed
    ({"a": [{"epoch": 0, "x": 1, "z": 0}]},
     {"a": [{"epoch": 0, "x": 1}]}, 1, 0, 0),
    # equal rows, arrays as field values
    ({"a": [{"epoch": 1, "v": np.arange(3)}]},
     {"a": [{"epoch": 1, "v": np.arange(3)}]}, 0, 0, 0),
])
def test_compare_counts_every_stream_and_field(got, want, fields, slots,
                                               failed):
    place = {"p": np.arange(4)}
    assert run_cell.compare((got, place), (want, place), 1) == (
        fields, slots, failed)


def test_compare_counts_every_placement_entry():
    rows = {"a": [{"epoch": 0, "x": 1}]}
    got = {"p": np.array([0, 1, 2, 3]), "q": np.zeros(5)}
    want = {"p": np.array([0, 1, 9, 9])}
    assert run_cell.compare((rows, got), (rows, want), 0) == (0, 2 + 5, 0)
    assert run_cell.compare((rows, {"p": np.zeros(3)}),
                            (rows, {"p": np.zeros(4)}), 0) == (0, 4, 0)
