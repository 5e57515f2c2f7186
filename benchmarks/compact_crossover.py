"""Chip microbenchmark of ``selectk.compact``'s two algorithms.

``compact(csel, k)`` turns the inclusive prefix count of a selection mask
into the indices of its first ``k`` selected elements.  The binary search
(``searchsorted``) costs ``k * ceil(log2(n + 1))`` gathers per row; the
scatter forms cost ``n`` updates per row.  This script times each form at
the fused epoch step's shapes (the two benchmark cells' selection and
free-slot compactions) and at sparse points (k/n of 1% and below), checks
that every form returns the search's answer bit for bit, and prints one
JSON line per point with ``ratio = n / (k * ceil(log2(n + 1)))``: the
scatter wins where its time is below the search's, which fixes the
constant ``selectk.SCATTER_C`` of the rule
``k * ceil(log2(n + 1)) >= n / SCATTER_C``.  Besides the library's two
(``search``, ``scatter``) it times two other scatters: a histogram of
the counts (``count_rows``) and the mask passed in with unique indices
(``set_rows``).

    PYTHONPATH=src python3 benchmarks/compact_crossover.py [--reps 7]
        [--out FILE] [--points NAME ...] [--forms NAME ...]

On a CPU it runs at a tiny size only (``--tiny``): CPU times say nothing
of the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import selectk

# (name, rows, n, k, share of n selected)
CHIP_POINTS = (
    ("dlrm.select", 5, 5_000_000, 486_587, 486_587 / 5_000_000),
    ("dlrm.free_slots", 6, 486_587, 486_587, 0.1),
    ("mmap.select", 5, 2_621_440, 262_144, 262_144 / 2_621_440),
    ("mmap.free_slots", 6, 262_144, 262_144, 0.1),
    ("dlrm.k2pct", 5, 5_000_000, 100_000, 0.02),
    ("dlrm.k1pct", 5, 5_000_000, 50_000, 0.01),
    ("dlrm.k0.5pct", 5, 5_000_000, 25_000, 0.005),
    ("dlrm.k0.1pct", 5, 5_000_000, 5_000, 0.001),
    ("rank_sparse", 1, 5_000_000, 24_000, 0.0048),
    ("mmap.k1pct", 5, 2_621_440, 26_214, 0.01),
)
TINY_POINTS = (
    ("tiny.select", 3, 4_000, 400, 0.1),
    ("tiny.free_slots", 2, 512, 512, 0.1),
    ("tiny.sparse", 1, 4_000, 10, 0.002),
)


def search(sel, csel, k):
    return selectk._compact_search(csel, k)


def scatter(sel, csel, k):
    """The library's scatter: rows by ``lax.map``, the mask recovered from
    the count inside each row."""
    return selectk._compact_scatter(csel, k)


def count_rows(sel, csel, k):
    """A histogram of each row's prefix counts, then its prefix sum: entry
    j counts the positions whose count is at most j, which is the index of
    the (j+1)-th selected element (n past the last)."""
    rows = [jnp.zeros((k,), jnp.int32).at[csel[r]].add(1, mode="drop")
            for r in range(csel.shape[0])]
    return selectk.prefix_sum(jnp.stack(rows))


def set_rows(sel, csel, k):
    """The mask passed in, each dropped entry given its own slot past the
    end, so that ``unique_indices=True`` holds."""
    n = csel.shape[-1]
    iota = jnp.arange(n, dtype=jnp.int32)

    def row(s, cs):
        dest = jnp.where(s & (cs <= k), cs - 1, k + iota)
        return jnp.full((k,), n, jnp.int32).at[dest].set(
            iota, mode="drop", unique_indices=True)
    return jnp.stack([row(sel[r], csel[r]) for r in range(csel.shape[0])])


# The scatters take rows one at a time: a batched scatter's (rows, n, 2)
# index tuples take 480 MB of temporaries at the DLRM select's shape on a
# v5e.
FORMS = {"search": search, "scatter": scatter, "count_rows": count_rows,
         "set_rows": set_rows}


def _inputs(rows, n, k, share, seed):
    rng = np.random.default_rng(seed)
    sel = rng.random((rows, n)) < share
    if share * n >= k:          # the select shape: exactly k selected
        for r in range(rows):
            on = np.flatnonzero(sel[r])
            if on.size > k:
                sel[r, rng.choice(on, on.size - k, replace=False)] = False
    sel = jnp.asarray(sel)
    return sel, jax.jit(selectk.prefix_sum)(sel)


def _time(f, args, reps):
    jax.block_until_ready(f(*args))                 # compile + warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--points", nargs="*", default=None,
                    help="names of the points to run (default: all)")
    ap.add_argument("--forms", nargs="*", default=None,
                    help="names of the forms to time (default: all)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print("no TPU: pass --tiny for a CPU rehearsal", file=sys.stderr)
        return 2
    lines = []
    forms = {f: FORMS[f] for f in (args.forms or FORMS)}
    for i, (name, rows, n, k, share) in enumerate(
            TINY_POINTS if args.tiny else CHIP_POINTS):
        if args.points and name not in args.points:
            continue
        sel, csel = _inputs(rows, n, k, share, seed=i)
        ref = None
        line = {"point": name, "rows": rows, "n": n, "k": k,
                "ratio": n / (k * math.ceil(math.log2(n + 1))),
                "device": dev.device_kind}
        for form, fn in forms.items():
            f = jax.jit(fn, static_argnums=2)
            got = np.asarray(f(sel, csel, k))
            if ref is None:
                ref = got
            line[f"{form}_ok"] = bool(np.array_equal(ref, got))
            line[f"{form}_ms"] = 1e3 * _time(f, (sel, csel, k), args.reps)
        print(json.dumps(line), flush=True)
        lines.append(line)
        del sel, csel
    if args.out:
        with open(args.out, "w") as fh:
            fh.write("".join(json.dumps(x) + "\n" for x in lines))
    return 0 if all(v for x in lines for kk, v in x.items()
                    if kk.endswith("_ok")) else 1


if __name__ == "__main__":
    sys.exit(main())
