#!/usr/bin/env python3
"""Readings of the control that every limit in ``checks`` is set against.

    python3 bench/control.py --workload mmap_paper.scan --epochs 300 \
        --seeds 11 12 13

The control is the plain reference of the configuration's harness module
computed one precision step down (for ``single``: bfloat16 scores, where
the configuration states float32), put in the program's place: for each
seed it replays ``--epochs`` epochs of the cell's pool through the
reference and through the control, and compares the two exactly as a run
compares the program with the reference.  A sound limit lies below every
number printed here.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from run_cell import REHEARSAL_BLOCKS, compare, load_cell  # noqa: E402


def control_readings(spec: dict, seed: int, n_epochs: int) -> dict:
    config, traffic = spec["config"], spec["traffic"]
    harness = spec["harness"]
    pool = harness.make_pool(config, traffic, seed)
    ref = harness.reference(config, traffic, pool, n_epochs)
    ctl = harness.reference(config, traffic, pool, n_epochs, control=True)
    bad_fields, bad_slots, failed = compare(
        ctl, ref, int(traffic["warmup_epochs"]))
    return {"record_mismatches": bad_fields,
            "placement_mismatches": bad_slots, "failed_epochs": failed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--epochs", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at a reduced size")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload,
                     REHEARSAL_BLOCKS if args.rehearse else None)
    rows = []
    for seed in args.seeds:
        r = control_readings(spec, seed, args.epochs)
        rows.append(r)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "epochs": args.epochs, **r}), flush=True)
    low = {k: int(np.min([r[k] for r in rows])) for k in rows[0]}
    print(json.dumps({"workload": args.workload, "least": low}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
