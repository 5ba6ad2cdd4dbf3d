#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference put in the
program's place with one stated guarantee broken, at the cell's own
size, through the same harness (``run.drive``). It must come out NOT
correct; a fault name from ``lib.standin.FAULTS`` plants another fault,
and ``--fault none`` shows the reference itself passing.

    python benchmarks/control.py --workload <cell> --seed <n> --seconds <s> [--fault stale_state]

The control solves each batch against the occupancy the batch started
with (no carry inside a batch): the step that would tempt a later PR,
and the one that breaks "no node over allocatable" and "zone skew <=
maxSkew". No chip is used and no number printed here is a measurement.
The stand-in decides 1,024-pod batches at 2,000 pods/s, the first
``SOUND_FIRST`` pods soundly (the set-up's, so that it ends).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import files, standin  # noqa: E402


PACE_PODS_PER_S = 2000.0  # a closed loop needs a pace
SOUND_FIRST = 90  # pods decided soundly before the fault starts
# the stand-in publishes a batch every few seconds at most while it holds
# pods; a fault that leaves pods undecided stops the loop before t1
BATCH_WAIT_S = 10.0


def main(argv=None, trace: int = 0) -> int:
    """``trace=1`` (tests) prints the per-layer metrics a stand-in can carry."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default="stale_state")
    ap.add_argument("--rehearse-size", action="store_true",
                    help="the cell's tiny 'rehearse' size (tests)")
    args = ap.parse_args(argv)
    cell = files.load_workload(args.workload)
    cfg = files.load_config(cell["config"])
    if args.rehearse_size:
        bench_run.apply_rehearsal(cell, cfg)
    workdir = os.path.join(files.ROOT, ".bench_work", cell["name"] + ".control")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    system = standin.StandIn(
        cfg, workdir, fault=None if args.fault == "none" else args.fault,
        pace_pods_per_s=PACE_PODS_PER_S * (0.2 if args.rehearse_size else 1.0),
        fault_after=SOUND_FIRST,
    )
    run_args = argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=trace, rehearse_cpu=True
    )
    try:
        line = bench_run.drive(
            run_args, cell, cfg, system, workdir, batch_wait_s=BATCH_WAIT_S
        )
    finally:
        system.close()
    line["control"] = args.fault
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
