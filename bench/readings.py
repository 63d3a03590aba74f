#!/usr/bin/env python3
"""Readings of the compared numbers over many seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--control | --fault <name>]

Runs the cell's whole run (set-up, a short window at the cell's load,
the comparison with the reference) once per seed and prints one JSON line
per seed with the numbers compared.  ``--control`` runs the control in
the program's place: the program's own approximate peel
(``method='approx'``, delta 0.1), where the configurations state the
exact one.  ``--fault`` plants one of ``faults.py``'s faults.  The lower
and upper readings that PERF.md sets each limit from come from here; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import faults
import run
from spec import load_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = load_cell(run.ROOT, args.workload)
    if args.control:
        cell.config["request"].update(method="approx", delta=0.1)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.fault:
            with faults.planted(args.fault):
                result = run.run(cell, seed, args.seconds, False)
        else:
            result = run.run(cell, seed, args.seconds, False)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": args.control, "fault": args.fault,
            "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "job_s": result["metrics"].get("job_s", {}).get("value"),
            "checks": {k: v["value"] for k, v in result["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
