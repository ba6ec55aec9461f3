"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py --workload serve256.think --seeds 12 --seconds 10

Runs the cell's timed path on ``--seeds`` fresh seeds in one process and,
for each, prints the compared numbers for the program (sound runs: the
lower readings) and for the control, the plain reference computed a
precision below the configuration's (an upper reading), all in one JSON
line last.  ``--fault <name>`` plants one of the kind's faults in the
program first, so that the program's readings are the fault's.  The
benchmark's own runs never run the control or a fault.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--fault", default=None,
                    help="plant this fault of the kind's FAULTS in the program")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.prepare(ROOT)
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    devices = harness.require_chips(int(cell["chips"]))
    cfg = bench.config(cell["config"])
    ctx = argparse.Namespace(bench=bench, cell=cell, cfg=cfg,
                             mix=bench.traffic(cell["traffic"]),
                             seconds=args.seconds, devices=devices,
                             log=harness.log)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    kind = bench.kind(cfg["kind"])
    if args.fault:
        with kind.FAULTS[args.fault]():
            rows = kind.control(ctx, seeds)
    else:
        rows = kind.control(ctx, seeds)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
