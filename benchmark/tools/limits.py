"""Readings for the limits of "How correct is decided": the program's
number (lower reading) and the control's (upper reading) on several
seeds in ONE process, at the cell's own sizes, so that set-up and
compilation are paid once.

    python -m benchmark.tools.limits --workload <cell> --seeds 1,2,3 [--control] [--seconds S]

Not part of a benchmark run. The control is the plain reference put in
the program's place in the nearest precision below the configuration's
(``quant=`` of the reference module). Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness

    cell = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    harness.prepare_env(cell)
    devices = harness.gate_devices(cell)
    driver = harness.driver_of(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        row = driver.limit_readings(cell, devices, seed, args.control,
                                    args.seconds)
        row.update(seed=seed, wall_s=round(time.perf_counter() - t0, 1),
                   platform=devices[0].platform)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
