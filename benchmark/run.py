"""One run of one cell, in a fresh process:

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds weights and inputs on the device from the seed, warms the cell's
own shapes (set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints the result as the
last line of standard output. Without the chips the cell asks for it
exits non-zero and prints no result; ``--rehearsal`` runs the cell's
tiny sizes on the CPU as a wiring check, stamps ``platform=cpu``, prints
no device metric and prefixes its lines with ``REHEARSAL``.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; wiring check only")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness

    if importlib.util.find_spec("tensorframes_tpu") is None:
        print("benchmark: the program under test (tensorframes_tpu) is not "
              "in this directory; no result", file=sys.stderr)
        return 3
    cell = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    harness.prepare_env(cell)
    devices = harness.gate_devices(cell)
    driver = harness.driver_of(cell)
    driver.run(cell, args, t_start, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
