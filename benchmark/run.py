#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --selftest            # no chip: the yardstick's own checks
    python3 benchmark/run.py --cpu --workload ...  # tiny rehearsal on the CPU backend

One run is one new process tree: a ``--router xla`` broker child at its
defaults, a fleet of publisher and subscriber processes, and the plain
reference in a process of its own. No cell, configuration, mix or metric is
named in code: each is a file under ``benchmark/`` found by its name in
``BENCHMARK.json`` (see ``harness/spec.py``). Only the broker child touches
JAX while it lives. The last line of standard output is the result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="tiny rehearsal on the CPU backend; names its platform")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args()
    if args.selftest:
        from harness import selftest

        return selftest.main()
    if not args.workload:
        ap.error("--workload is required")
    from harness.cell import run_cell

    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      T_START, cpu=args.cpu)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
