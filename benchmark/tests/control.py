#!/usr/bin/env python3
"""Run a cell with the broker's router broken (``faulty_broker.py``): the
control of ``correct``, at the cell's own size on the chip.

    python3 benchmark/tests/control.py --workload <cell> --seed <n> --seconds <s> --fault drop|alter [--cpu]

Prints the result line of a ``--trace 0`` run; ``correct`` must read false.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=("drop", "alter"), required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    os.environ["BENCHMARK_FAULT"] = args.fault
    from harness.cell import run_cell

    result = run_cell(args.workload, args.seed, args.seconds, False, T_START,
                      cpu=args.cpu, launcher=HERE / "faulty_broker.py")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
