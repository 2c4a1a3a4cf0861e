"""The broker with its router broken on purpose, for the tests here.

    BENCHMARK_FAULT=drop|alter python faulty_broker.py <control dir> <broker args>

Started in place of ``harness/launch_broker.py``. Every ``EVERY``-th
non-empty match row is spoilt where it is produced, in
``XlaRouter._expand`` — the one place all three routing paths (host mirror,
device, failover) hand their matches on:

- ``drop``: the row loses its last filter. The control: the configuration's
  delivery guarantee (every publish reaches every matching subscriber) is
  broken while every PUBACK still comes.
- ``alter``: the row is replaced by the row before it — an answer altered
  where it is produced: some subscribers get what is not theirs and others
  miss what is.
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "harness"))

EVERY = 97


def install(fault: str) -> None:
    from rmqtt_tpu.router.xla import XlaRouter

    orig = XlaRouter._expand
    state = {"n": 0, "last": None}

    def _expand(self, items, fid_rows):
        rows = []
        for fids in fid_rows:
            if len(fids):
                state["n"] += 1
                if state["n"] % EVERY == 0:
                    fids = fids[:-1] if fault == "drop" else state["last"]
                else:
                    state["last"] = fids
            rows.append(fids)
        return orig(self, items, rows)

    XlaRouter._expand = _expand


if __name__ == "__main__":
    fault = os.environ["BENCHMARK_FAULT"]
    if fault not in ("drop", "alter"):
        raise SystemExit(f"unknown BENCHMARK_FAULT {fault!r}")
    install(fault)
    import launch_broker

    launch_broker.main()
