"""Run ``python -m rmqtt_tpu.broker <args>`` with one thread beside it.

    python launch_broker.py <control dir> <the broker's own arguments>

Only the process that holds the chip can trace it or read its memory
statistics, so this thread does both on request, through files in the
control directory (the harness stays off JAX while the broker lives):

- ``trace.on`` appears → ``jax.profiler.start_trace``, then ``trace.started``
  is written; it goes → the trace is stopped and ``trace.done`` written
  ({dir, start, stop}: the instants between which the profiler was on);
- ``mem.req`` appears → ``mem.json`` ({memory_peak_bytes}: the largest
  ``peak_bytes_in_use`` over the local devices, as JAX reports it);
- ``threads.req`` appears → ``threads.json`` ({names}: the names of the
  process's live threads. The program compiles a match program it has not
  met on a worker thread of its own, off the routing path, and no counter
  says that one is at work: its name does, ``Broker.compiling``).

The broker module itself runs unchanged, as ``__main__``, on the main thread.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading
import time
from pathlib import Path


def _write(path: Path, obj: dict) -> None:
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def _control(ctl: Path) -> None:
    tracing = None
    while True:
        time.sleep(0.05)
        on = (ctl / "trace.on").exists()
        if on and tracing is None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # device planes only: small and cheap
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(ctl / "trace"), profiler_options=opts)
            tracing = time.perf_counter()
            _write(ctl / "trace.started", {"start": tracing})
        elif tracing is not None and not on:
            import jax

            stop = time.perf_counter()
            jax.profiler.stop_trace()
            _write(ctl / "trace.done", {"dir": str(ctl / "trace"),
                                        "start": tracing, "stop": stop})
            tracing = None
        if (ctl / "mem.req").exists():
            import jax

            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.local_devices()]
            (ctl / "mem.req").unlink()
            _write(ctl / "mem.json", {"memory_peak_bytes": int(max(peaks))})
        if (ctl / "threads.req").exists():
            (ctl / "threads.req").unlink()
            _write(ctl / "threads.json",
                   {"names": [t.name for t in threading.enumerate()]})


def main() -> None:
    ctl = Path(sys.argv[1])
    threading.Thread(target=_control, args=(ctl,), daemon=True).start()
    sys.argv = ["rmqtt_tpu.broker", *sys.argv[2:]]
    runpy.run_module("rmqtt_tpu.broker", run_name="__main__")


if __name__ == "__main__":
    main()
