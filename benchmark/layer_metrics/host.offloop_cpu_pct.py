"""CPU the broker process burnt OFF the event-loop thread over the window, as a
share of it: delta of (``host_proc_cpu_ms_total`` - ``host_loop_cpu_ms_total``):
executor threads (the device matcher's host side, GIL spinning included),
XLA's runtime threads, the profiler when it is on. May pass 100 (several
threads). Absent on a broker without the counters."""

from _stages import delta, window_s

SPEC = {"layer": "executor threads + runtime", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    proc, loop = delta(run, "host_proc_cpu_ms_total"), delta(run, "host_loop_cpu_ms_total")
    if proc is None or loop is None:
        return None
    return 100.0 * (proc - loop) / (window_s(run) * 1e3)
