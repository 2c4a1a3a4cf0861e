"""CPU the broker's event-loop thread burnt over the window, as a share of it:
the delta of ``host_loop_cpu_ms_total`` (``time.thread_time`` read on the loop
thread when the stats body is built). Near 100 the one Python thread is the
wall. Absent on a broker without the counter."""

from _stages import delta, window_s

SPEC = {"layer": "broker event loop (one Python thread)", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    ms = delta(run, "host_loop_cpu_ms_total")
    return 100.0 * ms / (window_s(run) * 1e3) if ms is not None else None
