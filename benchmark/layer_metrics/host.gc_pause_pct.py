"""Seconds of the window the broker's process stood stopped in the cyclic
collector, every generation and every thread's collections summed, as a share
of the window: the delta of ``host_gc_pause_ms_total`` (``gc.callbacks``
start → stop pairs, ``rmqtt_tpu/broker/hostprof.py``). A collection stops
every thread, so this is time nothing was served. Absent on a broker without
the counter."""

from _stages import delta, window_s

SPEC = {"layer": "broker event loop (one Python thread)", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    ms = delta(run, "host_gc_pause_ms_total")
    return 100.0 * ms / (window_s(run) * 1e3) if ms is not None else None
