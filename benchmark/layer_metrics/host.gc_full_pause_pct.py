"""Seconds of the window the broker's process stood stopped in FULL
(generation 2) passes of the cyclic collector, thaws included, as a share of
the window: the delta of ``host_gc_full_pause_ms_total``
(``rmqtt_tpu/broker/gcpolicy.py``). A full pass walks every object that is
not frozen and holds every publish in flight for its length, so its share is
what the tails read. Absent on a broker without the counter (a program from
before PR 34)."""

from _stages import delta, window_s

SPEC = {"layer": "broker event loop (one Python thread)", "unit": "%",
        "source": "program_counter", "moves": "puback_p99_ms"}


def read(run: dict):
    ms = delta(run, "host_gc_full_pause_ms_total")
    return 100.0 * ms / (window_s(run) * 1e3) if ms is not None else None
