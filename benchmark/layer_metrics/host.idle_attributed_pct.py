"""Share of the device's idle seconds, inside the traced span, that the program's
own spans explain: ``harness/host_spans.py`` gives every idle instant the name
of the ``matcher.*`` span open then, else of the loop thread's open span, else
``loop.unspanned``; this is 100 less the share of ``loop.unspanned``. Absent
where the trace holds no span of the program's."""

from harness import host_spans

SPEC = {"layer": "device + host, one clock", "unit": "%",
        "source": "device_trace", "moves": "deliveries_per_s"}


def read(run: dict):
    red = host_spans.from_run(run)
    if not red or not red["idle_total_s"]:
        return None
    return 100.0 * (1.0 - red["idle_s"].get(host_spans.UNSPANNED, 0.0) / red["idle_total_s"])
