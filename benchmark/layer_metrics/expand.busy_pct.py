"""Loop-thread busy share of the window in ``routing.expand`` (``XlaRouter._expand``:
matched filter ids to subscriber relations). Batches expanded on an executor
thread are kept apart by the program and not counted here.
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "relations expansion router/xla.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return busy_pct(run, ('routing.expand',))
