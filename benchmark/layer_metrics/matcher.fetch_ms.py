"""Host-clock milliseconds per device batch in the matcher's ``fetch`` section
(blocking on the result's transfer to the host): the ``rmqtt/matcher.fetch`` spans' time over the runs of ``jit_match_*``
programs in the same trace. On an executor thread, so GIL wait is inside.
Absent where the trace holds no such span or no such run."""

from _stages import matcher_ms

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "ms/batch",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return matcher_ms(run, "fetch")
