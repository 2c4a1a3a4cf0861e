"""Loop-thread busy share of the window in ``ack.in`` (a subscriber's PUBACK /
PUBREC / PUBCOMP releasing the outbound window) + ``ack.out`` (the publisher's
PUBACK / PUBREC encode and feed).
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "PUBACK paths broker/session.py", "unit": "%",
        "source": "program_span", "moves": "puback_p99_ms"}


def read(run: dict):
    return busy_pct(run, ('ack.in', 'ack.out'))
