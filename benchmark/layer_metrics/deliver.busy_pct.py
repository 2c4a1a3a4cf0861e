"""Loop-thread busy share of the window in ``deliver.send`` (props, outbound window
entry, encode, egress feed) + ``egress.flush`` (one vectored write per
connection and loop turn; the publishers' PUBACK frames pass here too).
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "deliver + egress broker/session.py egress.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return busy_pct(run, ('deliver.send', 'egress.flush'))
