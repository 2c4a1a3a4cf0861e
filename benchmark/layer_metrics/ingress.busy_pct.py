"""Loop-thread busy share of the window in ``ingress.decode`` (``codec.feed`` per
read chunk) + ``ingress.publish`` (topic check, hooks, ACL, retain: up to the
call of ``registry.forwards``).
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "ingress codec + admission broker/session.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return busy_pct(run, ('ingress.decode', 'ingress.publish'))
