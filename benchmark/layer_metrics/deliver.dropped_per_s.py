"""Messages the broker dropped a second, all reasons summed
(``messages.dropped.<reason>``: ``queue_full``, ``shed_qos0``, ``expired``,
``no_session``, ...). Reads 0 in a sound run: a QoS1 cell whose comparison
finds every pair has dropped nothing it had acked."""

from _counters import dropped
from _stages import window_s

SPEC = {"layer": "session deliver queue broker/queue.py", "unit": "1/s",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    return dropped(run) / window_s(run)
