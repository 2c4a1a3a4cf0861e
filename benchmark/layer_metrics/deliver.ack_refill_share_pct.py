"""Share of the window's deliveries that the read task sent from the ack path
(``deliver.ack_refills`` over the ``deliver.send`` count): the credit a read
chunk's PUBACKs / PUBCOMPs freed on a session whose deliver loop was parked
on a full outbound QoS1 window, spent in the turn they were read. 0 where
deliveries went out and none came that way; absent where the broker has no
such counter (a program from before PR 37) or delivered nothing."""

from _counters import metric
from _stages import delta

SPEC = {"layer": "outbound QoS1 window broker/inflight.py", "unit": "%",
        "source": "program_counter", "moves": "deliver_p99_ms"}


def read(run: dict):
    refills, sent = metric(run, "deliver.ack_refills"), delta(run, "stage_deliver_send_count")
    return 100.0 * refills / sent if refills is not None and sent else None
