"""Times a second a deliver loop found its outbound QoS1 window full and waited
for a PUBACK to free a slot (``deliver.credit_wait`` count). 0 where deliveries
went out and none waited; absent where the broker has no such counter or
delivered nothing."""

from _stages import delta, window_s

SPEC = {"layer": "outbound QoS1 window broker/inflight.py", "unit": "1/s",
        "source": "program_counter", "moves": "deliver_p99_ms"}


def read(run: dict):
    waits, sent = delta(run, "stage_deliver_credit_wait_count"), delta(run, "stage_deliver_send_count")
    return waits / window_s(run) if waits is not None and sent else None
