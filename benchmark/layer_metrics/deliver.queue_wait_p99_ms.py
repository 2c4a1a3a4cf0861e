"""99th percentile, over the WINDOW's samples only, of a delivery parked on its subscriber's deliver queue (``Session.enqueue`` → the
deliver loop's pop; a full outbound QoS1 window holds it there).
From the deltas of the histogram's cumulative log2 buckets: the value is the
upper edge of the bucket that holds the percentile (a power of two of ns), so
it is exact to a factor of 2. Absent without the buckets or without a sample."""

from _stages import p99_ms

SPEC = {"layer": "session deliver queue broker/queue.py", "unit": "ms",
        "source": "program_span", "moves": "deliver_p99_ms"}


def read(run: dict):
    return p99_ms(run, "deliver.queue_wait")
