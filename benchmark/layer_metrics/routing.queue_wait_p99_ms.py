"""99th percentile, over the WINDOW's samples only, of a publish parked on the routing service's ingress queue (enqueue → its batch's
dispatch).
From the deltas of the histogram's cumulative log2 buckets: the value is the
upper edge of the bucket that holds the percentile (a power of two of ns), so
it is exact to a factor of 2. Absent without the buckets or without a sample."""

from _stages import p99_ms

SPEC = {"layer": "routing service broker/routing.py", "unit": "ms",
        "source": "program_span", "moves": "deliver_p99_ms"}


def read(run: dict):
    return p99_ms(run, "routing.queue_wait")
