"""99th percentile, over the WINDOW's samples only, of ``publish.e2e``: PUBLISH handed to the pipeline → its last forward enqueued
(what the publisher's PUBACK waits for).
From the deltas of the histogram's cumulative log2 buckets: the value is the
upper edge of the bucket that holds the percentile (a power of two of ns), so
it is exact to a factor of 2. Absent without the buckets or without a sample."""

from _stages import p99_ms

SPEC = {"layer": "ingress to last forward enqueued", "unit": "ms",
        "source": "program_span", "moves": "puback_p99_ms"}


def read(run: dict):
    return p99_ms(run, "publish.e2e")
